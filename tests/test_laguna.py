"""Laguna-S-2.1 through the normal path at toy size on the CPU, against the
plain reference the benchmark uses (benchmarks/references/laguna.py — this
file imports that same file, there is no second copy): loss and every
leaf's gradient with packed documents, the stack at any depth, the two
rotary tables, the share of an expert-parallel layer (its parts add up),
what it refuses by name and its sizes.  The engine's first step, each
thing that makes the model itself planted wrong in turn (the window, the
two rotary tables, YaRN, the per-head gate, the two head counts' groups)
and the scopes and accounts of a toy step are tests/test_laguna_engine.py,
on this file's toy model.

Where a test asks for ``real_kernels``, ``DS_GGEMM_INTERPRET=1`` runs the
real grouped GEMM kernels in Pallas' interpreter (elsewhere their jnp form
stands in: the same plan, faster to compile).  Everything is float32 with
seeded weights: the two sides differ only in the order of summation."""
import functools
import importlib.util
import json
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import laguna
from deepspeed_tpu.models.laguna import (FULL, SLIDING, LagunaConfig,
                                         count_params, laguna_model)
from deepspeed_tpu.models.model import param_stream_scope
from deepspeed_tpu.moe import layer as moe_layer
from deepspeed_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "laguna_reference",
    os.path.join(REPO, "benchmarks", "references", "laguna.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

LOSS_TOL = 2e-5         # measured 0 to 2e-6
GRAD_TOL = 1e-4         # max |a - b| / max |b| per leaf; measured <= 4e-6

#: the lead, one period (three sliding layers, a full one) and one sliding
#: layer left over; 6 and 9 query heads to a KV head as published
TOY = dict(num_layers=6, d_model=64, num_heads_full=12, num_heads_sliding=18,
           num_kv_heads=2, head_dim=16, sliding_window=8,
           original_max_position_embeddings=16, rope_factor=8.0,
           d_ff_dense=96, d_ff=32, shared_expert_d_ff=32, num_experts=8,
           top_k=3, experts_held=2, expert_offset=4, held_rows_factor=4,
           vocab_size=512, max_seq_len=128, dtype="float32", remat=True)
GAS, B, S, DOCS = 2, 2, 48, 3


@pytest.fixture(autouse=True)
def _isolation():
    tracing.reset_programs()
    yield
    tracing.reset_programs()


@pytest.fixture
def real_kernels(monkeypatch):
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")


def toy_model(**overrides):
    return laguna_model("s-2.1", **{**TOY, **overrides})


def sizes_of(model):
    return {k: getattr(model.config, k) for k in reference.SIZES}


def seeded_params(model, seed=0):
    """Seeded weights at which every part matters: norm weights away from
    their start, router logits, attention scores and gates wide."""
    params = model.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def push(path, w):
        nonlocal key
        key, sub = jax.random.split(key)
        name = path[-1].key
        if "norm" in name:
            return w + 0.3 * jax.random.normal(sub, w.shape)
        if name in ("router", "lm_head", "wg"):
            return w * 20.0
        if name in ("wq", "wk"):
            return w * 12.0
        if name == "wte":
            return w
        return w * 5.0

    return jax.tree_util.tree_map_with_path(push, params)


def packed_batch(seed=0, gas=GAS, docs=DOCS):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TOY["vocab_size"], size=(gas, B, S),
                       dtype=np.int32)
    cuts = np.sort(rng.integers(1, S, size=(gas, B, docs - 1)), axis=-1)
    cuts[0, 0, :2] = (15, 16)         # a one-token document
    segments = (np.arange(S)[None, None, :, None]
                >= cuts[:, :, None, :]).sum(-1).astype(np.int32)
    return {"input_ids": ids, "segment_ids": segments}


def micro(batch, g=0):
    return {k: jnp.asarray(v[g]) for k, v in batch.items()}


def reference_loss(params, mb, sizes):
    return reference.micro_batch_loss(
        params, mb["input_ids"], mb.get("segment_ids"), sizes, block=24)


def jitted_reference_loss(model, grad=False):
    """One compile where the eager form dispatches op by op."""
    fn = functools.partial(reference_loss, sizes=sizes_of(model))
    return jax.jit(jax.value_and_grad(fn) if grad else fn)


def one_device():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))


@functools.lru_cache(maxsize=None)
def seeded_toy(docs=DOCS):
    """(model, seeded weights, first micro-batch, the reference's loss
    there), made once a process: the right side of every planted fault."""
    model = toy_model()
    params, mb = seeded_params(model), micro(packed_batch(docs=docs))
    return model, params, mb, float(jitted_reference_loss(model)(params, mb))


@pytest.mark.parametrize("held", ["a_share", "every_expert"])
def test_gradients_match_the_reference(held, real_kernels):
    model = toy_model(**({} if held == "a_share" else
                         dict(experts_held=None, expert_offset=0)))
    params, mb = seeded_params(model), micro(packed_batch())
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, mb)
        want, want_grads = jitted_reference_loss(model, grad=True)(params, mb)
    assert abs(float(loss) - float(want)) < LOSS_TOL
    worst = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))),
        grads, want_grads)
    assert max(jax.tree.leaves(worst)) < GRAD_TOL, worst
    # every leaf learns: the gate's, both kinds', the tail's
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        assert float(jnp.abs(leaf).max()) > 0, jax.tree_util.keystr(path)
    assert set(grads) == {"wte", "lead", "blocks", "tail", "final_norm",
                          "lm_head"}


@pytest.mark.parametrize("layers", [2, 5, 9])
def test_any_depth_walks_the_stack_in_order(layers):
    """The lead alone with one sliding layer; one whole period; two periods
    (two full layers behind the lead's)."""
    model = toy_model(num_layers=layers, remat=False)
    params, mb = seeded_params(model), micro(packed_batch())
    kinds = [kind for kind, _ in laguna.layers_in_order(params, model.config)]
    assert kinds == [FULL if l % 4 == 0 else SLIDING
                     for l in range(1, layers)]
    want = float(jitted_reference_loss(model)(params, mb))
    assert abs(float(jax.jit(model.loss)(params, mb)) - want) < LOSS_TOL


def test_the_two_rotary_tables():
    """Sliding: plain, the whole head.  Full: half the head, the first
    frequencies theta^(-2i/rot) as they are, the last over the factor, a
    ramp between; the program's and the reference's are the same numbers."""
    whole = LagunaConfig()
    freqs, scale = laguna.rotary_table(whole, SLIDING)
    np.testing.assert_allclose(freqs, 10000.0 ** (-np.arange(64) / 64))
    assert scale == 1.0 and len(freqs) == 64
    freqs, scale = laguna.rotary_table(whole, FULL)
    plain = 500000.0 ** (-np.arange(32) / 32)
    assert len(freqs) == 32 and whole.rotary_ndims == 64
    assert scale == pytest.approx(0.1 * np.log(128) + 1, rel=1e-12)
    np.testing.assert_allclose(freqs[:10], plain[:10], rtol=1e-12)
    np.testing.assert_allclose(freqs[-8:], plain[-8:] / 128, rtol=1e-12)
    between = (freqs < plain * (1 - 1e-9)) & (freqs > plain / 128 * (1 + 1e-9))
    assert 5 < between.sum() < 20
    assert np.all(np.diff(freqs) < 0)
    sizes = {k: getattr(whole, k) for k in reference.SIZES}
    for full, kind in ((True, FULL), (False, SLIDING)):
        want, factor = reference.inverse_frequencies(sizes, full)
        got, scale = laguna.rotary_table(whole, kind)
        np.testing.assert_allclose(got, want, rtol=1e-12)
        assert factor == scale


# ------------------------------------------------------- the share's sums
def _held(params, offset, n):
    return {k: (w[offset:offset + n] if k in ("w_in", "w_out", "w_gate")
                else w) for k, w in params.items()}


@pytest.mark.parametrize("kind", [SLIDING, FULL])
def test_the_shares_add_up_to_the_uncut_layer(kind):
    """The guide's share test on a whole expert layer of each kind: the
    routed parts of all four shares (2 experts of 8 each) plus the shared
    expert and the attention counted once are the uncut layer's output;
    the router loss is the same on every share."""
    uncut = toy_model(experts_held=None, expert_offset=0)
    cfg = uncut.config
    layer = jax.tree.map(lambda a: a[0, 0],
                         seeded_params(uncut)["blocks"][kind])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64))
    whole, (aux, _) = jax.jit(lambda x, layer: laguna._expert_block(
        x, layer, cfg, kind, train=True))(x, layer)
    attended = jax.jit(lambda x, layer: laguna._attention(
        x, layer, cfg, kind, None))(x, layer)
    h = laguna._rms_norm(attended, layer["mlp_norm"], cfg.norm_eps)
    routed_only = replace(cfg.moe, shared_expert_d_ff=0)
    shared = moe_layer.moe_layer(layer["moe"], h, cfg.moe)[0] \
        - moe_layer.moe_layer(layer["moe"], h, routed_only)[0]
    total = attended + shared
    for i in range(4):
        part_cfg = replace(routed_only, expert_offset=2 * i, experts_held=2,
                           held_rows_factor=4)
        part, aux_i, stats = moe_layer.moe_layer(
            _held(layer["moe"], 2 * i, 2), h, part_cfg, return_stats=True)
        assert int(stats["dropped"]) == 0
        assert float(aux_i) == pytest.approx(float(aux), rel=1e-5)
        total = total + part
    np.testing.assert_allclose(total, whole, atol=1e-5 * float(
        jnp.abs(whole).max()))


def test_a_share_allocates_its_own_experts_only():
    tree = jax.eval_shape(toy_model().init, jax.random.PRNGKey(0))
    for kind, lead in ((SLIDING, (1, 3)), (FULL, (1, 1))):
        moe = tree["blocks"][kind]["moe"]
        assert set(moe) == {"router", "w_in", "w_out", "w_gate", "shared_in",
                            "shared_out", "shared_gate"}
        assert moe["router"].shape == lead + (64, 8)
        assert moe["w_gate"].shape == moe["w_in"].shape == lead + (2, 64, 32)
        assert moe["w_out"].shape == lead + (2, 32, 64)
    assert tree["tail"]["moe"]["w_in"].shape == (1, 2, 64, 32)
    # two head counts in one stack, one gate a head
    assert tree["blocks"][SLIDING]["wq"].shape == (1, 3, 64, 18 * 16)
    assert tree["blocks"][SLIDING]["wg"].shape == (1, 3, 64, 18)
    assert tree["blocks"][FULL]["wq"].shape == (1, 1, 64, 12 * 16)
    assert tree["blocks"][FULL]["wg"].shape == (1, 1, 64, 12)
    assert tree["lead"]["wq"].shape == (64, 12 * 16)
    assert tree["lead"]["wk"].shape == (64, 2 * 16)
    assert tree["lead"]["w_gate"].shape == (64, 96)


def test_a_row_over_the_bound_is_counted(monkeypatch):
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    monkeypatch.setattr(gg, "default_block_m", lambda: 8)
    monkeypatch.setattr(gg, "held_rows_bound", lambda *a, **k: 16)
    model = toy_model(remat=False)
    params, mb = seeded_params(model), micro(packed_batch())
    _, counts = jax.jit(model.loss_with_counts_fn)(params, mb)
    assert int(counts["moe/rows_over_bound"]) > 0
    rows = np.asarray(jax.jit(model.meta["routed_rows"])(params, mb))
    assert rows.shape == (5, 8) and (rows.sum(-1) == B * S * 3).all()


# ------------------------------------------------------- the rest of it
def test_zero3_and_streaming_refuse_clearly():
    model = toy_model()
    params, mb = model.init(jax.random.PRNGKey(0)), micro(packed_batch())
    with param_stream_scope(True, mode="gather"):
        with pytest.raises(NotImplementedError, match="ZeRO stage 0-2"):
            model.loss(params, mb)


@pytest.mark.parametrize("entry", ["init_cache_fn", "prefill_fn",
                                   "decode_fn", "verify_fn"])
def test_serving_entry_points_name_the_missing_piece(entry):
    with pytest.raises(NotImplementedError, match="sliding_window positions"):
        getattr(toy_model(), entry)(None, None, None)


def test_the_size_is_the_published_one_and_the_cut_is_the_files():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "laguna-s-2.1.json")) as f:
        config = json.load(f)
    whole = LagunaConfig()
    assert count_params(whole) == config["published"]["n_params"] \
        == 117_561_953_280
    # the published lists, layer by layer, are this config's two rules
    heads = [whole.heads(FULL if l % whole.full_attention_interval == 0
                         else SLIDING) for l in range(whole.num_layers)]
    assert heads == config["num_attention_heads_per_layer"]
    assert ["full_attention" if h == 48 else "sliding_attention"
            for h in heads] == config["layer_types"]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert set(config["gating_types"]) == {"per_head"}
    assert (whole.num_periods, whole.tail_layers) == (11, 3)
    model = laguna_model(**config["builder"]["kwargs"])
    for key, want in config["model"].items():
        have = model.meta[key] if key == "n_params" \
            else getattr(model.config, key)
        assert have == want, key
    assert model.meta["n_params"] == 811_017_216
    cut = model.config
    assert (cut.d_model, cut.num_heads_full, cut.num_kv_heads, cut.head_dim,
            cut.d_ff_dense, cut.d_ff, cut.shared_expert_d_ff, cut.top_k,
            cut.sliding_window, cut.num_experts) == tuple(
        config[k] for k in (
            "hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "moe_intermediate_size",
            "shared_expert_intermediate_size", "num_experts_per_tok",
            "sliding_window")) + (config["published"]["num_experts"],)
    full = config["rope_parameters"]["full_attention"]
    assert (cut.rope_theta, cut.rope_factor, cut.beta_fast, cut.beta_slow,
            cut.attention_factor, cut.partial_rotary_factor,
            cut.original_max_position_embeddings) == tuple(
        full[k] for k in ("rope_theta", "factor", "beta_fast", "beta_slow",
                          "attention_factor", "partial_rotary_factor",
                          "original_max_position_embeddings"))
    assert cut.sliding_rope_theta \
        == config["rope_parameters"]["sliding_attention"]["rope_theta"]
    assert (cut.norm_eps, cut.routed_scaling_factor) == (
        config["rms_norm_eps"], config["moe_routed_scaling_factor"])
    assert config["mlp_only_layers"] == [0]
    assert config["num_attention_heads_per_layer"][:5] \
        == [48, 72, 72, 72, 48]
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    moe = shapes["blocks"][SLIDING]["moe"]
    assert moe["router"].shape == (1, 3, 3072, 256)
    assert moe["w_gate"].shape == moe["w_in"].shape == (1, 3, 8, 3072, 1024)
    assert moe["w_out"].shape == (1, 3, 8, 1024, 3072)
    assert shapes["lead"]["w_gate"].shape == (3072, 12288)
    assert shapes["lead"]["wq"].shape == (3072, 48 * 128)
    assert shapes["blocks"][SLIDING]["wq"].shape == (1, 3, 3072, 72 * 128)
    assert shapes["blocks"][FULL]["wq"].shape == (1, 1, 3072, 48 * 128)
    assert shapes["blocks"][FULL]["wk"].shape == (1, 1, 3072, 8 * 128)
    assert shapes["wte"].shape == (12544, 3072)
    assert "tail" not in shapes
    with pytest.raises(ValueError, match="one leading dense layer"):
        LagunaConfig(num_layers=1)
    with pytest.raises(ValueError, match="whole groups"):
        LagunaConfig(num_heads_sliding=70)


