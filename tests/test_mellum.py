"""Mellum 2 through the normal path at toy size on the CPU, against the
plain reference the benchmark uses (benchmarks/references/mellum2.py — this
file imports that same file, there is no second copy): loss and every
leaf's gradient with packed documents, the stack at any depth, the two
rotary tables, the share of an expert-parallel layer (its parts add up to
the UNCUT reference's layer), each thing that makes the model itself
planted wrong in turn, the engine's first step on one device and on a
four-wide ``expert`` axis (the exchange), what it refuses by name and its
sizes.

Where a test asks for ``real_kernels``, ``DS_GGEMM_INTERPRET=1`` runs the
real grouped GEMM kernels in Pallas' interpreter.  Everything is float32
with seeded weights: the two sides differ only in the order of summation."""
import functools
import importlib.util
import json
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import laguna, mellum
from deepspeed_tpu.models.mellum import (FULL, SLIDING, MellumConfig,
                                         count_params, mellum_model)
from deepspeed_tpu.models.model import param_stream_scope
from deepspeed_tpu.moe import layer as moe_layer
from deepspeed_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "mellum2_reference",
    os.path.join(REPO, "benchmarks", "references", "mellum2.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

LOSS_TOL = 2e-5         # measured 0 to 3e-6
GRAD_TOL = 1e-4         # max |a - b| / max |b| per leaf; measured <= 5e-6

#: one period (three sliding layers, then the full one) and one sliding
#: layer left over; 8 query heads to a KV head as published
TOY = dict(num_layers=5, d_model=64, num_heads=16, num_kv_heads=2,
           head_dim=16, sliding_window=8, original_max_position_embeddings=16,
           rope_factor=8.0, d_ff=32, num_experts=8, top_k=3,
           held_rows_factor=4, vocab_size=512, max_seq_len=128,
           dtype="float32", remat=True)
GAS, B, S, DOCS = 2, 4, 48, 3


@pytest.fixture(autouse=True)
def _isolation():
    tracing.reset_programs()
    yield
    tracing.reset_programs()


@pytest.fixture
def real_kernels(monkeypatch):
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")


def toy_model(**overrides):
    return mellum_model("12b-a2.5b", **{**TOY, **overrides})


def sizes_of(model):
    return {k: getattr(model.config, k) for k in reference.SIZES}


def seeded_params(model, seed=0):
    """Seeded weights at which every part matters: norm weights away from
    their start, router logits and attention scores wide."""
    params = model.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def push(path, w):
        nonlocal key
        key, sub = jax.random.split(key)
        name = path[-1].key
        if "norm" in name:
            return w + 0.3 * jax.random.normal(sub, w.shape)
        if name in ("router", "lm_head"):
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


@functools.lru_cache(maxsize=None)
def seeded_toy():
    """(model, seeded weights, first micro-batch, the reference's loss
    there), made once a process: the right side of every planted fault."""
    model = toy_model()
    params, mb = seeded_params(model), micro(packed_batch())
    return model, params, mb, float(jitted_reference_loss(model)(params, mb))


def test_gradients_match_the_reference(real_kernels):
    model = toy_model()
    params, mb = seeded_params(model), micro(packed_batch())
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, mb)
        want, want_grads = jitted_reference_loss(model, grad=True)(params, mb)
    assert abs(float(loss) - float(want)) < LOSS_TOL
    worst = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))),
        grads, want_grads)
    assert max(jax.tree.leaves(worst)) < GRAD_TOL, worst
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        assert float(jnp.abs(leaf).max()) > 0, jax.tree_util.keystr(path)
    assert set(grads) == {"wte", "blocks", "tail", "final_norm", "lm_head"}


@pytest.mark.parametrize("layers", [1, 4, 8, 10])
def test_any_depth_walks_the_stack_in_order(layers):
    """A sliding layer alone; one whole period; two; two and a tail."""
    model = toy_model(num_layers=layers, remat=False)
    params, mb = seeded_params(model), micro(packed_batch())
    kinds = [kind for kind, _ in mellum.layers_in_order(params, model.config)]
    assert kinds == [FULL if l % 4 == 3 else SLIDING for l in range(layers)]
    want = float(jitted_reference_loss(model)(params, mb))
    assert abs(float(jax.jit(model.loss)(params, mb)) - want) < LOSS_TOL


def test_the_two_rotary_tables():
    """Both on the whole head at one base.  Sliding: plain.  Full: the
    first frequencies as they are, the last over the factor, a ramp
    between; cos and sin times 0.1 ln 16 + 1; the program's (Laguna's
    function) and the reference's own are the same numbers."""
    whole = MellumConfig()
    plain = 500000.0 ** (-np.arange(64) / 64)
    freqs, scale = laguna.rotary_table(whole, SLIDING)
    np.testing.assert_allclose(freqs, plain)
    assert scale == 1.0 and len(freqs) == 64
    freqs, scale = laguna.rotary_table(whole, FULL)
    assert len(freqs) == 64 and whole.rotary_ndims == 128
    assert scale == pytest.approx(0.1 * np.log(16) + 1, rel=1e-12)
    np.testing.assert_allclose(freqs[:18], plain[:18], rtol=1e-12)
    np.testing.assert_allclose(freqs[-28:], plain[-28:] / 16, rtol=1e-12)
    between = (freqs < plain * (1 - 1e-9)) & (freqs > plain / 16 * (1 + 1e-9))
    assert 10 < between.sum() < 20
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
    """The guide's share test on a whole layer of each kind: the routed
    parts of all four shares (2 experts of 8 each, ``experts_held`` /
    ``expert_offset`` alone, no exchange) plus the attention counted once
    are the uncut layer's output; the router loss is the same on every
    share.  (That the uncut layer is the plain reference's is
    ``test_gradients_match_the_reference``; that the exchanged layer is
    the uncut one, output and gradients, tests/test_moe_exchange.py.)"""
    uncut = toy_model()
    cfg = uncut.config
    layer = jax.tree.map(lambda a: a[0, 0],
                         seeded_params(uncut)["blocks"][kind])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64))
    whole, (aux, _) = jax.jit(lambda x, layer: mellum._block(
        x, layer, cfg, kind, train=True))(x, layer)
    attended = jax.jit(lambda x, layer: mellum._attention(
        x, layer, cfg, kind, None))(x, layer)
    h = mellum._rms_norm(attended, layer["mlp_norm"], cfg.norm_eps)
    total = attended
    for i in range(4):
        part_cfg = replace(cfg.moe, expert_offset=2 * i, experts_held=2)
        part, aux_i, stats = moe_layer.moe_layer(
            _held(layer["moe"], 2 * i, 2), h, part_cfg, return_stats=True)
        assert int(stats["dropped"]) == 0
        assert float(aux_i) == pytest.approx(float(aux), rel=1e-5)
        total = total + part
    np.testing.assert_allclose(total, whole, atol=1e-5 * float(
        jnp.abs(whole).max()))


def test_a_share_allocates_its_own_experts_only():
    tree = jax.eval_shape(toy_model(experts_held=2, expert_offset=4).init,
                          jax.random.PRNGKey(0))
    for kind, lead in ((SLIDING, (1, 3)), (FULL, (1, 1))):
        block = tree["blocks"][kind]
        assert set(block) == {"attn_norm", "wq", "wk", "wv", "wo",
                              "mlp_norm", "moe"}
        moe = block["moe"]
        assert set(moe) == {"router", "w_in", "w_out", "w_gate"}
        assert moe["router"].shape == lead + (64, 8)
        assert moe["w_gate"].shape == moe["w_in"].shape == lead + (2, 64, 32)
        assert moe["w_out"].shape == lead + (2, 32, 64)
        # one head count in both kinds, no gate
        assert block["wq"].shape == lead + (64, 16 * 16)
        assert block["wk"].shape == lead + (64, 2 * 16)
    assert tree["tail"]["moe"]["w_in"].shape == (1, 2, 64, 32)
    assert set(tree) == {"wte", "blocks", "tail", "final_norm", "lm_head"}


def test_a_row_over_the_bound_is_counted(monkeypatch):
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    monkeypatch.setattr(gg, "default_block_m", lambda: 8)
    monkeypatch.setattr(gg, "held_rows_bound", lambda *a, **k: 16)
    model = toy_model(remat=False, experts_held=2, expert_offset=4)
    params, mb = seeded_params(model), micro(packed_batch())
    _, counts = jax.jit(model.loss_with_counts_fn)(params, mb)
    assert int(counts["moe/rows_over_bound"]) > 0
    rows = np.asarray(jax.jit(model.meta["routed_rows"])(params, mb))
    assert rows.shape == (5, 8) and (rows.sum(-1) == B * S * 3).all()


# ----------------------------------------------- what makes it this model
def _attention_given(monkeypatch, change):
    """``causal_attention`` as the model calls it, its arguments changed."""
    real = mellum.causal_attention

    def patched(q, k, v, **kw):
        return real(*change(q, k, v, kw), **kw)

    monkeypatch.setattr(mellum, "causal_attention", patched)


def _a_window_on_the_full_layers(monkeypatch):
    def change(q, k, v, kw):
        kw["window"] = TOY["sliding_window"]
        return q, k, v
    _attention_given(monkeypatch, change)


def _no_window(monkeypatch):
    def change(q, k, v, kw):
        kw["window"] = None
        return q, k, v
    _attention_given(monkeypatch, change)


def _groups_interleaved(monkeypatch):
    """Query head n reads KV head n % KV, not n // (H / KV)."""
    def change(q, k, v, kw):
        rep = q.shape[2] // k.shape[2]
        return q, jnp.tile(k, (1, 1, rep, 1)), jnp.tile(v, (1, 1, rep, 1))
    _attention_given(monkeypatch, change)


def _rotary_tables_swapped(monkeypatch):
    real = mellum.rotary_table
    monkeypatch.setattr(
        mellum, "rotary_table",
        lambda config, kind: real(config, SLIDING if kind == FULL else FULL))


def _full_layer_first(monkeypatch):
    """The full layer first in its period (Laguna's order), not last."""
    monkeypatch.setattr(MellumConfig, "pattern", property(
        lambda self: (FULL,) + (SLIDING,) * (
            self.full_attention_interval - 1)))


FAULTS = {
    "window_one_short": (None, dict(sliding_window=7)),
    "window_one_long": (None, dict(sliding_window=9)),
    "no_window": (_no_window, {}),
    "a_window_on_the_full_layers": (_a_window_on_the_full_layers, {}),
    "full_layer_first_in_its_period": (_full_layer_first, {}),
    "rotary_tables_swapped": (_rotary_tables_swapped, {}),
    "yarn_factor_left_out": (None, dict(rope_factor=1.0)),
    "attention_factor_left_out": (None, dict(attention_factor=1.0)),
    "theta_1e4": (None, dict(rope_theta=1e4, sliding_rope_theta=1e4)),
    "groups_interleaved": (_groups_interleaved, {}),
    "top_k_not_renormalised": (None, dict(norm_topk_prob=False)),
    "top_k_one_fewer": (None, dict(top_k=2)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_outside_the_tolerance(fault, monkeypatch):
    patch, overrides = FAULTS[fault]
    _, params, mb, want = seeded_toy()
    if patch:
        patch(monkeypatch)
    model = toy_model(**overrides)
    got = float(jax.jit(model.loss)(params, mb))
    assert abs(got - want) > 50 * LOSS_TOL, (got, want)


def test_with_nothing_planted_the_same_comparison_holds():
    model, params, mb, want = seeded_toy()
    assert abs(float(jax.jit(model.loss)(params, mb)) - want) < LOSS_TOL


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
                           "mellum2-12b-a2.5b-ep4.json")) as f:
        config = json.load(f)
    whole = MellumConfig()
    assert count_params(whole) == config["published"]["n_params"] \
        == 28 * 417_747_456 + 452_984_832 + 2304
    kinds = ["full_attention" if l % 4 == 3 else "sliding_attention"
             for l in range(whole.num_layers)]
    assert kinds == config["layer_types"]
    assert config["mlp_layer_types"] == ["sparse"] * 28
    assert (whole.num_periods, whole.tail_layers) == (7, 0)
    model = mellum_model(**config["builder"]["kwargs"])
    for key, want in config["model"].items():
        have = model.meta[key] if key == "n_params" \
            else getattr(model.config, key)
        assert have == want, key
    assert model.meta["n_params"] == 4 * 417_747_456 + 452_987_136 \
        == 2_123_976_960
    cut = model.config
    assert cut.experts_held is None and cut.num_layers == 4
    assert (cut.d_model, cut.num_heads, cut.num_kv_heads, cut.head_dim,
            cut.d_ff, cut.top_k, cut.sliding_window, cut.num_experts,
            cut.vocab_size) == tuple(config[k] for k in (
                "hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "moe_intermediate_size", "num_experts_per_tok",
                "sliding_window", "num_experts", "vocab_size"))
    full = config["rope_parameters"]["full_attention"]
    assert (cut.rope_theta, cut.rope_factor, cut.beta_fast, cut.beta_slow,
            cut.attention_factor,
            cut.original_max_position_embeddings) == tuple(
        full[k] for k in ("rope_theta", "factor", "beta_fast", "beta_slow",
                          "attention_factor",
                          "original_max_position_embeddings"))
    assert cut.sliding_rope_theta \
        == config["rope_parameters"]["sliding_attention"]["rope_theta"]
    assert (cut.norm_eps, cut.norm_topk_prob) == (
        config["rms_norm_eps"], config["norm_topk_prob"])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    moe = shapes["blocks"][SLIDING]["moe"]
    assert moe["router"].shape == (1, 3, 2304, 64)
    assert moe["w_gate"].shape == moe["w_in"].shape == (1, 3, 64, 2304, 896)
    assert moe["w_out"].shape == (1, 3, 64, 896, 2304)
    assert shapes["blocks"][FULL]["wq"].shape == (1, 1, 2304, 32 * 128)
    assert shapes["blocks"][FULL]["wk"].shape == (1, 1, 2304, 4 * 128)
    assert shapes["wte"].shape == (98304, 2304)
    assert "tail" not in shapes
    with pytest.raises(ValueError, match="whole groups"):
        MellumConfig(num_heads=30)
