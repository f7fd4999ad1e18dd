"""JoyAI-LLM-Flash through the normal path at toy size on the CPU, against
the plain reference the benchmark uses (benchmarks/references/joyai.py —
this file imports that same file, there is no second copy): loss and
gradients with packed documents (the embedding and the head carrying both
of their uses), the share of an expert-parallel layer (its parts add up; a
row over the bound is counted), what it refuses by name, its sizes and the
lowered text of its rotary.  The engine's first step, each thing that
makes the model itself left out in turn and the scopes and accounts of a
toy step are tests/test_joyai_engine.py, on this file's toy model.

Where a test asks for ``real_kernels``, ``DS_GGEMM_INTERPRET=1`` runs the
real grouped GEMM kernels in Pallas' interpreter (elsewhere their jnp
form stands in: the same plan, faster to compile).  Everything is float32
with seeded weights: the two sides differ only in the order of
summation."""
import functools
import importlib.util
import json
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import joyai
from deepspeed_tpu.models.joyai import JoyAIConfig, count_params, joyai_model
from deepspeed_tpu.models.model import param_stream_scope
from deepspeed_tpu.moe import layer as moe_layer
from deepspeed_tpu.moe.layer import MoEConfig, init_moe_params
from deepspeed_tpu.telemetry import tracing
from tests.util import base_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "joyai_reference",
    os.path.join(REPO, "benchmarks", "references", "joyai.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

LOSS_TOL = 2e-5         # measured 0 to 4e-6
GRAD_TOL = 1e-4         # max |a - b| / max |b| per leaf; measured <= 6e-6

TOY = dict(num_layers=3, d_model=64, num_heads=4, q_lora_rank=48,
           kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, rope_theta=10000.0, d_ff_dense=96, d_ff=32,
           shared_expert_d_ff=32, num_experts=16, top_k=4, experts_held=4,
           expert_offset=8, vocab_size=512, max_seq_len=128,
           dtype="float32", remat=True)
GAS, B, S, DOCS = 2, 2, 48, 4


@pytest.fixture(autouse=True)
def _isolation():
    tracing.reset_programs()
    yield
    tracing.reset_programs()


@pytest.fixture
def real_kernels(monkeypatch):
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")


def toy_model(**overrides):
    return joyai_model("llm-flash", **{**TOY, **overrides})


def sizes_of(model):
    return {k: getattr(model.config, k) for k in reference.SIZES}


def seeded_params(model, seed=0):
    """Seeded weights at which every part matters: norm weights away from
    their start, router logits and attention scores wide, the selection
    bias off its start."""
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
        if name == "e_score_correction_bias":
            return 0.2 * jax.random.normal(sub, w.shape)
        if name in ("w_uq", "w_ukv", "w_dkv", "w_dq"):
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
    cuts[0, 0, :3] = (15, 16, 30)     # a one-token document
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


def _pop_biases(grads):
    return [grads["blocks"]["moe"].pop("e_score_correction_bias"),
            grads["mtp"]["block"]["moe"].pop("e_score_correction_bias")]


@pytest.mark.parametrize("held", ["a_share", "every_expert"])
def test_gradients_match_the_reference(held, real_kernels):
    model = toy_model(**({} if held == "a_share" else
                         dict(experts_held=None, expert_offset=0)))
    params, mb = seeded_params(model), micro(packed_batch())
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, mb)
        want, want_grads = jitted_reference_loss(model, grad=True)(params, mb)
    assert abs(float(loss) - float(want)) < LOSS_TOL
    for bias in _pop_biases(grads) + _pop_biases(want_grads):
        assert float(jnp.abs(bias).max()) == 0
    worst = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))),
        grads, want_grads)
    assert max(jax.tree.leaves(worst)) < GRAD_TOL, worst
    # every other leaf learns
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        assert float(jnp.abs(leaf).max()) > 0, jax.tree_util.keystr(path)


def test_the_embedding_and_the_head_carry_both_uses():
    """One leaf each, two uses: the gradient of ``wte`` and of ``lm_head``
    is the main model's plus the module's, and neither alone."""
    model, alone = toy_model(), toy_model(num_mtp_layers=0)
    params, mb = seeded_params(model), micro(packed_batch())
    assert set(params["mtp"]) == {"norm_h", "norm_e", "w_eh", "block",
                                  "final_norm"}
    main = {k: v for k, v in params.items() if k != "mtp"}
    with jax.default_matmul_precision("highest"):
        both = jax.jit(jax.grad(model.loss))(params, mb)
        first = jax.jit(jax.grad(alone.loss))(main, mb)
        _, want = jitted_reference_loss(model, grad=True)(params, mb)
    for leaf in ("wte", "lm_head"):
        scale = float(jnp.abs(want[leaf]).max())
        assert float(jnp.abs(both[leaf] - want[leaf]).max()) \
            < GRAD_TOL * scale
        second = both[leaf] - first[leaf]
        assert float(jnp.abs(second).max()) > 0.05 * scale, leaf
        assert float(jnp.abs(first[leaf]).max()) > 0.05 * scale, leaf


def test_the_modules_token_losses_match_the_reference():
    model = toy_model()
    params, mb = seeded_params(model), micro(packed_batch())
    got, scored = jax.jit(model.meta["mtp_token_losses"])(params, mb)
    want, want_scored = reference.mtp_token_losses(
        params, mb, sizes_of(model), chunk=1)
    np.testing.assert_array_equal(scored, want_scored)
    seg = np.asarray(mb["segment_ids"])
    # t, t+1 and t+2 of one document, inside the sequence
    by_hand = np.zeros_like(want_scored)
    by_hand[:, :-2] = (seg[:, :-2] == seg[:, 1:-1]) & (seg[:, :-2]
                                                       == seg[:, 2:])
    np.testing.assert_array_equal(want_scored, by_hand)
    assert 0 < want_scored.sum() < want_scored.size
    np.testing.assert_allclose(np.asarray(got)[want_scored],
                               want[want_scored], atol=2e-4)
    main, main_scored = reference.token_losses(params, mb, sizes_of(model),
                                               chunk=1)
    logits = jax.jit(model.apply)(params, mb)
    nll = jax.scipy.special.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, jnp.roll(mb["input_ids"], -1, 1)[..., None], -1)[..., 0]
    np.testing.assert_allclose(np.asarray(nll)[main_scored],
                               main[main_scored], atol=2e-4)


# ------------------------------------------------------- the share's sums
SHARE = MoEConfig(d_model=32, d_ff=16, num_experts=16, top_k=4,
                  dispatch_mode="grouped", load_balance="all_choices",
                  aux_loss_coef=1e-4, router="sigmoid",
                  routed_scaling_factor=2.5, activation="silu_glu",
                  shared_expert_d_ff=16)


def _held(params, offset, n):
    return {k: (w[offset:offset + n] if k in ("w_in", "w_out", "w_gate")
                else w) for k, w in params.items()}


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test on a whole expert block: the routed parts of
    all four shares (4 experts of 16 each) plus the shared expert and the
    attention counted once are the uncut block's output; the router loss
    is the same on every share."""
    cfg = JoyAIConfig(**{**{k: v for k, v in TOY.items()
                            if k not in ("experts_held", "expert_offset")}})
    layer = jax.tree.map(lambda a: a[0], seeded_params(
        toy_model(experts_held=None, expert_offset=0))["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64))
    whole, (aux, _) = jax.jit(lambda x, layer: joyai._expert_block(
        x, layer, cfg, train=True))(x, layer)
    attended = jax.jit(lambda x, layer: joyai._latent_attention(
        x, layer, cfg, None))(x, layer)
    no_shared = JoyAIConfig.moe.fget
    h = joyai._rms_norm(attended, layer["mlp_norm"], cfg.norm_eps)
    routed_only = replace(no_shared(cfg), shared_expert_d_ff=0)
    shared = moe_layer.moe_layer(layer["moe"], h, no_shared(cfg))[0] \
        - moe_layer.moe_layer(layer["moe"], h, routed_only)[0]
    total = attended + shared
    for i in range(4):
        part_cfg = replace(routed_only, expert_offset=4 * i, experts_held=4)
        part, aux_i, stats = moe_layer.moe_layer(
            _held(layer["moe"], 4 * i, 4), h, part_cfg, return_stats=True)
        assert int(stats["dropped"]) == 0
        assert float(aux_i) == pytest.approx(float(aux), rel=1e-5)
        total = total + part
    np.testing.assert_allclose(total, whole, atol=1e-5 * float(
        jnp.abs(whole).max()))


def test_a_share_allocates_its_own_experts_only():
    cfg = replace(SHARE, expert_offset=4, experts_held=4)
    shapes = jax.eval_shape(lambda k: init_moe_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert set(shapes) == {"router", "w_in", "w_out", "w_gate", "shared_in",
                           "shared_out", "shared_gate",
                           "e_score_correction_bias"}
    assert shapes["router"].shape == (32, 16)
    assert shapes["e_score_correction_bias"].shape == (16,)
    assert shapes["w_gate"].shape == shapes["w_in"].shape == (4, 32, 16)
    assert shapes["w_out"].shape == (4, 16, 32)
    model = toy_model()
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert tree["blocks"]["moe"]["w_in"].shape == (2, 4, 64, 32)
    assert tree["blocks"]["moe"]["router"].shape == (2, 64, 16)
    assert tree["mtp"]["block"]["moe"]["w_in"].shape == (4, 64, 32)
    assert "wte" not in tree["mtp"] and "lm_head" not in tree["mtp"]


def test_a_row_over_the_bound_is_counted(monkeypatch):
    """A plan too short for the rows the router sends here: the rest is
    counted, the model's loss comes with the sum over its expert layers,
    the module's among them, and the engine adds it up."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    monkeypatch.setattr(gg, "default_block_m", lambda: 8)
    monkeypatch.setattr(gg, "held_rows_bound", lambda *a, **k: 16)
    model = toy_model(remat=False)
    params, mb = seeded_params(model), micro(packed_batch())
    _, counts = jax.jit(model.loss_with_counts_fn)(params, mb)
    main_only = toy_model(remat=False, num_mtp_layers=0)
    _, fewer = jax.jit(main_only.loss_with_counts_fn)(
        {k: v for k, v in params.items() if k != "mtp"}, mb)
    assert int(counts["moe/rows_over_bound"]) \
        > int(fewer["moe/rows_over_bound"]) > 0
    assert "callback" not in jax.jit(model.loss).lower(params, mb).as_text()
    engine, *_ = deepspeed_tpu.initialize(
        model=toy_model(), config=base_config(
            train_micro_batch_size_per_gpu=B,
            gradient_accumulation_steps=GAS, seed=3), mesh=one_device())
    engine.train_batch(batch=packed_batch())
    assert engine.step_counts()["moe/rows_over_bound"] > 0


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
    with pytest.raises(NotImplementedError, match="absorbed form"):
        getattr(toy_model(), entry)(None, None, None)


def test_the_size_is_the_published_one_and_the_cut_is_the_files():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "joyai-llm-flash.json")) as f:
        config = json.load(f)
    whole = JoyAIConfig()
    assert count_params(whole) == config["published"]["n_params"] \
        == 50_190_491_648
    assert count_params(replace(whole, num_mtp_layers=0)) \
        == config["published"]["n_params_main"] == 48_942_542_592
    assert (whole.qk_head_dim, whole.v_head_dim, whole.expert_layers) \
        == (192, 128, 39)
    model = joyai_model(**config["builder"]["kwargs"])
    for key, want in config["model"].items():
        have = model.meta[key] if key == "n_params" \
            else getattr(model.config, key)
        assert have == want, key
    assert model.meta["n_params"] == 680_441_088
    # every width under the source's own key
    cut = model.config
    assert (cut.d_model, cut.num_heads, cut.q_lora_rank, cut.kv_lora_rank,
            cut.qk_nope_head_dim, cut.qk_rope_head_dim, cut.v_head_dim,
            cut.d_ff_dense, cut.d_ff, cut.top_k, cut.num_experts) == tuple(
        config[k] for k in (
            "hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "moe_intermediate_size",
            "num_experts_per_tok")) + (
                config["published"]["n_routed_experts"],)
    assert (cut.rope_theta, cut.norm_eps, cut.routed_scaling_factor,
            cut.num_mtp_layers) == (
        config["rope_theta"], config["rms_norm_eps"],
        config["routed_scaling_factor"], config["num_nextn_predict_layers"])
    assert config["first_k_dense_replace"] == 1     # the one leading block
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    moe = shapes["blocks"]["moe"]
    assert moe["router"].shape == (4, 2048, 256)
    assert moe["w_gate"].shape == moe["w_in"].shape == (4, 16, 2048, 768)
    assert moe["w_out"].shape == (4, 16, 768, 2048)
    assert shapes["dense"]["w_gate"].shape == (2048, 7168)
    assert shapes["blocks"]["w_uq"].shape == (4, 1536, 32 * 192)
    assert shapes["blocks"]["w_dkv"].shape == (4, 2048, 512 + 64)
    assert shapes["blocks"]["w_ukv"].shape == (4, 512, 32 * 256)
    assert shapes["mtp"]["w_eh"].shape == (4096, 2048)
    assert shapes["wte"].shape == (16160, 2048)
    with pytest.raises(ValueError, match="one leading dense layer"):
        JoyAIConfig(num_layers=1)
    with pytest.raises(ValueError, match="0 or 1"):
        JoyAIConfig(num_mtp_layers=2)


def _assembled_latent_attention(x, layer, config, segment_ids):
    """``joyai.latent_attention`` as it stood before PR 57, the oracle of
    the test below: ``kv = c_kv W_ukv`` whole, ``k`` joined from each
    head's own lanes and a copy a head of the one rotary key, ``v``
    sliced."""
    from deepspeed_tpu.models.llama import _rms_norm
    from deepspeed_tpu.models.model import qdot
    B, S, _ = x.shape
    H, rkv = config.num_heads, config.kv_lora_rank
    nope, rot, vd = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                     config.v_head_dim)
    eps = config.norm_eps
    h = _rms_norm(x, layer["attn_norm"], eps)
    c_q = _rms_norm(qdot(h, layer["w_dq"]), layer["q_norm"], eps)
    q = qdot(c_q, layer["w_uq"]).reshape(B, S, H, nope + rot)
    ckv = qdot(h, layer["w_dkv"])
    c_kv = _rms_norm(ckv[..., :rkv], layer["kv_norm"], eps)
    kv = qdot(c_kv, layer["w_ukv"]).reshape(B, S, H, nope + vd)
    q, k_r = joyai._rotary(q, jnp.expand_dims(ckv[..., rkv:], 2), config)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (B, S, H, rot))], axis=-1)
    attn = joyai.causal_attention(q, k, kv[..., nope:],
                                  impl=config.attention_impl,
                                  segment_ids=segment_ids)
    return qdot(attn.reshape(B, S, H * vd), layer["w_o"])


def handed_to_the_kernels(fn, x, layer, config, segment_ids):
    """-> (``fn``'s output, the ``k`` and the ``v`` it handed
    ``joyai.causal_attention``); scripts/latent_attention_table.py asks
    the same on the chip."""
    handed = []
    attention = joyai.causal_attention

    def noting(q, k, v, **kwargs):
        handed.append((k, v))
        return attention(q, k, v, **kwargs)

    joyai.causal_attention = noting
    try:
        out = fn(x, layer, config, segment_ids)
    finally:
        joyai.causal_attention = attention
    return (out, *handed[-1])


@pytest.mark.parametrize("packed", [False, True], ids=["whole", "packed"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_k_and_v_leave_their_products_as_the_assembled_ones(dtype, packed):
    """``k = [c_kv | k_r] [W_uk 0 ; 0 I]`` and ``v = c_kv W_uv`` (PR 57)
    against the assembled form: a value times one plus exact zeros is that
    value, so in bfloat16 — what the configurations run — the kernels are
    handed the same to the bit, and the layer's output is the same; in
    float32 the rotary lanes and ``v`` are, and each head's own lanes are
    to the last place (this CPU's dot sums 40 terms in another order than
    32: 1.3e-7 of the largest, measured).  The cotangents go back through
    the products' transposes (the sum of ``dk_r`` over the heads in the
    accumulator, no pad-and-add), so the gradients agree to rounding."""
    exact = dtype == "bfloat16"
    config = JoyAIConfig(**{k: v for k, v in TOY.items()
                            if k not in ("dtype", "remat")})
    dt = jnp.dtype(dtype)
    # wide scores and norm weights away from their start, as seeded_params
    layer = joyai._attn_params(config, jax.random.PRNGKey(3))
    for i, (name, w) in enumerate(layer.items()):
        if w.ndim == 1:
            layer[name] = w + 0.3 * jax.random.normal(jax.random.PRNGKey(i),
                                                      w.shape)
        else:
            layer[name] = (w * (1.0 if name == "w_o" else 12.0)).astype(dt)
    x = jax.random.normal(jax.random.PRNGKey(4),
                          (B, S, TOY["d_model"])).astype(dt)
    seg = micro(packed_batch())["segment_ids"] if packed else None
    forms = (joyai.latent_attention, _assembled_latent_attention)
    (out, k, v), (want, want_k, want_v) = (
        [np.asarray(a) for a in handed_to_the_kernels(fn, x, layer, config,
                                                      seg)] for fn in forms)
    H, nope, rot, vd = (TOY["num_heads"], TOY["qk_nope_head_dim"],
                        TOY["qk_rope_head_dim"], TOY["v_head_dim"])
    assert k.dtype == want_k.dtype == dt and v.dtype == want_v.dtype == dt
    assert k.shape == (B, S, H, nope + rot) and v.shape == (B, S, H, vd)
    np.testing.assert_array_equal(k[..., nope:], want_k[..., nope:])
    np.testing.assert_array_equal(v, want_v)
    if exact:
        np.testing.assert_array_equal(k, want_k)
        np.testing.assert_array_equal(out, want)
    else:
        assert np.abs(k - want_k).max() < 4e-7 * np.abs(want_k).max()
        assert np.abs(out - want).max() < 1e-5 * np.abs(want).max()

    def loss(fn, layer, x):
        return jnp.sum(fn(x, layer, config, seg).astype(jnp.float32) ** 2)

    got, ref = (jax.jit(jax.value_and_grad(functools.partial(loss, fn),
                                           (0, 1)))(layer, x)
                for fn in forms)
    assert abs(float(got[0]) - float(ref[0])) <= (
        0 if exact else LOSS_TOL * float(ref[0]))
    worst = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))
                           / jnp.max(jnp.abs(b.astype(jnp.float32)))),
        got[1], ref[1])
    # bfloat16: one rounding of a cotangent is 2 ** -8 of it
    assert max(jax.tree.leaves(worst)) < (2 ** -6 if exact else GRAD_TOL), \
        worst


@pytest.mark.parametrize("family", ["joyai", "xing"])
def test_no_array_of_ks_size_is_joined_spread_or_padded_in_the_lowered_toy_step(
        family):
    """Beside the test of ``q`` below, the same lowered text by scope:
    under ``ds.block/attn/kv_latent`` and ``.../scores`` no
    ``concatenate``, ``broadcast_in_dim`` or ``pad`` results in a ``[B, S,
    H, ·]`` array — before PR 57 ``k`` was a concatenate of each head's
    lanes and a broadcast of the shared rotary key, and the backward
    rebuilt ``dkv`` by a pad and an add — in any pass of the block:
    forward, recompute and backward, the main stack's and the module's.
    models/xing.py runs the same function under its own rotary."""
    import re
    if family == "joyai":
        model = toy_model()
    else:
        from tests.test_xing import toy_model as xing_toy_model
        model = xing_toy_model()
    H = TOY["num_heads"]
    text = jax.jit(jax.value_and_grad(model.loss)).lower(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)),
        micro(packed_batch())).as_text(debug_info=True)
    scope_of = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    whole = re.compile(rf"tensor<{B}x{S}x{H}x\d+x\w+>")
    seen = {"kv_latent": 0, "scores": 0}
    products = 0
    for line in text.splitlines():
        m = re.search(r'= "?(?:stablehlo|chlo)\.(\w+).* loc\((#loc\d+)\)$',
                      line)
        scope = m and re.search(r"ds\.block/attn/(kv_latent|scores)/",
                                scope_of.get(m.group(2), ""))
        if not scope:
            continue
        op, result = m.group(1), line.split("->")[-1]
        seen[scope.group(1)] += 1
        if whole.search(result):
            assert op not in ("concatenate", "broadcast_in_dim", "pad"), line
            products += op == "dot_general"
    # k and v leave a product each in every forward pass and recompute
    assert products >= 10, products
    assert seen["kv_latent"] > 100 and seen["scores"] > 100, seen


def test_no_stride_and_no_join_of_q_in_the_lowered_toy_step():
    """The lowered text of the toy step's forward and backward (tests/
    flash_step_texts.py: ``value_and_grad(loss)``, remat on), by the scope
    of each instruction: under ``ds.block/attn/rope`` and
    ``.../q_latent`` no slice has a stride other than 1, nothing gathers
    or scatters (what ``x[..., 0::2]`` is before XLA sees it, and what its
    transpose is), and no concatenate under ``q_latent`` builds a
    ``[B, S, H, nope + rot]`` array — ``q`` leaves its up-projection whole
    and turns in place (PR 45).  Every pass of the block is looked at: the
    main stack's and the module's, forward, recompute and backward."""
    import re
    model = toy_model()
    H, wide = TOY["num_heads"], (TOY["qk_nope_head_dim"]
                                 + TOY["qk_rope_head_dim"])
    mb = micro(packed_batch())
    text = jax.jit(jax.value_and_grad(model.loss)).lower(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)), mb).as_text(
            debug_info=True)
    scope_of = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    seen = {"rope": 0, "q_latent": 0}
    for line in text.splitlines():
        m = re.search(r'= "?(?:stablehlo|chlo)\.(\w+).* loc\((#loc\d+)\)$',
                      line)
        scope = m and re.search(r"ds\.block/attn/(rope|q_latent)/",
                                scope_of.get(m.group(2), ""))
        if not scope:
            continue
        op, where = m.group(1), scope.group(1)
        seen[where] += 1
        assert op not in ("gather", "scatter", "dynamic_slice"), line
        if op == "slice":
            # [a:b:stride, ...]: the stride is printed where it is not 1
            assert not re.search(r"\d+:\d+:\d+", line), line
        if op == "concatenate" and where == "q_latent":
            assert f"x{H}x{wide}x" not in line.split("->")[-1], line
    # both scopes were read, each in forward, recompute and backward
    assert seen["rope"] > 30 and seen["q_latent"] > 30, seen


