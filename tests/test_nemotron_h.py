"""Nemotron-H through the normal path at toy size on the CPU, against the
plain reference the benchmark uses (benchmarks/references/nemotron_h.py —
this file imports that same file, there is no second copy): loss and
gradients with packed documents, each thing that makes the model itself
left out in turn, the share of an expert-parallel layer (its parts add
up; a row over the bound is counted), the router's selection bias, and
what stays as it was for the families with a softmax router and SwiGLU
experts.

``DS_GGEMM_INTERPRET=1`` runs the real grouped GEMM kernels in Pallas'
interpreter.  Everything is float32 with seeded weights: the two sides
differ only in the order of summation and in the form of the state-space
scan (chunked here, per token there).

The toy, its seeded weights, the reference's loss and gradients and the
model's own are made once a process (``functools.lru_cache``) and every
test reads them; a departure runs only the departed side.  The tests that
build an engine are ``tests/test_nemotron_h_engine.py``, so that ``--dist
loadfile`` gives the family's tests to two workers."""
import functools
import importlib.util
import json
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.mixtral import mixtral_model
from deepspeed_tpu.models.model import param_stream_scope
from deepspeed_tpu.models.nemotron_h import (NemotronHConfig, count_params,
                                             nemotron_h_model)
from deepspeed_tpu.models.qwen3_next import qwen3_next_model
from deepspeed_tpu.moe import layer as moe_layer
from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.moe.layer import MoEConfig, init_moe_params
from deepspeed_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "nemotron_h_reference",
    os.path.join(REPO, "benchmarks", "references", "nemotron_h.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

LOSS_TOL = 2e-5         # measured 0 to 3e-6
GRAD_TOL = 1e-4         # max |a - b| / max |b| per leaf; measured <= 2e-5

TOY = dict(num_layers=5, hybrid_override_pattern="MEM*E", d_model=64,
           num_heads=4, num_kv_heads=2, head_dim=32, mamba_num_heads=8,
           mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=16,
           d_ff=32, shared_expert_d_ff=64, num_experts=16, top_k=4,
           experts_held=4, expert_offset=8, vocab_size=512, max_seq_len=128,
           dtype="float32", remat=True)
GAS, B, S, DOCS = 2, 2, 72, 4


@pytest.fixture(autouse=True)
def real_kernels(monkeypatch):
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    tracing.reset_programs()
    yield
    tracing.reset_programs()


def toy_model(**overrides):
    return nemotron_h_model("3-nano-30b-a3b", **{**TOY, **overrides})


def sizes_of(model):
    return {k: getattr(model.config, k) for k in reference.SIZES}


def seeded_params(model, seed=0):
    """Seeded weights at which every part matters: norm weights away from
    their start, router logits wide, the skip term, the convolution's bias
    and the selection bias off their start."""
    params = model.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def push(path, w):
        nonlocal key
        key, sub = jax.random.split(key)
        name = path[-1].key
        if name.endswith("norm") or name in ("D", "conv_b"):
            return w + 0.3 * jax.random.normal(sub, w.shape)
        if name in ("router", "lm_head"):
            return w * 20.0
        if name in ("wq", "wk"):
            return w * 10.0
        if name == "e_score_correction_bias":
            return 0.2 * jax.random.normal(sub, w.shape)
        if name in ("w_gate", "w_in", "w_out", "shared_gate", "shared_in",
                    "shared_out", "conv_w", "wv", "wo"):
            return w * 4.0
        return w

    return jax.tree_util.tree_map_with_path(push, params)


def packed_batch(seed=0, gas=GAS):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TOY["vocab_size"], size=(gas, B, S),
                       dtype=np.int32)
    cuts = np.sort(rng.integers(1, S, size=(gas, B, DOCS - 1)), axis=-1)
    cuts[0, 0] = (15, 16, 48)     # a one-token document at a chunk's edge
    segments = (np.arange(S)[None, None, :, None]
                >= cuts[:, :, None, :]).sum(-1).astype(np.int32)
    return {"input_ids": ids, "segment_ids": segments}


def micro(batch, g=0):
    return {k: jnp.asarray(v[g]) for k, v in batch.items()}


def reference_loss(params, mb, sizes):
    return reference.micro_batch_loss(
        params, mb["input_ids"], mb.get("segment_ids"), sizes, block=36)


def one_device():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))


@functools.lru_cache(maxsize=None)
def toy(held="a_share"):
    """(model, seeded weights, first micro-batch, the model's jitted loss
    and gradients) of the toy that holds a share or every expert."""
    model = toy_model(**({} if held == "a_share" else
                         dict(experts_held=None, expert_offset=0)))
    return (model, seeded_params(model), micro(packed_batch()),
            jax.jit(jax.value_and_grad(model.loss)))


@functools.lru_cache(maxsize=None)
def reference_numbers(held="a_share"):
    """The reference's loss and gradients at :func:`toy`'s weights and
    batch."""
    model, params, mb, _ = toy(held)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(functools.partial(
            reference_loss, sizes=sizes_of(model))))(params, mb)


def reference_loss_without_reset():
    """The loss of the reference that never resets at a document's start,
    at the same weights and batch."""
    model, params, mb, _ = toy()
    return jax.jit(functools.partial(reference_loss, sizes=sizes_of(model)))(
        params, {"input_ids": mb["input_ids"]})


@pytest.mark.parametrize("held", ["a_share", "every_expert"])
def test_gradients_match_the_reference(held):
    _, params, mb, loss_and_grads = toy(held)
    with jax.default_matmul_precision("highest"):
        loss, grads = loss_and_grads(params, mb)
    # copies down to the leaves' dict: the bias is popped, and the
    # reference's numbers are every test's
    want, want_grads = jax.tree.map(lambda x: x, reference_numbers(held))
    assert abs(float(loss) - float(want)) < LOSS_TOL
    bias = lambda g: g["blocks"]["experts"]["moe"].pop(
        "e_score_correction_bias")
    assert float(jnp.abs(bias(grads)).max()) == 0
    assert float(jnp.abs(bias(want_grads)).max()) == 0
    worst = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))),
        grads, want_grads)
    assert max(jax.tree.leaves(worst)) < GRAD_TOL, worst
    # every other leaf learns
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        assert float(jnp.abs(leaf).max()) > 0, jax.tree_util.keystr(path)


# ------------------------------------------------- the router's two forms
SHARE = MoEConfig(d_model=32, d_ff=16, num_experts=16, top_k=4,
                  dispatch_mode="grouped", load_balance="all_choices",
                  aux_loss_coef=1e-4, router="sigmoid",
                  routed_scaling_factor=2.5, activation="relu2",
                  shared_expert_d_ff=32)


def _share_setup():
    params = jax.tree.map(lambda a: a * 20,
                          init_moe_params(SHARE, jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    return params, x


def _held(params, offset, n):
    return {k: (w[offset:offset + n] if k in ("w_in", "w_out") else w)
            for k, w in params.items()}


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts of all four shares (4
    experts of 16 each) plus the shared expert counted once are the uncut
    layer's output; the router loss is the same on every share.  (A
    mixer is a layer of its own here and is in no share.)"""
    params, x = _share_setup()
    whole, aux = moe_layer.moe_layer(params, x, SHARE)
    routed_only = replace(SHARE, shared_expert_d_ff=0)
    shared = whole - moe_layer.moe_layer(params, x, routed_only)[0]
    total = shared
    for i in range(4):
        cfg = replace(routed_only, expert_offset=4 * i, experts_held=4)
        part, aux_i, stats = moe_layer.moe_layer(
            _held(params, 4 * i, 4), x, cfg, return_stats=True)
        assert int(stats["dropped"]) == 0
        assert float(aux_i) == pytest.approx(float(aux), rel=1e-6)
        total = total + part
    np.testing.assert_allclose(total, whole, atol=1e-5 * float(
        jnp.abs(whole).max()))


@pytest.mark.parametrize("dispatch", ["einsum", "grouped"])
def test_the_selection_bias_moves_the_choice_and_not_the_weights(dispatch):
    """A bias that lifts experts 12..15 over the rest: every token then
    chooses those four, its weights are still their bare scores over their
    sum times the scaling factor, and no gradient reaches the bias."""
    params, x = _share_setup()
    cfg = replace(SHARE, dispatch_mode=dispatch, capacity_factor=16.0)
    lifted = {**params, "e_score_correction_bias":
              jnp.where(jnp.arange(16) >= 12, 5.0, 0.0)}
    logits = moe_layer._routing_logits(params, x.reshape(-1, 32), cfg)
    plain = moe_layer._route(params, logits, cfg, True, None)
    moved = moe_layer._route(lifted, logits, cfg, True, None)
    assert set(np.unique(moved.expert_idx)) == {12, 13, 14, 15}
    assert set(np.unique(plain.expert_idx)) > {12, 13, 14, 15}
    scores = jax.nn.sigmoid(logits)[:, 12:]
    want = scores / scores.sum(-1, keepdims=True) * 2.5
    order = jnp.argsort(moved.expert_idx, axis=1)
    np.testing.assert_allclose(
        jnp.take_along_axis(moved.gate_weights, order, 1), want, rtol=1e-5)
    out = lambda p: jnp.sum(moe_layer.moe_layer(p, x, cfg)[0] ** 2)
    assert float(out(lifted)) != float(out(params))
    grads = jax.grad(out)(lifted)
    assert float(jnp.abs(grads["e_score_correction_bias"]).max()) == 0
    assert float(jnp.abs(grads["router"]).max()) > 0


def test_softmax_is_what_it_was_and_sigmoid_is_refused_by_name():
    logits = jax.random.normal(jax.random.PRNGKey(0), (12, 8))
    a = sharded_moe.topk_routing(logits, 2)
    b = sharded_moe.topk_routing(logits, 2, router="softmax",
                                 selection_bias=None, scale=1.0)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    with pytest.raises(ValueError, match="softmax"):
        sharded_moe.topk_routing(logits, 2, router="tanh")


def test_a_share_allocates_its_own_experts_only_and_no_gate_matrix():
    cfg = replace(SHARE, expert_offset=4, experts_held=4)
    shapes = jax.eval_shape(lambda k: init_moe_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert set(shapes) == {"router", "w_in", "w_out", "shared_in",
                           "shared_out", "e_score_correction_bias"}
    assert set(moe_layer.moe_logical_specs(cfg)) == set(shapes)
    assert shapes["router"].shape == (32, 16)
    assert shapes["e_score_correction_bias"].shape == (16,)
    assert shapes["w_in"].shape == (4, 32, 16)
    assert shapes["w_out"].shape == (4, 16, 32)


def test_a_row_over_the_bound_is_counted(monkeypatch):
    """A plan too short for the rows the router sends here: the rest is
    counted, and the model's loss comes with the sum over its expert
    layers alone."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    monkeypatch.setattr(gg, "default_block_m", lambda: 8)
    monkeypatch.setattr(gg, "held_rows_bound", lambda *a, **k: 16)
    model = toy_model(remat=False)
    params, mb = seeded_params(model), micro(packed_batch())
    cfg = model.config.moe
    h = jax.random.normal(jax.random.PRNGKey(0), (B, S, 64))
    layer = jax.tree.map(lambda w: w[0, 0],
                         params["blocks"]["experts"]["moe"])
    _, _, stats = moe_layer.moe_layer(layer, h, cfg, return_stats=True)
    assert int(stats["dispatched"]) <= 16 + 4 * 8       # the plan
    assert int(stats["dropped"]) > 0
    eids = moe_layer._route(layer, moe_layer._routing_logits(
        layer, h.reshape(-1, 64), cfg), cfg, True, None).expert_idx
    here = int(jnp.sum((eids >= 8) & (eids < 12)))
    assert int(stats["dropped"]) + int(stats["dispatched"]) == here
    _, counts = jax.jit(model.loss_with_counts_fn)(params, mb)
    assert int(counts["moe/rows_over_bound"]) > int(stats["dropped"])
    assert "callback" not in jax.jit(model.loss).lower(params, mb).as_text()


def test_the_held_rows_factor_sizes_the_plan():
    """``held_rows_factor`` times the even share, where the default's
    twice is too little for this router at initialisation (PERF.md section
    6, PR 34): the bound, the plan and the account follow it, and the
    default is the plan it was."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    assert gg.held_rows_bound(98304, 8, 128) == 12288
    assert gg.held_rows_bound(98304, 8, 128, factor=3) == 18432
    assert MoEConfig(d_model=8, d_ff=8).held_rows_factor == 2
    model = toy_model(held_rows_factor=3, remat=False)
    assert model.config.moe.held_rows_factor == 3
    params, mb = seeded_params(model), micro(packed_batch())
    with tracing.step_account("test/factor"):
        jax.eval_shape(model.loss, params, mb)
    rows = tracing.grouped_gemm_rows("test/factor")
    T, k = B * S, TOY["top_k"]
    bound = -(-(3 * T * k * 4 // 16) // 128) * 128
    assert rows["held_rows_bound"] == bound
    assert rows["padded_rows_per_call"] == bound + 4 * 128


def _lowered(model):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {"input_ids": jnp.zeros((2, 64), jnp.int32),
             "segment_ids": jnp.zeros((2, 64), jnp.int32)}
    return jax.jit(jax.value_and_grad(model.loss)).lower(
        shapes, batch).as_text()


def _olmoe_toy():
    return mixtral_model(
        size="olmoe-1b-7b", num_layers=2, d_model=64, num_heads=2,
        num_kv_heads=2, d_ff=64, num_experts=4, top_k=2, vocab_size=512,
        max_seq_len=128, moe_dispatch="grouped", remat=True)


def _qwen3_next_toy():
    return qwen3_next_model(
        "80b-a3b", num_layers=4, d_model=64, num_heads=4, num_kv_heads=2,
        head_dim=32, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=16, d_ff=32,
        shared_expert_d_ff=32, num_experts=16, top_k=4, experts_held=4,
        expert_offset=8, vocab_size=512, max_seq_len=128,
        delta_rule_chunk=16, dtype="float32", remat=True)


@pytest.mark.parametrize("build", [_olmoe_toy, _qwen3_next_toy])
def test_a_softmax_router_and_swiglu_experts_are_the_program_they_were(
        build, monkeypatch):
    """Default MoEConfig: the OLMoE and Qwen3-Next programs are untouched by
    the router's second form, the scaling factor and the third activation —
    saying "softmax, times 1, SwiGLU" out loud lowers to the same text, and
    that text has nothing of the new path in it (PERF.md section 6, PR 34,
    has the comparison with the parent commit's text)."""
    model = build()
    text = _lowered(model)
    explicit = type(model.config).moe.fget
    monkeypatch.setattr(type(model.config), "moe", property(
        lambda self: replace(explicit(self), router="softmax",
                             routed_scaling_factor=1.0,
                             activation="silu_glu")))
    assert _lowered(build()) == text
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert not [p for p, _ in jax.tree_util.tree_leaves_with_path(shapes)
                if "e_score_correction_bias" in jax.tree_util.keystr(p)]


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
    with pytest.raises(NotImplementedError, match="recurrent state"):
        getattr(toy_model(), entry)(None, None, None)


def test_the_size_is_the_published_one_and_the_cut_is_the_files():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        config = json.load(f)
    whole = NemotronHConfig()
    assert count_params(whole) == config["published"]["n_params"] \
        == 31_577_940_288
    assert len(whole.pattern) == 52 and whole.num_periods == 1
    assert [whole.layers_of(k) for k in ("ssm", "experts", "attn")] \
        == [23, 23, 6]
    assert (whole.d_inner, whole.conv_channels) == (4096, 6144)
    model = nemotron_h_model(**config["builder"]["kwargs"])
    for key, want in config["model"].items():
        have = model.meta[key] if key == "n_params" \
            else getattr(model.config, key)
        assert have == want, key
    cut = model.config
    assert cut.hybrid_override_pattern \
        == whole.hybrid_override_pattern[:9] == "MEMEM*EME"
    assert [cut.layers_of(k) for k in ("ssm", "experts", "attn")] \
        == [4, 4, 1]
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    moe = shapes["blocks"]["experts"]["moe"]
    assert moe["router"].shape == (1, 4, 2688, 128)
    assert moe["w_in"].shape == (1, 4, 8, 2688, 1856)
    assert "w_gate" not in moe and "shared_gate" not in moe
    assert shapes["blocks"]["ssm"]["w_in"].shape == (1, 4, 2688, 10304)
    with pytest.raises(ValueError, match="whole"):
        NemotronHConfig(num_layers=10).num_periods
    with pytest.raises(ValueError, match="not built"):
        NemotronHConfig(num_layers=2, hybrid_override_pattern="M-").pattern
