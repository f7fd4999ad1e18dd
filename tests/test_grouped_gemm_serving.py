"""The grouped dispatch in the layer and in serving, on the kernels of
tests/test_grouped_gemm.py: how a dispatch mode is chosen, grouped against
einsum in evaluation and training, droplessness, the routing telemetry,
an expert-parallel mesh, and Mixtral through the continuous-batching
scheduler (int8 KV and weights, speculative decoding, the prefix cache).
A file of its own so that ``--dist loadfile`` gives the grouped GEMM's
tests to two workers."""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.moe.layer import (MoEConfig, dispatch_scope,
                                     init_moe_params, moe_layer,
                                     resolve_dispatch_mode,
                                     set_moe_metrics_registry)
from deepspeed_tpu.moe.sharded_moe import topk_routing, topkgating
from deepspeed_tpu.ops.pallas import grouped_gemm as gg

from tests.test_grouped_gemm import (  # noqa: F401 (the fixtures come by name)
    _debug_invariant, _layer_setup, _mixed_prompts, _rand_eids, _run_cb)


# ------------------------------------------------------- dispatch modes
def test_dispatch_mode_resolution_and_validation(monkeypatch):
    cfg = MoEConfig(d_model=8, d_ff=16, dispatch_mode="auto")
    assert resolve_dispatch_mode(cfg, train=True) == "einsum"
    # this host has 8 (virtual) devices and no real kernel: auto at eval
    # keeps the sharded einsum formulation; with the real kernel forced
    # (interpret) auto picks grouped
    assert resolve_dispatch_mode(cfg, train=False) == "einsum"
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    assert resolve_dispatch_mode(cfg, train=False) == "grouped"
    monkeypatch.delenv("DS_GGEMM_INTERPRET")
    with dispatch_scope("grouped"):
        assert resolve_dispatch_mode(cfg, train=True) == "grouped"
    assert resolve_dispatch_mode(cfg, train=True) == "einsum"
    with pytest.raises(ValueError, match="dispatch mode"):
        with dispatch_scope("bogus"):
            pass
    os.environ["DS_MOE_DISPATCH"] = "einsum"
    try:
        with dispatch_scope("grouped"):     # env wins over the override
            assert resolve_dispatch_mode(cfg, train=False) == "einsum"
    finally:
        del os.environ["DS_MOE_DISPATCH"]
    from deepspeed_tpu.runtime.config import ServingConfig
    with pytest.raises(ValueError, match="moe_dispatch"):
        ServingConfig(moe_dispatch="nope")
    assert ServingConfig(moe_dispatch="grouped").moe_dispatch == "grouped"


def test_serving_config_installs_dispatch_override(devices8):
    """An explicit serving.moe_dispatch reaches the layer-side resolver
    at scheduler construction (the quant_scan_threshold pattern)."""
    from deepspeed_tpu.moe.layer import set_dispatch_override
    from deepspeed_tpu.runtime.config import ServingConfig
    from deepspeed_tpu.serving import ContinuousBatchingScheduler
    from tests.util import tiny_gpt2
    m = tiny_gpt2()
    eng = deepspeed_tpu.init_inference(model=m,
                                       config={"dtype": "float32"})
    cfg = ServingConfig(block_size=8, num_blocks=16, moe_dispatch="einsum")
    try:
        ContinuousBatchingScheduler(m, eng.params, cfg)
        mcfg = MoEConfig(d_model=8, d_ff=16, dispatch_mode="auto")
        assert resolve_dispatch_mode(mcfg, train=False) == "einsum"
    finally:
        set_dispatch_override(None)


def test_topk_routing_matches_topkgating():
    """The extracted routing decision is bitwise the gating half of
    topkgating — capacity is a property of the dispatch, not the
    router."""
    logits = jax.random.normal(jax.random.PRNGKey(0), (32, 4))
    r = topk_routing(logits, 2)
    g = topkgating(logits, 2, capacity_factor=2.0)
    assert float(r.l_aux) == float(g.l_aux)
    # each token's gate weights appear in the combine tensor exactly
    cw = np.asarray(g.combine_weights)      # [T, E, C]
    for t in range(8):
        for i in range(2):
            e = int(r.expert_idx[t, i])
            want = float(r.gate_weights[t, i])
            assert np.isclose(cw[t, e].max(), want, atol=1e-7)


@pytest.mark.parametrize("activation", ["silu_glu", "gelu"])
def test_grouped_matches_einsum_eval(activation):
    cfg, params, x = _layer_setup(activation=activation)
    with dispatch_scope("einsum"):
        ye, ae = moe_layer(params, x, cfg, train=False)
    with dispatch_scope("grouped"):
        yg, ag = moe_layer(params, x, cfg, train=False)
    np.testing.assert_allclose(np.asarray(yg), np.asarray(ye),
                               rtol=2e-5, atol=2e-5)
    assert float(ae) == pytest.approx(float(ag), rel=1e-6)


def test_grouped_matches_einsum_train_fwd_bwd():
    """Train-mode forward AND gradients agree at matched (drop-free)
    capacity — the formulations compute the same math."""
    cfg, params, x = _layer_setup()

    def loss(p, mode):
        with dispatch_scope(mode):
            out, aux = moe_layer(p, x, cfg, train=True)
        return jnp.sum(out.astype(jnp.float32) ** 2) + aux

    le, ge = jax.value_and_grad(loss)(params, "einsum")
    lg, gr = jax.value_and_grad(loss)(params, "grouped")
    assert float(le) == pytest.approx(float(lg), rel=1e-5)
    for key in ("router", "w_in", "w_out", "w_gate"):
        np.testing.assert_allclose(np.asarray(gr[key]), np.asarray(ge[key]),
                                   rtol=5e-5, atol=5e-5,
                                   err_msg=f"grad mismatch on {key}")


def test_grouped_is_dropless_when_einsum_drops():
    """Skewed routing at capacity_factor=1: einsum drops tokens (output
    loses their contribution), grouped computes every routed token."""
    E, k, D, F = 4, 1, 16, 32
    cfg = MoEConfig(d_model=D, d_ff=F, num_experts=E, top_k=k,
                    capacity_factor=1.0, eval_capacity_factor=1.0,
                    min_capacity=1)
    params = init_moe_params(cfg, jax.random.PRNGKey(2))
    # force every token to expert 0: router bias via inputs aligned to
    # one direction -> capacity T/E drops 3/4 of tokens in einsum mode
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(3), (1, 1, D)),
                 (2, 8, 1))
    with dispatch_scope("einsum"):
        ye, _ = moe_layer(params, x, cfg, train=False)
    with dispatch_scope("grouped"):
        yg, _ = moe_layer(params, x, cfg, train=False)
    # identical rows: grouped computes ALL of them; einsum zeroes the
    # dropped ones -> rows differ
    assert not np.allclose(np.asarray(ye), np.asarray(yg))
    # grouped treats every row of the tiled batch identically (dropless)
    g = np.asarray(yg).reshape(-1, D)
    np.testing.assert_allclose(g, np.broadcast_to(g[0], g.shape),
                               rtol=1e-5, atol=1e-6)


def test_expert_ffn_gelu_ignores_gate_operand():
    """ISSUE 8 satellite: gelu-mode experts must not consume (nor
    require) a gate operand — outputs identical with and without the
    w_gate key present."""
    cfg, slim, x = _layer_setup(activation="gelu", seed=7)
    assert "w_gate" not in slim     # gelu init carries no gate weights
    # a spurious gate leaf (e.g. a checkpoint converted from a GLU
    # config) must be IGNORED, not vmapped as a phantom operand — the
    # old params.get("w_gate", params["w_in"]) default always vmapped
    # something
    params = dict(slim, w_gate=jnp.ones_like(slim["w_in"]) * 999.0)
    with dispatch_scope("einsum"):
        with_gate, _ = moe_layer(params, x, cfg, train=False)
    with dispatch_scope("einsum"):
        without_gate, _ = moe_layer(slim, x, cfg, train=False)
    np.testing.assert_array_equal(np.asarray(with_gate),
                                  np.asarray(without_gate))
    with dispatch_scope("grouped"):
        grouped, _ = moe_layer(slim, x, cfg, train=False)
    np.testing.assert_allclose(np.asarray(grouped),
                               np.asarray(without_gate),
                               rtol=2e-5, atol=2e-5)


def test_routing_telemetry_counters():
    """moe/dispatch_tokens + moe/dropped_tokens + moe_drop_fraction:
    einsum reports real capacity drops, grouped pins drops to 0."""
    from deepspeed_tpu.telemetry import MetricsRegistry
    E, k, D, F = 4, 1, 16, 32
    cfg = MoEConfig(d_model=D, d_ff=F, num_experts=E, top_k=k,
                    capacity_factor=1.0, eval_capacity_factor=1.0,
                    min_capacity=1)
    params = init_moe_params(cfg, jax.random.PRNGKey(2))
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(3), (1, 1, D)),
                 (2, 8, 1))                 # all 16 tokens -> one expert
    reg = MetricsRegistry()
    set_moe_metrics_registry(reg)
    try:
        with dispatch_scope("einsum"):
            moe_layer(params, x, cfg, train=False)
        jax.effects_barrier()
        dropped = reg.get_counter("moe/dropped_tokens")
        assert dropped == 12                # capacity 4 of 16 kept
        assert reg.get_counter("moe/dispatch_tokens") == 4
        assert reg.get_gauge("moe_drop_fraction") == pytest.approx(0.75)
        with dispatch_scope("grouped"):
            moe_layer(params, x, cfg, train=False)
        jax.effects_barrier()
        assert reg.get_counter("moe/dropped_tokens") == dropped  # +0
        assert reg.get_counter("moe/dispatch_tokens") == 4 + 16
        assert reg.get_gauge("moe_drop_fraction") == 0.0
    finally:
        set_moe_metrics_registry(None)


def test_grouped_gemm_span_on_eager_call(tmp_path, monkeypatch):
    """moe/grouped_gemm span lands on the Perfetto timeline for eager
    kernel invocations (the sweep/op-level surface)."""
    from deepspeed_tpu.telemetry import SpanTracer
    from deepspeed_tpu.telemetry import tracing as _tracing
    rng = np.random.default_rng(8)
    E, K, N, R = 3, 16, 24, 10
    eids = _rand_eids(rng, R, E)
    x = jnp.asarray(rng.standard_normal((R, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((E, K, N)), jnp.float32)
    plan = gg.make_group_plan(eids, E, block_m=8)
    tracer = SpanTracer(str(tmp_path / "trace.json"))
    monkeypatch.setattr(_tracing, "_ACTIVE", tracer)
    gg.ds_ggemm(gg.scatter_to_groups(x, plan), w, plan, interpret=True)
    names = [e.get("name") for e in tracer._events]
    assert "moe/grouped_gemm" in names


# ------------------------------------------------------- EP: the exchange
def test_grouped_request_on_ep_mesh_exchanges_and_matches(devices8):
    """A grouped request on a multi-device expert axis stays grouped: the
    layer exchanges its rows (moe/layer.py ``_exchanged_grouped_moe``; here
    expert 2 x data 4) and the math is unchanged vs the single-device
    grouped run.  Tokens the chips cannot split evenly (a generation's 14
    prompt tokens and 2 a decode step over 8 chips) are made up with rows
    of zero gate, so ``generate`` serves as it did through the einsum, and
    greedy tokens match exactly."""
    from deepspeed_tpu.models.mixtral import mixtral_model
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.comm import reset_topology
    m = mixtral_model("tiny", attention_impl="xla", dtype="float32",
                      max_seq_len=64, moe_dispatch="grouped")
    params = m.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    batch = {"input_ids": rng.integers(1, 200, (8, 16)).astype(np.int32)}
    ref_eng = InferenceEngine(m, DeepSpeedInferenceConfig(dtype="float32"),
                              model_parameters=params)
    ref = np.asarray(jax.jit(m.apply)(ref_eng.params, batch))
    prompts = rng.integers(1, 200, (2, 7)).astype(np.int32)
    ref_tokens = np.asarray(ref_eng.generate(prompts, max_new_tokens=8,
                                             do_sample=False))
    reset_topology()
    ep_eng = InferenceEngine(
        m, DeepSpeedInferenceConfig(dtype="float32", moe={"ep_size": 2}),
        model_parameters=params)
    assert dict(ep_eng.mesh.shape)["expert"] == 2
    with ep_eng.mesh:
        from deepspeed_tpu.comm.mesh import get_topology
        assert dict(get_topology().mesh.shape)["expert"] == 2
        assert resolve_dispatch_mode(m.config.moe, train=False) == "grouped"
        fn = jax.jit(m.apply)
        got = np.asarray(fn(ep_eng.params, batch))
        assert " all-to-all(" in fn.lower(
            ep_eng.params, batch).compile().as_text()
    np.testing.assert_allclose(got, ref, atol=2e-5)
    got_tokens = np.asarray(ep_eng.generate(prompts, max_new_tokens=8,
                                            do_sample=False))
    np.testing.assert_array_equal(got_tokens, ref_tokens)


@pytest.fixture(scope="module")
def mixtral_served():
    from deepspeed_tpu.models.mixtral import mixtral_model
    m = mixtral_model("tiny", attention_impl="xla", dtype="float32",
                      max_seq_len=128)
    eng = deepspeed_tpu.init_inference(model=m,
                                       config={"dtype": "float32"})
    return m, eng


def test_mixtral_cb_grouped_matches_einsum(mixtral_served):
    m, eng = mixtral_served
    prompts = _mixed_prompts(4, seed=1)
    max_new = [6, 4, 8, 5]
    outs_g, _ = _run_cb(m, eng.params, "grouped", prompts, max_new)
    outs_e, _ = _run_cb(m, eng.params, "einsum", prompts, max_new)
    assert outs_g == outs_e


def test_mixtral_cb_grouped_int8_kv(mixtral_served):
    m, _ = mixtral_served
    eng8 = deepspeed_tpu.init_inference(
        model=m, config={"dtype": "float32", "kv_cache_dtype": "int8"})
    prompts = _mixed_prompts(3, seed=2)
    max_new = [5, 5, 5]
    outs_g, _ = _run_cb(m, eng8.params, "grouped", prompts, max_new,
                        kv_cache_dtype="int8")
    outs_e, _ = _run_cb(m, eng8.params, "einsum", prompts, max_new,
                        kv_cache_dtype="int8")
    assert outs_g == outs_e


def test_mixtral_cb_grouped_int8_weights_interpret(mixtral_served,
                                                   monkeypatch):
    """int8 expert stacks through the REAL fused-dequant grouped kernels
    (interpret mode): cb greedy == static int8 generate, with the 4-D
    expert leaves staying quantized into the kernel (keep_moe_quantized)
    and the dense projections on the qgemm route."""
    m, _ = mixtral_served
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    from deepspeed_tpu.models.serving import (moe_dispatch_grouped,
                                              qgemm_scope)
    engq = deepspeed_tpu.init_inference(
        model=m, config={"dtype": "float32", "quant": {"enabled": True}})
    from deepspeed_tpu.models.model import QuantizedTensor
    is_q = lambda x: isinstance(x, QuantizedTensor)
    ndims = {l.q.ndim for l in jax.tree_util.tree_leaves(
        engq.params["blocks"], is_leaf=is_q) if is_q(l)}
    assert 4 in ndims                       # stacked experts quantized
    prompts = _mixed_prompts(3, seed=3)
    max_new = [5, 6, 4]
    with qgemm_scope(True):
        with dispatch_scope("grouped"):
            assert moe_dispatch_grouped(m.config.moe)
        outs_g, _ = _run_cb(m, engq.params, "grouped", prompts, max_new)
        refs = [list(np.asarray(engq.generate(
            p[None], max_new_tokens=mn, do_sample=False))[0, p.size:])
            for p, mn in zip(prompts, max_new)]
    assert outs_g == refs


def test_mixtral_spec_decode_grouped_parity(mixtral_served):
    """Speculative (ngram) decoding over grouped dispatch — verify
    windows ride the slot/grouped kernels and rollback keeps greedy
    outputs identical to plain grouped cb."""
    rng = np.random.default_rng(4)
    m, eng = mixtral_served
    motif = rng.integers(1, 200, (5,))
    prompts = [np.concatenate([rng.integers(1, 200, (2,)),
                               np.tile(motif, 4)]).astype(np.int32)
               for _ in range(3)]
    max_new = [8, 6, 8]
    spec_cfg = {"spec": {"mode": "ngram", "max_draft_tokens": 4}}
    outs_spec, sched = _run_cb(m, eng.params, "grouped", prompts, max_new,
                               cfg_kw=spec_cfg)
    assert sched.metrics.counters["spec_verify_steps"] > 0
    outs_plain, _ = _run_cb(m, eng.params, "grouped", prompts, max_new)
    assert outs_spec == outs_plain


def test_mixtral_prefix_cache_grouped_parity(mixtral_served):
    """Prefix-cache COW forks + suffix prefill through grouped dispatch:
    cache-on greedy outputs == cache-off (shared-prefix workload)."""
    rng = np.random.default_rng(5)
    m, eng = mixtral_served
    system = rng.integers(1, 200, (24,))
    prompts = [np.concatenate([system,
                               rng.integers(1, 200, (int(t),))]
                              ).astype(np.int32)
               for t in rng.integers(3, 8, 3)]
    max_new = [6, 6, 6]
    pc = {"prefix_cache": {"enabled": True}}
    outs_on, sched = _run_cb(m, eng.params, "grouped", prompts, max_new,
                             cfg_kw=pc)
    assert sched.metrics.counters["prefix_cache_hit"] > 0
    outs_off, _ = _run_cb(m, eng.params, "grouped", prompts, max_new)
    assert outs_on == outs_off
