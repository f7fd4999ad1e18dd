"""Fused decode megakernel + unified batched-window step (ISSUE 12).

The load-bearing contracts:
- the Pallas megakernel (interpret mode) matches the jnp reference
  composition — which IS the unfused per-layer math — for every wired
  variant (ln/rms, fused/headmajor/split QKV, rotary/partial rotary,
  alibi, serial/parallel residual, gelu/swiglu/none MLP, int8 KV cache,
  int8 weights);
- greedy continuous-batching output is token-identical fused vs unfused
  across the parity matrix (families × int8 KV × int8 weights under
  interpret qgemm × MoE grouped dispatch × prefix-cache COW × spec
  rollback × chunked prefill);
- the compiled fused decode step issues ≤ L + k kernel launches where
  the unfused int8 composition issues ~(4-6)L (counted as pallas_call
  equations in the traced program — launch sites, one device launch
  each per execution);
- use_scan_decode does not double-count weight bytes the megakernel
  streams itself; serving.fused_decode round-trips through config and
  installs the override.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.model import QuantizedTensor
from deepspeed_tpu.ops.pallas.fused_decode import (FusedLayerSpec,
                                                   _ref_fused_layer,
                                                   ds_fused_layer,
                                                   fused_decode_scope)
from deepspeed_tpu.runtime.config import ServingConfig
from deepspeed_tpu.serving import (ContinuousBatchingScheduler,
                                   RequestState, SamplingParams)
from tests.util import child_env, tiny_gpt2


@pytest.fixture(autouse=True)
def _debug_invariant(monkeypatch):
    monkeypatch.setenv("DS_SERVE_DEBUG", "1")


def _mk(rng, shape, scale=0.2):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32) * scale


def _gpt2_spec_weights(rng, D=32, H=4, hd=8):
    spec = FusedLayerSpec(num_heads=H, num_kv_heads=H, head_dim=hd,
                          d_model=D, norm="ln", qkv="fused",
                          mlp="gelu_tanh")
    cw = dict(n1_s=_mk(rng, (D,), 0.1) + 1, n1_b=_mk(rng, (D,)),
              wqkv=_mk(rng, (D, 3 * D)), bqkv=_mk(rng, (3 * D,)),
              wo=_mk(rng, (D, D)), bo=_mk(rng, (D,)),
              n2_s=_mk(rng, (D,), 0.1) + 1, n2_b=_mk(rng, (D,)),
              w_in=_mk(rng, (D, 4 * D)), b_in=_mk(rng, (4 * D,)),
              w_out=_mk(rng, (4 * D, D)), b_out=_mk(rng, (D,)))
    return spec, cw


def _llama_spec_weights(rng, D=32, H=4, KV=2, hd=8, mlp="swiglu"):
    spec = FusedLayerSpec(num_heads=H, num_kv_heads=KV, head_dim=hd,
                          d_model=D, norm="rms", qkv="split",
                          qkv_bias=False, out_bias=False, mlp=mlp,
                          mlp_bias=False, rotary_dims=hd)
    cw = dict(n1_s=_mk(rng, (D,), 0.1) + 1,
              wq=_mk(rng, (D, H * hd)), wk=_mk(rng, (D, KV * hd)),
              wv=_mk(rng, (D, KV * hd)), wo=_mk(rng, (H * hd, D)))
    if mlp == "swiglu":
        cw.update(n2_s=_mk(rng, (D,), 0.1) + 1,
                  w_gate=_mk(rng, (D, 2 * D)), w_up=_mk(rng, (D, 2 * D)),
                  w_down=_mk(rng, (2 * D, D)))
    return spec, cw


def _neox_spec_weights(rng, D=32, H=4, hd=8, residual="parallel",
                       alibi=False):
    spec = FusedLayerSpec(num_heads=H, num_kv_heads=H, head_dim=hd,
                          d_model=D, norm="ln", qkv="headmajor",
                          mlp="gelu_exact", residual=residual,
                          rotary_dims=0 if alibi else hd // 2,
                          alibi=alibi)
    cw = dict(n1_s=_mk(rng, (D,), 0.1) + 1, n1_b=_mk(rng, (D,)),
              wqkv=_mk(rng, (D, H * 3 * hd)), bqkv=_mk(rng, (H * 3 * hd,)),
              wo=_mk(rng, (D, D)), bo=_mk(rng, (D,)),
              n2_s=_mk(rng, (D,), 0.1) + 1, n2_b=_mk(rng, (D,)),
              w_in=_mk(rng, (D, 4 * D)), b_in=_mk(rng, (4 * D,)),
              w_out=_mk(rng, (4 * D, D)), b_out=_mk(rng, (D,)))
    return spec, cw


def _quantize_cw(cw, keys):
    from deepspeed_tpu.ops.pallas.quantization import block_quantize_int8
    out = dict(cw)
    for k in keys:
        q, s = block_quantize_int8(np.asarray(cw[k]), block=16)
        out[k] = QuantizedTensor(jnp.asarray(q), jnp.asarray(s), "float32")
    return out


def _run_layer(spec, cw, W=3, B=2, S=64, quant=False, slopes=None,
               interpret=True, seed=3):
    rng = np.random.default_rng(seed)
    KV, hd = spec.num_kv_heads, spec.head_dim
    x = _mk(rng, (B, W, spec.d_model))
    k_l = _mk(rng, (B, S, KV, hd), 1.0)
    v_l = _mk(rng, (B, S, KV, hd), 1.0)
    lengths = jnp.asarray([5, 17][:B], jnp.int32)
    ks_l = vs_l = None
    if quant:
        from deepspeed_tpu.ops.pallas.decode_attention import quantize_kv
        k_l, ks_l = quantize_kv(k_l)
        v_l, vs_l = quantize_kv(v_l)
    ref = _ref_fused_layer(x, cw, k_l, v_l, lengths, spec, ks_l, vs_l,
                           slopes)
    got = ds_fused_layer(x, cw, k_l, v_l, lengths, spec, ks_l=ks_l,
                         vs_l=vs_l, alibi_slopes=slopes,
                         interpret=interpret)
    return ref, got


def _assert_close(ref, got, tol=2e-4):
    for a, b in zip(ref, got):
        if a is None:
            assert b is None
            continue
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=tol)


# ----------------------------------------------------- kernel vs reference
def test_kernel_matches_reference_gpt2_float():
    rng = np.random.default_rng(0)
    _assert_close(*_run_layer(*_gpt2_spec_weights(rng)))


def test_kernel_matches_reference_gpt2_int8_cache():
    rng = np.random.default_rng(1)
    _assert_close(*_run_layer(*_gpt2_spec_weights(rng), quant=True))


def test_kernel_matches_reference_gpt2_int8_weights():
    rng = np.random.default_rng(2)
    spec, cw = _gpt2_spec_weights(rng)
    cwq = _quantize_cw(cw, ("wqkv", "wo", "w_in", "w_out"))
    _assert_close(*_run_layer(spec, cwq, quant=True))


def test_kernel_matches_reference_llama_gqa_rope_swiglu():
    rng = np.random.default_rng(3)
    _assert_close(*_run_layer(*_llama_spec_weights(rng)))
    _assert_close(*_run_layer(*_llama_spec_weights(rng), quant=True))


def test_kernel_matches_reference_moe_attn_half():
    """mlp="none": the kernel stops after the attn-out residual (the
    MoE expert FFN rides the grouped-GEMM kernels outside)."""
    rng = np.random.default_rng(4)
    spec, cw = _llama_spec_weights(rng, mlp="none")
    _assert_close(*_run_layer(spec, cw))


def test_kernel_matches_reference_neox_parallel_partial_rope():
    rng = np.random.default_rng(5)
    _assert_close(*_run_layer(*_neox_spec_weights(rng)))


def test_kernel_matches_reference_bloom_alibi():
    rng = np.random.default_rng(6)
    spec, cw = _neox_spec_weights(rng, residual="serial", alibi=True)
    slopes = np.asarray([2.0 ** -(i + 1) for i in range(4)], np.float32)
    _assert_close(*_run_layer(spec, cw, slopes=slopes))


def test_kernel_w1_decode_shape():
    rng = np.random.default_rng(7)
    _assert_close(*_run_layer(*_gpt2_spec_weights(rng), W=1))


def test_vmem_budget_falls_back_to_reference(monkeypatch):
    """Past the resident-weights VMEM budget the dispatch must run the
    reference composition (no pallas_call in the traced program), not
    fail."""
    monkeypatch.setenv("DS_FUSED_DECODE_VMEM_MB", "0")
    rng = np.random.default_rng(8)
    spec, cw = _gpt2_spec_weights(rng)

    def fn(x, k, v, lengths):
        return ds_fused_layer(x, cw, k, v, lengths, spec,
                              interpret=True)[0]

    B, W, S = 2, 1, 64
    x = _mk(rng, (B, W, spec.d_model))
    k = _mk(rng, (B, S, 4, 8))
    v = _mk(rng, (B, S, 4, 8))
    lengths = jnp.asarray([3, 5], jnp.int32)
    jaxpr = jax.make_jaxpr(fn)(x, k, v, lengths)
    assert _count_pallas_eqns(jaxpr.jaxpr) == 0
    ref, got = _run_layer(spec, cw)         # unset env path still kernels
    _assert_close(ref, got)


# -------------------------------------------------------- launch counting
# the launch-site counter graduated into the shared cost-model API
# (ISSUE 13): the same recursion that backed this file's L-vs-4L
# assertion now feeds perf/pallas_launches on /metrics
from deepspeed_tpu.telemetry.costmodel import \
    count_pallas_launches as _count_pallas_eqns  # noqa: E402


def test_fused_step_launch_count(monkeypatch):
    """Acceptance (ISSUE 12): the fused decode step lowers to <= L + k
    kernel-launch sites; the unfused int8 composition issues ~(4-6)L
    (four qgemm projections per layer at minimum).  Counted on the
    SAME model/params, CPU-runnable via interpret mode."""
    m = tiny_gpt2(num_layers=3)
    engq = deepspeed_tpu.init_inference(
        model=m, config={"dtype": "float32", "quant": {"enabled": True}})
    L = m.config.num_layers
    cache = m.init_cache_fn(2, 64, None)
    toks = jnp.asarray([3, 4], jnp.int32)
    lengths = jnp.asarray([5, 6], jnp.int32)

    monkeypatch.setenv("DS_QGEMM_INTERPRET", "1")
    with fused_decode_scope(False):
        jaxpr_unfused = jax.make_jaxpr(
            lambda p, t, c, l: m.decode_fn(p, t, c, l)[0])(
                engq.params, toks, cache, lengths)
    monkeypatch.setenv("DS_FUSED_DECODE_INTERPRET", "1")
    with fused_decode_scope(True):
        jaxpr_fused = jax.make_jaxpr(
            lambda p, t, c, l: m.decode_fn(p, t, c, l)[0])(
                engq.params, toks, cache, lengths)
    n_unfused = _count_pallas_eqns(jaxpr_unfused.jaxpr)
    n_fused = _count_pallas_eqns(jaxpr_fused.jaxpr)
    # unfused: >= 4 qgemm launches per layer (QKV, attn-out, MLP in/out)
    assert n_unfused >= 4 * L, (n_unfused, L)
    # fused: one megakernel per layer + k extras (the lm-head qgemm)
    assert n_fused <= L + 2, (n_fused, L)
    assert n_fused < n_unfused


# ------------------------------------------------------- cb parity matrix
def _cb_outputs(model, params, prompts, max_new, cfg_kwargs=None,
                sampling=None, proposer=None):
    cfg = ServingConfig(**dict(dict(block_size=8, num_blocks=64,
                                    max_num_seqs=4,
                                    max_num_batched_tokens=256),
                               **(cfg_kwargs or {})))
    sched = ContinuousBatchingScheduler(model, params, cfg,
                                        proposer=proposer)
    reqs = [sched.submit(p, sampling or SamplingParams(max_new_tokens=mn))
            for p, mn in zip(prompts, max_new)]
    sched.run_until_idle()
    assert all(r.state == RequestState.FINISHED for r in reqs)
    return [np.asarray(r.output_ids) for r in reqs], sched


def _parity_fused_vs_unfused(model, params, interpret=False,
                             cfg_kwargs=None, proposer_fn=None, n=4,
                             seed=5, vocab=120):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, vocab, (int(L),)).astype(np.int32)
               for L in rng.integers(4, 12, n)]
    max_new = [int(v) for v in rng.integers(3, 8, n)]
    with fused_decode_scope(False):
        base, _ = _cb_outputs(model, params, prompts, max_new, cfg_kwargs,
                              proposer=proposer_fn() if proposer_fn
                              else None)
    if interpret:
        os.environ["DS_FUSED_DECODE_INTERPRET"] = "1"
    try:
        with fused_decode_scope(True):
            fused, sched = _cb_outputs(model, params, prompts, max_new,
                                       cfg_kwargs,
                                       proposer=proposer_fn()
                                       if proposer_fn else None)
    finally:
        os.environ.pop("DS_FUSED_DECODE_INTERPRET", None)
    for a, b in zip(base, fused):
        np.testing.assert_array_equal(a, b)
    return sched


# ------------------------------------------------- accounting + config
def test_use_scan_decode_fused_accounting(monkeypatch):
    """The small fix: with the fused kernel real, 2-D stacked int8
    projection weights stream through the megakernel and must not count
    against the scan threshold (the unfused path without qgemm still
    counts every byte)."""
    from deepspeed_tpu.models import serving as sv
    rng = np.random.default_rng(23)
    q = jnp.asarray(rng.integers(-127, 127, (2, 64, 64)), jnp.int8)
    s = jnp.ones((2, 64, 1), jnp.float32)
    blocks = {"qkv_w": QuantizedTensor(q, s, "float32")}
    monkeypatch.setattr(sv, "QUANT_SCAN_THRESHOLD", 1)   # 1 byte
    # CPU, no interpret: neither kernel is real -> all bytes count
    assert sv.use_scan_decode(blocks)
    assert sv.use_scan_decode(blocks, fused=True)
    # fused kernel real (interpret): the megakernel absorbs the leaves
    monkeypatch.setenv("DS_FUSED_DECODE_INTERPRET", "1")
    assert not sv.use_scan_decode(blocks, fused=True)
    # ...but an unfused program still pays the dequant
    assert sv.use_scan_decode(blocks, fused=False)


def test_serving_config_fused_decode_round_trip():
    import json
    cfg = ServingConfig(fused_decode=True)
    assert cfg.fused_decode is True
    cfg2 = ServingConfig(**json.loads(json.dumps(
        {"fused_decode": False, "block_size": 8})))
    assert cfg2.fused_decode is False
    assert ServingConfig().fused_decode is None


def test_scheduler_installs_fused_override():
    from deepspeed_tpu.ops.pallas import fused_decode as fd
    m = tiny_gpt2()
    eng = deepspeed_tpu.init_inference(model=m,
                                       config={"dtype": "float32"})
    prev = fd._configured_fused
    try:
        cfg = ServingConfig(block_size=8, num_blocks=32,
                            fused_decode=False)
        ContinuousBatchingScheduler(m, eng.params, cfg)
        assert fd._configured_fused is False
        assert not fd.fused_decode_enabled()
    finally:
        fd.set_fused_decode_override(prev)


# ------------------------------------------------------------- tooling
def test_fused_sweep_script_smoke():
    """scripts/fused_sweep.py runs the interpret-mode smoke and emits a
    winner row per kind."""
    import json
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = child_env(FUSED_SWEEP_SMOKE="1")
    out = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "fused_sweep.py")],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(line) for line in out.stdout.splitlines() if line]
    winners = {r["kind"] for r in rows if "winner" in r}
    assert {"decode", "window", "int8kv", "int8w"} <= winners, rows
