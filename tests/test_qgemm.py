"""Fused-dequant int8 GEMM kernel (ISSUE 2 tentpole): ``ds_qgemm``
parity vs the dequantize-then-matmul reference across multi-tile grids
and edge-padded shapes, the serving integration (qgemm path == dequant
fallback == scan fallback, token-for-token), and the compiled-memory
contract — the decode step must NOT materialize a layer's compute-dtype
weights (the gpt2-1.3B int8 collapse PERF.md round 5 measured)."""
import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

import deepspeed_tpu
from deepspeed_tpu.models import serving
from deepspeed_tpu.ops.pallas.qgemm import ds_qgemm, _ref_qgemm
from deepspeed_tpu.ops.pallas.quantization import (block_dequantize_int8,
                                                   block_quantize_int8)
from tests.util import tiny_gpt2


# ----------------------------------------------------------- kernel parity
@pytest.mark.parametrize(
    "M,K,N,qblock,blocks",
    [
        (4, 256, 512, 128, (8, 128, 128)),     # multi-tile grid all 3 dims
        (8, 256, 256, 256, (8, 128, 128)),     # one scale group per tile row
        (9, 384, 640, 128, (8, 128, 256)),     # M needs edge-tile padding
        (3, 100, 300, 128, (8, 128, 128)),     # ragged K/N + ragged groups
        (17, 512, 768, 256, (16, 256, 512)),   # bn spanning 2 scale groups
        (2, 64, 130, 64, (8, 128, 128)),       # N < bn, ragged last group
    ])
def test_ds_qgemm_interpret_matches_reference(M, K, N, qblock, blocks):
    """Acceptance: ds_qgemm(x, q, scales) == x @ dequant(q, scales) within
    bf16-class tolerance, across dims covering multi-tile grids and
    shapes needing edge-tile padding (interpret mode on the CPU mesh)."""
    rng = np.random.default_rng(M * K + N)
    x = jnp.asarray(rng.standard_normal((M, K)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((K, N)).astype(np.float32))
    q, s = block_quantize_int8(w, block=qblock)
    ref = np.asarray(x @ block_dequantize_int8(q, s))
    bm, bk, bn = blocks
    out = np.asarray(ds_qgemm(x, q, s, interpret=True, block_m=bm,
                              block_k=bk, block_n=bn))
    np.testing.assert_allclose(out, ref, atol=1e-3, rtol=1e-3)


def test_ds_qgemm_leading_dims_and_bf16():
    """[B, S, K] inputs flatten to the GEMM M dim; bf16 x stays within
    bf16 tolerance of the fp32 dequant reference."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2, 3, 256)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((256, 384)).astype(np.float32))
    q, s = block_quantize_int8(w, block=128)
    ref = np.asarray(x @ block_dequantize_int8(q, s))
    out = np.asarray(ds_qgemm(x, q, s, interpret=True, block_m=8,
                              block_k=128, block_n=128))
    assert out.shape == (2, 3, 384)
    np.testing.assert_allclose(out, ref, atol=1e-3, rtol=1e-3)
    out16 = np.asarray(ds_qgemm(
        x.astype(jnp.bfloat16), q, s, interpret=True, block_m=16,
        block_k=128, block_n=128).astype(jnp.float32))
    np.testing.assert_allclose(out16, ref, atol=0.15, rtol=0.05)


def test_ds_qgemm_compiles_in_cpu_suite():
    """tier-1 interpret-mode smoke (ISSUE 2 satellite): the Pallas kernel
    traces and compiles under jit on the CPU mesh."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 128)).astype(np.float32))
    q, s = block_quantize_int8(
        jnp.asarray(rng.standard_normal((128, 256)).astype(np.float32)),
        block=128)
    fn = jax.jit(functools.partial(ds_qgemm, interpret=True, block_m=8,
                                   block_k=128, block_n=128))
    out = np.asarray(fn(x, q, s))
    np.testing.assert_allclose(
        out, np.asarray(_ref_qgemm(x, q, s)), atol=1e-3, rtol=1e-3)


def test_ds_qgemm_rejects_stacked_weights():
    x = jnp.zeros((2, 8))
    q = jnp.zeros((3, 8, 8), jnp.int8)
    s = jnp.ones((3, 8, 1))
    with pytest.raises(ValueError, match="2-D"):
        ds_qgemm(x, q, s)


# ------------------------------------------------------ serving integration
def _quant_engine(m, params):
    return deepspeed_tpu.init_inference(
        model=m, config={"dtype": "float32", "quant": {"enabled": True}},
        model_parameters=params)


def test_qgemm_decode_matches_dequant_fallback_and_scan(monkeypatch):
    """The three int8-weights decode forms — qgemm unrolled (default),
    dequant unrolled (DS_QGEMM off), dequant scan (threshold 0) — must
    generate identical tokens; the qgemm path must also match the
    no-cache oracle."""
    m = tiny_gpt2(d_model=64, num_heads=4)
    params = m.init(jax.random.PRNGKey(0))
    prompts = np.random.default_rng(3).integers(1, 120, (2, 7)).astype(
        np.int32)

    def gen(qgemm, threshold):
        monkeypatch.setattr(serving, "QUANT_SCAN_THRESHOLD", threshold)
        with serving.qgemm_scope(qgemm):
            eng = _quant_engine(m, params)
            out = np.asarray(eng.generate(prompts, max_new_tokens=8,
                                          do_sample=False))
            oracle = np.asarray(eng.generate(prompts, max_new_tokens=8,
                                             do_sample=False,
                                             use_cache=False))
        return out, oracle

    qgemm_out, oracle = gen(True, 1 << 62)
    np.testing.assert_array_equal(qgemm_out, oracle)
    dequant_out, _ = gen(False, 1 << 62)      # fallback: unrolled dequant
    np.testing.assert_array_equal(qgemm_out, dequant_out)
    scan_out, _ = gen(False, 0)               # fallback: scan dequant
    np.testing.assert_array_equal(qgemm_out, scan_out)


def test_qgemm_keeps_unrolled_loop_for_large_dense_models(monkeypatch):
    """With qgemm active the scan threshold guards only the residual
    (non-qgemm) dequant bytes — a dense int8 model stays on the faster
    unrolled loop even when its full dequant exceeds the threshold."""
    m = tiny_gpt2(d_model=64, num_heads=4)
    eng = _quant_engine(m, m.init(jax.random.PRNGKey(0)))
    blocks = eng.params["blocks"]
    monkeypatch.setattr(serving, "QUANT_SCAN_THRESHOLD", 0)
    with serving.qgemm_scope(True):
        assert serving.qgemm_active(blocks)
        assert not serving.use_scan_decode(blocks)
    with serving.qgemm_scope(False):
        assert not serving.qgemm_active(blocks)
        assert serving.use_scan_decode(blocks)


# --------------------------------------------------------- compiled memory
def test_qgemm_decode_temp_memory_has_no_layer_dequant(monkeypatch):
    """Acceptance: the compiled qgemm decode step materializes no
    per-layer dequant — no compute-dtype buffer has the shape of a whole
    quantized weight matrix (alone, as a layer slice or layer-stacked);
    the kernel dequantizes tile by tile.  XLA's memory_analysis bounds the
    temp allocation far below the all-layers hoist the unrolled dequant
    path allowed.

    The temp allocation is NOT held under one layer's compute-dtype bytes
    (the first form of this test, red on every tree since the seed): under
    jax 0.9 XLA:CPU keeps a copy of the layer-stacked int8 weights that the
    interpreted kernels' loops carry (12.6 MB here, by coincidence exactly
    one fp32 layer at L=4) beside the KV cache's, 20.7 MB in all — none of
    it a dequantized weight, which the text shows and a byte count cannot."""
    import re
    monkeypatch.setenv("DS_QGEMM_INTERPRET", "1")
    L, D = 4, 512
    m = tiny_gpt2(d_model=D, num_heads=4, num_layers=L, vocab_size=128,
                  max_seq_len=64)
    eng = _quant_engine(m, m.init(jax.random.PRNGKey(0)))
    cache = m.init_cache_fn(2, 64, None)
    toks = jnp.zeros((2,), jnp.int32)
    lens = jnp.full((2,), 3, jnp.int32)
    with serving.qgemm_scope(True):
        fn = jax.jit(lambda p, t, c, l: m.decode_fn(p, t, c, l))
        compiled = fn.lower(eng.params, toks, cache, lens).compile()
    M = 4 * D
    text = compiled.as_text()
    for k, n in ((D, 3 * D), (D, M), (M, D)):
        # the weights are there, quantized, and never in the compute dtype
        assert re.search(rf"s8\[(\d+,)?{k},{n}\]", text), (k, n)
        dequantized = re.findall(rf"f32\[(?:\d+,)?{k},{n}\]", text)
        assert not dequantized, (k, n, len(dequantized))
    temp = int(getattr(compiled.memory_analysis(), "temp_size_in_bytes", 0))
    itemsize = 4                                    # fp32 compute on CPU
    per_layer = (D * 3 * D + D * D + D * M + M * D) * itemsize
    assert 0 < temp < L * per_layer / 2, (temp, L * per_layer)


# ------------------------------------------------------------- CI / tooling
@pytest.mark.slow
def test_qgemm_sweep_script_smoke():
    """Off-chip plumbing smoke for the on-chip block sweep script."""
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu", QGEMM_SWEEP_SMOKE="1")
    out = subprocess.run(
        [sys.executable, "scripts/qgemm_sweep.py"], env=env,
        capture_output=True, text=True, timeout=240,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    assert '"blocks"' in out.stdout
