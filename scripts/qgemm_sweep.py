"""ds_qgemm block-shape sweep (ISSUE 2 satellite) — the ds_flash_attention
tuning playbook applied to the fused-dequant int8 GEMM: on-chip A/B over
TPU-legal (bm, bk, bn) tile shapes at the serving-relevant GEMM shapes
(decode M = batch, K/N = the model's projection dims), slope-timed
(on-device fori_loop chains; only slopes between step counts are
trustworthy — see scripts/bench_util.py).

    python scripts/qgemm_sweep.py                     # gpt2-1.3b shapes
    QGEMM_M=8 QGEMM_SHAPES=4096x11008 python scripts/qgemm_sweep.py
    QGEMM_SWEEP_SMOKE=1 python scripts/qgemm_sweep.py # CPU plumbing smoke

Prints one JSON line per (shape, blocks) with the per-call slope in µs and
the achieved int8 weight-stream GB/s, then the winner per shape.  Off-TPU
(smoke) it runs tiny interpret-mode shapes — plumbing only, no timing
claims.
"""
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax


from scripts.bench_util import timed_chain


def main():
    from deepspeed_tpu.ops.pallas.qgemm import ds_qgemm
    from deepspeed_tpu.ops.pallas.quantization import block_quantize_int8

    smoke = bool(int(os.environ.get("QGEMM_SWEEP_SMOKE", "0")))
    on_tpu = "tpu" in str(jax.devices()[0]).lower()
    if smoke or not on_tpu:
        shapes = [(64, 128)]
        M = 4
        grid = [(8, 64, 128)]
        steps = 2
        interpret = True
        dtype = jnp.float32
    else:
        # gpt2-1.3b decode GEMMs by default: QKV [2048, 6144], proj
        # [2048, 2048], MLP [2048, 8192] / [8192, 2048]
        env = os.environ.get("QGEMM_SHAPES",
                             "2048x6144,2048x2048,2048x8192,8192x2048")
        shapes = [tuple(int(v) for v in s.split("x"))
                  for s in env.split(",")]
        M = int(os.environ.get("QGEMM_M", 4))
        bms = [8, 16, 32, 128]
        bks = [256, 512, 1024]
        bns = [256, 512, 1024, 2048]
        grid = list(itertools.product(bms, bks, bns))
        steps = int(os.environ.get("QGEMM_STEPS", 20))
        interpret = False
        dtype = jnp.bfloat16

    rng = np.random.default_rng(0)
    for (K, N) in shapes:
        w = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
        q, s = block_quantize_int8(w)
        x0 = jnp.asarray(rng.standard_normal((M, K)), dtype)
        best = None
        seen_effective = set()
        for bm, bk, bn in grid:
            # dedup on the EFFECTIVE blocks: the wrapper clamps bm to
            # round_up(M, align), so at decode M several requested bm
            # values collapse to the same kernel — time it once and label
            # it by what actually ran
            m_align = 16 if dtype == jnp.bfloat16 else 8
            bm = min(bm, -(-M // m_align) * m_align)
            key = (bm, bk, bn)
            if key in seen_effective:
                continue
            seen_effective.add(key)

            def step(state, _bm=bm, _bk=bk, _bn=bn):
                x, acc = state
                y = ds_qgemm(x, q, s, block_m=_bm, block_k=_bk, block_n=_bn,
                             interpret=interpret)
                # data dependency so the chain cannot be elided: fold the
                # output back into a [M, K] carry
                carry = jnp.tanh(y[:, :1]) + x
                return (carry, acc + jnp.sum(y))

            try:
                # clamp at 0: sub-noise slopes (tiny smoke shapes) must
                # not report a negative time
                sec = max(timed_chain(step, (x0, jnp.float32(0)), steps),
                          0.0)
            except Exception as e:  # keep sweeping past illegal tilings
                print(json.dumps({"shape": f"{K}x{N}",
                                  "blocks": [bm, bk, bn],
                                  "error": str(e)[:200]}))
                continue
            gbs = (K * N) / sec / 1e9 if sec > 0 else None
            row = {"shape": f"{K}x{N}", "M": M, "blocks": [bm, bk, bn],
                   "us_per_call": round(sec * 1e6, 2),
                   "int8_stream_GBs": round(gbs, 1) if gbs else None}
            print(json.dumps(row))
            if sec > 0 and (best is None or sec < best[0]):
                best = (sec, row)
        if best:
            print(json.dumps({"shape": f"{K}x{N}", "winner": best[1]}))
            from scripts.bench_util import emit_ledger
            emit_ledger({"metric": f"qgemm_sweep_{K}x{N}",
                         "value": round(best[0] * 1e6, 2),
                         "unit": "us_per_call",
                         "direction": "lower_better",
                         "detail": {"blocks": str(best[1]["blocks"])}})


if __name__ == "__main__":
    main()
