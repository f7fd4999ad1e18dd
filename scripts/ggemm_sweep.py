"""ds_ggemm block-shape sweep (ISSUE 8 satellite) — the qgemm_sweep
playbook applied to the grouped expert GEMM: on-chip A/B over TPU-legal
(bm, bk, bn) tile shapes at MoE-relevant grouped shapes (prefill-scale
token counts routed over E experts, K/N = the model's expert FFN dims),
slope-timed (on-device fori_loop chains; only slopes between step
counts are trustworthy — see scripts/bench_util.py).

    python scripts/ggemm_sweep.py                      # mixtral-8x7B dims
    GGEMM_T=4096 GGEMM_E=8 GGEMM_SHAPES=4096x14336 python scripts/ggemm_sweep.py
    GGEMM_SWEEP_SMOKE=1 python scripts/ggemm_sweep.py  # CPU plumbing smoke

Per (shape, blocks) prints one JSON line each for the float and the
fused-dequant int8 grouped kernel (per-call slope µs + achieved expert
weight-stream GB/s), then the winner per shape; the winning tuple is
what ``DS_GGEMM_BLOCKS=bm,bk,bn`` pins.  The decode-regime slot kernel
(ops/pallas/grouped_gemm.py ds_ggemm_slots) has no M-tiling to sweep —
its row block is the padded batch — so it gets one reference row per
shape at the default (bk, bn).  Off-TPU (smoke) everything runs tiny
interpret-mode shapes — plumbing only, no timing claims.
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
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    from deepspeed_tpu.ops.pallas.quantization import block_quantize_int8

    smoke = bool(int(os.environ.get("GGEMM_SWEEP_SMOKE", "0")))
    on_tpu = "tpu" in str(jax.devices()[0]).lower()
    if smoke or not on_tpu:
        shapes = [(64, 128)]
        T, E, top_k = 24, 4, 2
        grid = [(8, 64, 128)]
        steps = 2
        interpret = True
        dtype = jnp.float32
        decode_rows = 4
    else:
        # mixtral-8x7B expert FFN GEMMs by default: in [4096, 14336],
        # out [14336, 4096]
        env = os.environ.get("GGEMM_SHAPES", "4096x14336,14336x4096")
        shapes = [tuple(int(v) for v in s.split("x"))
                  for s in env.split(",")]
        T = int(os.environ.get("GGEMM_T", 4096))
        E = int(os.environ.get("GGEMM_E", 8))
        top_k = int(os.environ.get("GGEMM_TOPK", 2))
        bms = [128, 256, 512]
        bks = [256, 512, 1024]
        bns = [256, 512, 1024, 2048]
        grid = list(itertools.product(bms, bks, bns))
        steps = int(os.environ.get("GGEMM_STEPS", 20))
        interpret = False
        dtype = jnp.bfloat16
        decode_rows = int(os.environ.get("GGEMM_DECODE_B", 8)) * top_k

    rng = np.random.default_rng(0)
    R = T * top_k
    eids = jnp.asarray(rng.integers(0, E, (R,)), jnp.int32)
    for (K, N) in shapes:
        w = jnp.asarray(rng.standard_normal((E, K, N)), jnp.float32)
        q, s = block_quantize_int8(w)
        w = w.astype(dtype)
        rows = jnp.asarray(rng.standard_normal((R, K)), dtype)
        best = {}                   # per kind: float and int8 tilings
        #                             can differ (the int8 kernel adds
        #                             the per-tile scale expansion)
        for bm, bk, bn in grid:
            plan = gg.make_group_plan(eids, E, block_m=bm)
            x0 = gg.scatter_to_groups(rows, plan)

            def step(state, _w=None, _bk=bk, _bn=bn, _plan=plan):
                x, acc = state
                y = gg.ds_ggemm(x, _w, _plan, block_k=_bk, block_n=_bn,
                                interpret=interpret)
                # data dependency so the chain cannot be elided
                carry = x + jnp.tanh(y[:, :1]).astype(x.dtype)
                return (carry, acc + jnp.sum(y).astype(jnp.float32))

            for tag, wt, wbytes in (
                    ("f", w, int(w.size) * w.dtype.itemsize),
                    ("int8", (q, s), int(q.size) + 4 * int(s.size))):
                try:
                    sec = max(timed_chain(
                        lambda st, _wt=wt, _bk=bk, _bn=bn, _plan=plan:
                        step(st, _wt, _bk, _bn, _plan),
                        (x0, jnp.float32(0)), steps), 0.0)
                except Exception as e:  # keep sweeping past illegal tilings
                    print(json.dumps({"shape": f"{K}x{N}", "kind": tag,
                                      "blocks": [bm, bk, bn],
                                      "error": str(e)[:200]}))
                    continue
                gbs = wbytes / sec / 1e9 if sec > 0 else None
                row = {"shape": f"{K}x{N}", "kind": tag, "tokens": T,
                       "experts": E, "top_k": top_k,
                       "blocks": [bm, bk, bn],
                       "us_per_call": round(sec * 1e6, 2),
                       "weight_stream_GBs": round(gbs, 1) if gbs else None}
                print(json.dumps(row))
                if sec > 0 and (tag not in best or sec < best[tag][0]):
                    best[tag] = (sec, row)
        for tag, (sec_w, row) in sorted(best.items()):
            print(json.dumps({"shape": f"{K}x{N}", "kind": tag,
                              "winner": row}))
            from scripts.bench_util import emit_ledger
            emit_ledger({"metric": f"ggemm_sweep_{tag}_{K}x{N}",
                         "value": round(sec_w * 1e6, 2),
                         "unit": "us_per_call",
                         "direction": "lower_better",
                         "detail": {"blocks": str(row["blocks"])}})

        # decode-regime slot kernel: one row per shape (no M sweep — the
        # row block is the padded batch; bk/bn ride the defaults)
        d_eids = jnp.asarray(rng.integers(0, E, (decode_rows,)), jnp.int32)
        d_rows = jnp.asarray(rng.standard_normal((decode_rows, K)), dtype)
        splan = gg.make_slot_plan(d_eids, E)

        def slot_step(state):
            x, acc = state
            y = gg.ds_ggemm_slots(x, (q, s), splan, interpret=interpret)
            carry = x + jnp.tanh(y[:, :1]).astype(x.dtype)
            return (carry, acc + jnp.sum(y).astype(jnp.float32))

        try:
            sec = max(timed_chain(slot_step, (d_rows, jnp.float32(0)),
                                  steps), 0.0)
            distinct = min(decode_rows, E)
            sbytes = (int(q.size) + 4 * int(s.size)) * distinct // E
            print(json.dumps({
                "shape": f"{K}x{N}", "kind": "int8_slots",
                "rows": decode_rows, "distinct_experts_bound": distinct,
                "us_per_call": round(sec * 1e6, 2),
                "weight_stream_GBs": (round(sbytes / sec / 1e9, 1)
                                      if sec > 0 else None)}))
        except Exception as e:
            print(json.dumps({"shape": f"{K}x{N}", "kind": "int8_slots",
                              "error": str(e)[:200]}))


if __name__ == "__main__":
    main()
