"""ds_ggemm block-shape sweep — the qgemm_sweep playbook applied to the
grouped expert GEMM: on-chip A/B over (bm, bk, bn) tile shapes of the
three training kernels (``ds_ggemm_fwd``, ``ds_ggemm_dx``,
``ds_ggemm_dw``) and the fused-dequant int8 forward, slope-timed
(on-device fori_loop chains; only slopes between step counts are
trustworthy — see scripts/bench_util.py).

    python scripts/ggemm_sweep.py         # olmoe-1b-7b.packed-s4096-gas8
    GGEMM_T=4096 GGEMM_E=8 GGEMM_TOPK=2 GGEMM_SHAPES=4096x14336 \\
        python scripts/ggemm_sweep.py     # mixtral-8x7B's gate projection
    GGEMM_SWEEP_SMOKE=1 python scripts/ggemm_sweep.py  # CPU plumbing smoke

Defaults are the OLMoE cell's grouped calls: 4,096 tokens x 8 choices
over 64 experts, ``2048x1024`` (gate / up) and ``1024x2048`` (down).  Per
(shape, bm, blocks, kernel) one JSON line: the blocks the library ran
(``null`` blocks in ``asked`` = its own rule, grouped_gemm._choose_blocks),
the regime (``resident``: the expert's weight panel stays in VMEM across
its M-tiles | ``streamed``), per-call slope in µs, the bytes the grid
moves per call as tiled (the step account's upper bound,
telemetry/tracing.py ``grouped_gemm_rows``) with the GB/s that implies,
and the share of the chip's bf16 peak the routed rows' FLOPs reach.
``GGEMM_BMS`` / ``GGEMM_KINDS`` (of ``f,dx,dw,int8``) narrow the sweep.  Then the winner per
(shape, kind); the winning tuple is what ``DS_GGEMM_BLOCKS=bm,bk,bn``
pins.  The decode-regime slot kernel (ds_ggemm_slots) has no M-tiling to
sweep — its row block is the padded batch — so it gets one reference row
per shape at the default (bk, bn).  Off-TPU (smoke) everything runs tiny
interpret-mode shapes — plumbing only, no timing claims.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from scripts.bench_util import emit_ledger, timed_chain

SWEEP = "sweep/ggemm"            # the step account the calls count into


def _nudge(carry, out):
    """Chains call n+1 to call n through one (8, 128) corner, in place:
    a pass over the whole operand would cost a third of a kernel call."""
    r, c = min(8, carry.shape[0], out.shape[0]), min(128, carry.shape[1],
                                                     out.shape[1])
    corner = carry[:r, :c] + 1e-6 * jnp.tanh(out[:r, :c]).astype(carry.dtype)
    return lax.dynamic_update_slice(carry, corner, (0, 0))


def main():
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    from deepspeed_tpu.ops.pallas.quantization import block_quantize_int8
    from deepspeed_tpu.telemetry.mfu import peak_flops_per_device
    from deepspeed_tpu.telemetry.tracing import (count_in_step,
                                                 grouped_gemm_rows,
                                                 step_account)

    smoke = bool(int(os.environ.get("GGEMM_SWEEP_SMOKE", "0")))
    on_tpu = "tpu" in str(jax.devices()[0]).lower()
    kinds = os.environ.get("GGEMM_KINDS", "f,dx,dw,int8").split(",")
    if smoke or not on_tpu:
        shapes = [(64, 128)]
        T, E, top_k = 24, 4, 2
        bms = [8]
        steps = 2
        interpret = True
        dtype = jnp.float32
        decode_rows = 4
    else:
        # the OLMoE cell's expert FFN GEMMs by default: gate / up
        # [2048, 1024], down [1024, 2048]
        env = os.environ.get("GGEMM_SHAPES", "2048x1024,1024x2048")
        shapes = [tuple(int(v) for v in s.split("x"))
                  for s in env.split(",")]
        T = int(os.environ.get("GGEMM_T", 4096))
        E = int(os.environ.get("GGEMM_E", 64))
        top_k = int(os.environ.get("GGEMM_TOPK", 8))
        bms = [int(v) for v in
               os.environ.get("GGEMM_BMS", "128,256,512").split(",")]
        steps = int(os.environ.get("GGEMM_STEPS", 20))
        interpret = False
        dtype = jnp.bfloat16
        decode_rows = int(os.environ.get("GGEMM_DECODE_B", 8)) * top_k
    peak = peak_flops_per_device()

    def asked_blocks(K, N):
        """None = the library's rule; the K-innermost tiling it replaces;
        whole-K panels of several widths."""
        if smoke or not on_tpu:
            return [None, (64, 128)]
        cands = [None, (512, 1024), (K, 512), (K, 1024), (K, N)]
        return [c for n, c in enumerate(cands) if c not in cands[:n]]

    rng = np.random.default_rng(0)
    R = T * top_k
    eids = jnp.asarray(rng.integers(0, E, (R,)), jnp.int32)
    for (K, N) in shapes:
        w = jnp.asarray(rng.standard_normal((E, K, N)), jnp.float32)
        q, s = block_quantize_int8(w) if "int8" in kinds else (None, None)
        w = w.astype(dtype)
        rows = jnp.asarray(rng.standard_normal((R, K)), dtype)
        cots = jnp.asarray(rng.standard_normal((R, N)), dtype)
        best = {}
        for bm in bms:
            plan = gg.make_group_plan(eids, E, block_m=bm)
            x0 = gg.scatter_to_groups(rows, plan)
            dy0 = gg.scatter_to_groups(cots, plan)
            for asked in asked_blocks(K, N):
                kw = dict(interpret=interpret)
                if asked:
                    kw.update(block_k=asked[0], block_n=asked[1])

                # every step takes (carry, consts): the expert stack rides
                # the loop's state — closed over, its 268 MB would be a
                # constant of each compiled program
                def fwd(x, w_, _kw=kw, _plan=plan):
                    return _nudge(x, gg.ds_ggemm(x, w_, _plan, **_kw))

                def dx(dy, w_, _kw=kw, _plan=plan):
                    # the backward's call: blocks swapped with the dims
                    kw_t = dict(_kw)
                    if "block_k" in kw_t:
                        kw_t.update(block_k=_kw["block_n"],
                                    block_n=_kw["block_k"])
                    return _nudge(dy, gg.ds_ggemm(
                        dy, w_, _plan, transpose_rhs=True, **kw_t))

                def dw(x, consts, _kw=kw, _plan=plan):
                    # the cotangent of w alone: dx is dead code under jit
                    w_, dy = consts
                    _, vjp = jax.vjp(
                        lambda w__: gg.ds_ggemm(x, w__, _plan, **_kw), w_)
                    return _nudge(x, vjp(dy)[0][0])

                for kind, fn, state, name, shape in (
                        ("f", fwd, (x0, w), "ds_ggemm_fwd", (K, N)),
                        ("dx", dx, (dy0, w), "ds_ggemm_dx", (N, K)),
                        ("dw", dw, (x0, (w, dy0)), "ds_ggemm_dw", (K, N)),
                        ("int8", fwd, (x0, (q, s)), "ds_ggemm_q", (K, N))):
                    if kind not in kinds or (
                            kind == "int8" and asked
                            and asked[0] >= K and on_tpu and not smoke):
                        continue    # int8 keeps the K-innermost tiling
                    row = {"shape": f"{K}x{N}", "kind": kind, "tokens": T,
                           "experts": E, "top_k": top_k, "bm": bm,
                           "asked": asked}
                    try:
                        with step_account(SWEEP):
                            count_in_step(grouped_routed_rows=R,
                                          grouped_padded_rows=plan.padded_rows)
                            sec = max(timed_chain(
                                lambda st, _fn=fn: (_fn(*st), st[1]),
                                state, steps), 0.0)
                        call, = [c for c in grouped_gemm_rows(SWEEP)["calls"]
                                 if c["kernel"] == name
                                 and (c["k"], c["n"]) == shape]
                    except Exception as e:  # keep sweeping past illegal tilings
                        print(json.dumps(dict(row, error=str(e)[:200])),
                              flush=True)
                        continue
                    moved = (call["weight_bytes_per_call"]
                             + call["operand_bytes_per_call"])
                    row.update(
                        blocks=[bm, *call["blocks"]], regime=call["regime"],
                        us_per_call=round(sec * 1e6, 2),
                        bytes_per_call=moved,
                        GBs=round(moved / sec / 1e9, 1) if sec > 0 else None,
                        pct_of_bf16_peak=(
                            round(100 * 2 * R * K * N / sec / peak, 2)
                            if sec > 0 and peak else None))
                    print(json.dumps(row), flush=True)
                    if sec > 0 and (kind not in best
                                    or sec < best[kind][0]):
                        best[kind] = (sec, row)
        for kind, (sec_w, row) in sorted(best.items()):
            print(json.dumps({"shape": f"{K}x{N}", "kind": kind,
                              "winner": row}), flush=True)
            emit_ledger({"metric": f"ggemm_sweep_{kind}_{K}x{N}",
                         "value": round(sec_w * 1e6, 2),
                         "unit": "us_per_call",
                         "direction": "lower_better",
                         "detail": {"blocks": str(row["blocks"])}})

        if "int8" not in kinds:
            continue
        # decode-regime slot kernel: one row per shape (no M sweep — the
        # row block is the padded batch; bk/bn ride the defaults)
        d_eids = jnp.asarray(rng.integers(0, E, (decode_rows,)), jnp.int32)
        d_rows = jnp.asarray(rng.standard_normal((decode_rows, K)), dtype)
        splan = gg.make_slot_plan(d_eids, E)

        def slot_step(state):
            x, acc, qs = state
            y = gg.ds_ggemm_slots(x, qs, splan, interpret=interpret)
            carry = x + jnp.tanh(y[:, :1]).astype(x.dtype)
            return (carry, acc + jnp.sum(y).astype(jnp.float32), qs)

        try:
            sec = max(timed_chain(slot_step,
                                  (d_rows, jnp.float32(0), (q, s)),
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
