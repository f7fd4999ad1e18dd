"""ds_fused_layer cache-stream block sweep (ISSUE 12 satellite) — the
qgemm_sweep playbook applied to the decode megakernel: on-chip A/B over
``block_s`` (the KV-cache stream block, DS_FUSED_DECODE_BLOCKS) at the
serving-relevant layer shapes, slope-timed (on-device fori_loop chains;
value-fetch sync — see scripts/bench_util.py).

    python scripts/fused_sweep.py                     # gpt2-125m layer
    FUSED_SHAPES=2048x16x128 FUSED_S=4096 python scripts/fused_sweep.py
    FUSED_SWEEP_SMOKE=1 python scripts/fused_sweep.py # CPU interpret smoke

Kinds swept per shape: ``decode`` (W=1 float cache), ``window`` (W=8 —
the spec-verify / chunk surface), ``int8kv`` (W=1 int8 cache), and
``int8w`` (W=1 int8 weights) — the float and quantized optima differ
(the int8 paths add in-kernel scale expansions), so a winner prints PER
KIND.  Off-TPU (smoke) it runs a tiny interpret-mode shape — plumbing
only, no timing claims.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from scripts.bench_util import timed_chain


def _mk_weights(rng, D, H, hd, M, dtype, int8w):
    mk = lambda shape: jnp.asarray(rng.standard_normal(shape), dtype) * 0.2
    cw = {"n1_s": jnp.ones((D,), dtype), "n1_b": jnp.zeros((D,), dtype),
          "wqkv": mk((D, 3 * D)), "bqkv": jnp.zeros((3 * D,), dtype),
          "wo": mk((D, D)), "bo": jnp.zeros((D,), dtype),
          "n2_s": jnp.ones((D,), dtype), "n2_b": jnp.zeros((D,), dtype),
          "w_in": mk((D, M)), "b_in": jnp.zeros((M,), dtype),
          "w_out": mk((M, D)), "b_out": jnp.zeros((D,), dtype)}
    if int8w:
        from deepspeed_tpu.models.model import QuantizedTensor
        from deepspeed_tpu.ops.pallas.quantization import \
            block_quantize_int8
        for k in ("wqkv", "wo", "w_in", "w_out"):
            q, s = block_quantize_int8(np.asarray(cw[k], np.float32))
            cw[k] = QuantizedTensor(jnp.asarray(q), jnp.asarray(s),
                                    str(dtype))
    return cw


def main():
    from deepspeed_tpu.ops.pallas.fused_decode import (FusedLayerSpec,
                                                       ds_fused_layer)
    from deepspeed_tpu.ops.pallas.decode_attention import quantize_kv

    smoke = bool(int(os.environ.get("FUSED_SWEEP_SMOKE", "0")))
    on_tpu = "tpu" in str(jax.devices()[0]).lower()
    if smoke or not on_tpu:
        shapes = [(32, 4, 8)]               # D x H x hd
        S = 64
        B = 2
        blocks = [32, 64]
        steps = 2
        interpret = True
        dtype = jnp.float32
        kinds = ["decode", "window", "int8kv", "int8w"]
    else:
        env = os.environ.get("FUSED_SHAPES", "768x12x64,2048x16x128")
        shapes = [tuple(int(v) for v in s.split("x"))
                  for s in env.split(",")]
        S = int(os.environ.get("FUSED_S", 2048))
        B = int(os.environ.get("FUSED_B", 8))
        blocks = [128, 256, 512, 1024, 2048]
        steps = int(os.environ.get("FUSED_STEPS", 20))
        interpret = False
        dtype = jnp.bfloat16
        kinds = ["decode", "window", "int8kv", "int8w"]

    rng = np.random.default_rng(0)
    for (D, H, hd) in shapes:
        M = 4 * D
        spec = FusedLayerSpec(num_heads=H, num_kv_heads=H, head_dim=hd,
                              d_model=D, norm="ln", qkv="fused",
                              mlp="gelu_tanh")
        lengths = jnp.asarray(rng.integers(S // 2, S - 9, (B,)), jnp.int32)
        k_f = jnp.asarray(rng.standard_normal((B, S, H, hd)), dtype)
        v_f = jnp.asarray(rng.standard_normal((B, S, H, hd)), dtype)
        kq, ks = quantize_kv(k_f)
        vq, vs = quantize_kv(v_f)
        cw = _mk_weights(rng, D, H, hd, M, dtype, int8w=False)
        cwq = _mk_weights(rng, D, H, hd, M, dtype, int8w=True)
        best = {}
        for kind in kinds:
            W = 8 if kind == "window" else 1
            weights = cwq if kind == "int8w" else cw
            quant = kind == "int8kv"
            x0 = jnp.asarray(rng.standard_normal((B, W, D)), dtype)
            for bs in blocks:
                if bs > S:
                    continue

                def step(state, _bs=bs, _w=weights, _q=quant):
                    x, acc = state
                    out = ds_fused_layer(
                        x, _w, kq if _q else k_f, vq if _q else v_f,
                        lengths, spec,
                        ks_l=ks if _q else None, vs_l=vs if _q else None,
                        block_s=_bs, interpret=interpret)
                    return (jnp.tanh(out[0]) + x, acc + jnp.sum(out[0]))

                try:
                    sec = max(timed_chain(step, (x0, jnp.float32(0)),
                                          steps), 0.0)
                except Exception as e:  # keep sweeping past bad tilings
                    print(json.dumps({"shape": f"{D}x{H}x{hd}",
                                      "kind": kind, "block_s": bs,
                                      "error": str(e)[:200]}))
                    continue
                row = {"shape": f"{D}x{H}x{hd}", "kind": kind, "W": W,
                       "S": S, "B": B, "block_s": bs,
                       "us_per_layer": round(sec * 1e6, 2)}
                print(json.dumps(row))
                if sec > 0 and (kind not in best or sec < best[kind][0]):
                    best[kind] = (sec, row)
        # winner PER KIND: float/int8 optima differ (scale expansions)
        for kind, (sec_w, row) in sorted(best.items()):
            print(json.dumps({"shape": f"{D}x{H}x{hd}", "kind": kind,
                              "winner": row}))
            from scripts.bench_util import emit_ledger
            emit_ledger({"metric": f"fused_sweep_{kind}_{D}x{H}x{hd}",
                         "value": row["us_per_layer"],
                         "unit": "us_per_layer",
                         "direction": "lower_better",
                         "detail": {"block_s": row["block_s"]}})


if __name__ == "__main__":
    main()
