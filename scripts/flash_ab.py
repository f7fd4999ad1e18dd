"""Flash-kernel A/B: the from-scratch ds_flash_attention vs the tuned
stock wrapper, forward+backward at training shapes.

The dense-path dispatch default (ops/attention.py) is decided by this
measurement (PERF.md deferred list; round-3/4 VERDICT item 1): run on
the real chip at the 760M bench shape and flip the default if `ds` wins.

    python scripts/flash_ab.py                  # 760M shape (B12 S1024 H16 hd96)
    FLASH_AB_B=4 FLASH_AB_S=2048 python scripts/flash_ab.py

Prints one JSON line per kernel plus a "winner" line.  Off-TPU it runs a
tiny interpret-mode smoke (numbers meaningless, plumbing verified).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp


def main():
    from deepspeed_tpu.ops.attention import _on_tpu
    on_tpu = _on_tpu()
    if on_tpu:
        B = int(os.environ.get("FLASH_AB_B", 12))
        S = int(os.environ.get("FLASH_AB_S", 1024))
        H = int(os.environ.get("FLASH_AB_H", 16))
        hd = int(os.environ.get("FLASH_AB_HD", 96))
        steps, warmup = 20, 5
        interpret = None
    else:
        B, S, H, hd = 1, 128, 2, 64       # interpret-mode smoke
        steps, warmup = 1, 1
        interpret = True

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, hd)),
                           jnp.bfloat16) for _ in range(3))

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.ops.pallas.ds_flash_attention import \
        ds_flash_attention

    def stock(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def ds(q, k, v):
        return ds_flash_attention(q, k, v, causal=True)

    impls = {"stock": stock, "ds": ds}
    if interpret:
        from jax.experimental import pallas as pl
        import functools
        pl.pallas_call = functools.partial(pl.pallas_call, interpret=True)

    # Timing discipline: each iteration CONSUMES the previous one's
    # gradient (q <- q + eps*dq), so steps serialize by data dependency —
    # a bare re-call loop can under-report when only the final future is
    # awaited.  A known-FLOP matmul calibrates
    # the clock first; if it reads >2x faster than the chip peak allows,
    # the timings are untrustworthy and we say so.
    def timed_chain(step_fn, x0, n):
        # Loop ON DEVICE and time two step counts, reporting the SLOPE:
        # every run() pays a fixed dispatch + round-trip cost (plus a
        # fetch cost on any returned array) — the slope between m and 5m
        # steps cancels every fixed cost.  Only a scalar leaves the device.
        from jax import lax

        @jax.jit
        def run(x, m):
            x = lax.fori_loop(0, m, lambda i, xx: step_fn(xx), x)
            return jnp.sum(x.astype(jnp.float32))

        jax.block_until_ready(run(x0, warmup))

        def once(m):
            t0 = time.time()
            jax.block_until_ready(run(x0, m))
            return time.time() - t0

        t_small = min(once(n), once(n))
        t_big = min(once(5 * n), once(5 * n))
        return (t_big - t_small) / (4 * n) * 1e3

    calib_n = 2048
    w = jnp.asarray(rng.standard_normal((calib_n, calib_n)), jnp.bfloat16)
    mm = jax.jit(lambda x: jnp.tanh(x @ w))
    mm_ms = timed_chain(mm, w, steps)
    mm_tflops = (2 * calib_n ** 3 / (mm_ms * 1e-3) / 1e12
                 if mm_ms > 0 else None)
    # THIS chip's bf16 peak bounds any sane reading (2x headroom for
    # slope noise); a negative slope means host jitter swallowed the
    # measurement
    from deepspeed_tpu.telemetry.mfu import peak_flops_per_device
    timing_suspect = on_tpu and (
        mm_tflops is None
        or mm_tflops * 1e12 > 2.0 * peak_flops_per_device(env={}))
    print(json.dumps({"calibration": "matmul", "ms": round(mm_ms, 4),
                      "apparent_tflops": (round(mm_tflops, 1)
                                          if mm_tflops else None),
                      "timing_suspect": timing_suspect}))

    causal = True
    flops = 4 * B * S * S * H * hd * (0.5 if causal else 1.0) * 3.5
    results = {}
    for name, fn in impls.items():
        loss_grad = jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))

        @jax.jit
        def step(q):
            dq, dk, dv = loss_grad(q, k, v)
            # fold dk/dv into the chain so no backward pass is DCE'd
            return q + 1e-6 * dq + 1e-30 * (jnp.sum(dk) + jnp.sum(dv))
        ms = timed_chain(step, q, steps)
        results[name] = ms
        timing_suspect = timing_suspect or (on_tpu and ms <= 0)
        print(json.dumps({"kernel": name, "fwd_bwd_ms": round(ms, 3),
                          "apparent_tflops": (
                              round(flops / (ms * 1e-3) / 1e12, 1)
                              if ms > 0 else None),
                          "shape": [B, S, H, hd]}))
    winner = min(results, key=results.get)
    if timing_suspect:
        print(json.dumps({
            "winner": None,
            "error": "timings untrustworthy (calibration out of range or "
                     "non-positive slope — host jitter?); re-run before "
                     "acting on these numbers"}))
        return
    print(json.dumps({
        "winner": winner,
        "speedup": round(max(results.values()) / min(results.values()), 3),
        "action": ("flip ops/attention.py dense default to the ds kernel"
                   if winner == "ds" and on_tpu else
                   "keep the stock wrapper as the dense default"
                   if on_tpu else "smoke only (not on TPU)"),
    }))


if __name__ == "__main__":
    main()
