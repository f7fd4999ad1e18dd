"""The delta rule with a decay a key channel alone, on the chip, at a
benchmark cell's shape: its Mosaic kernels (ops/pallas/kda.py) against the
XLA chunked form (ops/linear_attention.py ``_chunked_xla_channel``) — ms of
the value and of the value and gradient in all five arguments, and how far
the two lowerings' results lie apart.

    chiprun --chips 1 -- python scripts/kda_rule_bench.py [--seq 16384]
        [--heads 32] [--seed <n>]

One JSON line.  Refuses the CPU: a time from there is no device number.
"""
import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def inputs(seed, S, H, d, dt):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), dt)
    q, k, v = f(1, S, H, d), f(1, S, H, d), f(1, S, H, d)
    # as the layer makes them: -exp(A_log) * softplus(.), A 1 .. 16
    g = -jnp.asarray(rng.uniform(1, 16, size=(1, 1, H, 1))
                     * np.logaddexp(0, rng.normal(size=(1, S, H, d)) - 2),
                     jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, size=(1, S, H)), jnp.float32)
    # documents that end inside chunks, at an edge and after one token
    ends = np.sort(rng.choice(np.arange(1, S), size=max(S // 4096, 1),
                              replace=False))
    edges = np.unique(np.concatenate([ends, [S // 2, S // 2 + 1]]))
    seg = np.searchsorted(edges, np.arange(S), side="right")
    return (q, k, v, g, beta), jnp.asarray(seg[None], jnp.int32)


def timed(fn, args, repeats):
    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t) * 1e3)
    return out, float(np.median(times))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        raise SystemExit("kda_rule_bench: needs a TPU, found a CPU")
    from deepspeed_tpu.ops import linear_attention as la
    d = args.width
    xs, seg = inputs(args.seed, args.seq, args.heads, d, jnp.bfloat16)
    weights = jnp.asarray(np.random.default_rng(7).normal(size=xs[2].shape),
                          jnp.float32)
    out = {"device": dev.device_kind, "seq": args.seq, "heads": args.heads,
           "width": d}
    results = {}
    for name, interpret in (("kernel", None), ("xla", False)):
        rule = lambda *a: la.gated_delta_rule(
            *a, seg, interpret=interpret, l2norm_scales=(d ** -0.5, 1.0))
        loss = lambda *a: jnp.sum(rule(*a).astype(jnp.float32) * weights)
        o, out[f"{name}_value_ms"] = timed(jax.jit(rule), xs, args.repeats)
        grads, out[f"{name}_value_and_grad_ms"] = timed(
            jax.jit(jax.grad(loss, range(5))), xs, args.repeats)
        results[name] = (o,) + tuple(grads)
    rel = lambda a, b: float(
        jnp.linalg.norm((a - b).astype(jnp.float32).ravel())
        / jnp.linalg.norm(b.astype(jnp.float32).ravel()))
    out["kernel_from_xla"] = {
        n: rel(a, b) for n, a, b in zip(
            ("o", "dq", "dk", "dv", "dg", "dbeta"), *results.values())}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
