"""Phi-4-mini-flash's new kernel calls on the chip, at the cell's shapes
(one packed sequence of 16,384 reasoning-trace tokens, bfloat16): one JSON
line a row, slope-timed (scripts/bench_util.py ``timed_chain``).

* ``diff_attention``: one differential layer's flash calls, forward +
  backward, in the two forms the mathematics allows — **two calls** of 20
  heads over 10 at score width 64 and value width 128 (models/phi4flash.py)
  and **four calls** at 64 / 64 (the source's ``attn11/12/21/22``: every
  score computed twice) — under the 512-key window and causal.
* ``selective_scan``: one Mamba-1 layer's scan (5,120 channels, 16 states),
  forward and forward + backward, a row a ``--channels`` a grid step takes.

    python scripts/phi4flash_kernel_table.py [--seed 1] [--channels 512,256]

Fails without a TPU: a time from the CPU is not a time.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import jax
import jax.numpy as jnp

from scripts.bench_util import timed_chain
from scripts.flash_window_table import segments


def diff_attention(seg, S, window, form, key):
    from deepspeed_tpu.ops.pallas.ds_flash_attention import \
        ds_flash_attention
    from deepspeed_tpu.ops.attention import WINDOW_BLOCKS
    k = jax.random.split(key, 5)
    dt = jnp.bfloat16
    q1, q2 = (jax.random.normal(k[i], (1, S, 20, 64), dt) for i in (0, 1))
    k1, k2 = (jax.random.normal(k[i], (1, S, 10, 64), dt) for i in (2, 3))
    v = jax.random.normal(k[4], (1, S, 10, 128), dt)
    kw = dict(segment_ids=seg, window=window)
    if window is not None:
        kw.update(block_q=WINDOW_BLOCKS[0], block_k=WINDOW_BLOCKS[1])
    flash = lambda *a: ds_flash_attention(*a, **kw)

    def maps(q1, q2, k1, k2, v):
        if form == "two_calls_64_128":
            return flash(q1, k1, v), flash(q2, k2, v)
        halves = v[..., :64], v[..., 64:]
        return tuple(jnp.concatenate([flash(q, kk, h) for h in halves], -1)
                     for q, kk in ((q1, k1), (q2, k2)))

    def loss(*a):
        a1, a2 = maps(*a)
        return jnp.sum((a1 - 0.5 * a2).astype(jnp.float32) ** 2)

    def step(state):
        grads = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*state)
        # a data dependency from every gradient to the next step's inputs
        return tuple(a + 1e-6 * g.astype(a.dtype)
                     for a, g in zip(state, grads))

    return timed_chain(step, (q1, q2, k1, k2, v), 3) * 1e3


def selective_scan(seg, S, channels, backward, key):
    from deepspeed_tpu.ops.pallas import selective_scan as kernels
    D, N = 5120, 16
    k = jax.random.split(key, 4)
    dt = jnp.bfloat16
    u = jax.random.normal(k[0], (1, S, D), dt)
    raw = (jax.random.normal(k[1], (1, S, D)) - 4.0).astype(dt)
    B, C = (jax.random.normal(k[i], (1, S, N), dt) for i in (2, 3))
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (D, N))
    first = jnp.concatenate([jnp.ones((1, 1), bool),
                             seg[:, 1:] != seg[:, :-1]], axis=1)
    blocking = kernels.Blocking(128, 1, channels,
                                kernels.working_set(channels, N, 2))
    scan = lambda u, raw: kernels.sscan_kernels(
        u, raw, A, B, C, jnp.ones((D,)), jnp.zeros((D,)), first, blocking)

    def step(state):
        u, raw = state
        if not backward:
            return (u + 1e-6 * scan(u, raw), raw)
        du, draw = jax.grad(lambda *a: jnp.sum(
            scan(*a).astype(jnp.float32) ** 2), argnums=(0, 1))(u, raw)
        return (u + 1e-6 * du, raw + 1e-6 * draw)

    return timed_chain(step, (u, raw), 3) * 1e3


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--traffic", default="packed-s16384-traces")
    parser.add_argument("--channels", default="512,256")
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit("phi4flash_kernel_table: no TPU — a time from "
                         f"the {device.platform} is not a time")
    from harness.manifest import Manifest
    traffic = Manifest(ROOT).traffic(args.traffic)
    seg, S = segments(traffic, args.seed), traffic["seq_len"]
    key = jax.random.PRNGKey(args.seed)
    say = lambda **row: print(json.dumps(
        {**row, "device": device.device_kind, "seq_len": S,
         "documents": int(seg.max()) + 1, "seed": args.seed}), flush=True)
    for window in (512, None):
        for form in ("two_calls_64_128", "four_calls_64_64"):
            say(row="diff_attention", window=window, form=form,
                fwd_bwd_ms=diff_attention(seg, S, window, form, key))
    for channels in map(int, args.channels.split(",")):
        for backward in (False, True):
            say(row="selective_scan", channels_per_step=channels,
                passes="fwd+bwd" if backward else "fwd",
                ms=selective_scan(seg, S, channels, backward, key))


if __name__ == "__main__":
    main()
