"""What the head and the loss cost on the chip, alone, at each benchmark
cell's (tokens a micro-batch of one chip, d_model, vocabulary): the loss
and both gradients of ``models.model.token_loss(h @ w)`` — whole logits —
beside ``models.model.head_token_loss`` at chunks of 1,024 / 2,048 / 4,096 /
8,192 tokens and at the chunk the library's rule gives
(``head_chunk_tokens``), bfloat16 ``h`` and ``w``: ms a call, slope-timed
(``scripts/bench_util.py timed_unrolled``: the gradients are the next
call's step on ``h`` and ``w``, so nothing of either is dropped), and the
compiled program's ``temp`` bytes.  The table is what the rule cites
(PERF.md section 6, PR 69).

    chiprun --chips 1 -- python scripts/head_loss_table.py \\
        [--seed 0] [--steps 2] [--cells phi-4,gpt2-760m.dense] \\
        [--chunks 1024,4096] [--out chiprun_out/<file>.json]

One JSON line per row, then one line with everything.  Refuses the CPU as
``benchmarks/run.py`` does.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: cell -> (tokens a micro-batch of one chip, d_model, vocabulary, tied):
#: micro-batch x S of each cell's traffic file, the widths of its
#: configuration file (benchmarks/configs, benchmarks/traffic)
CELLS = {
    "gpt2-760m.dense-s1024": (12288, 1536, 50257, True),
    "gpt2-760m.packed-s2048-gas4": (12288, 1536, 50257, True),
    "gpt2-2.7b-zero3x4.dense-s2048": (8192, 2560, 50257, True),
    "olmoe-1b-7b.packed-s4096-gas8": (4096, 2048, 50304, False),
    "qwen3-next-80b-a3b.packed-s8192-gas2": (16384, 2048, 18992, False),
    "nemotron-3-nano-30b-a3b.packed-s8192-gas2": (16384, 2688, 16384, False),
    "joyai-llm-flash.packed-s8192-gas2": (16384, 2048, 16160, False),
    "laguna-s-2.1.packed-s8192-gas4": (8192, 3072, 12544, False),
    "mellum2-12b-a2.5b-ep4.packed-s8192-gas4-ep": (8192, 2304, 98304, False),
    "kimi-linear-48b-a3b.packed-s16384-traces": (16384, 2304, 20480, False),
    "xing4.0-29b-a4b.packed-s4096-pretrain": (8192, 3584, 16384, False),
    "phi-4-mini-flash-reasoning.packed-s16384-traces":
        (16384, 2560, 25008, True),
    "minicpm-sala.packed-s16384-longdocs": (16384, 4096, 9181, False),
    "granite-4.0-h-small.packed-s4096-gas1": (4096, 4096, 12544, True),
    # four passes' states side by side through one head (models/ouro.py)
    "ouro-2.6b.packed-s16384-traces": (65536, 2048, 49152, False),
}
CHUNKS = (1024, 2048, 4096, 8192)


def losses(tied: bool):
    """{"whole": ..., "chunked": ...}: ``(h, w, batch) -> mean loss``."""
    from deepspeed_tpu.models.model import head_token_loss, token_loss
    return {
        "whole": lambda h, w, batch: token_loss(
            h @ (w.T if tied else w), batch),
        "chunked": lambda h, w, batch: head_token_loss(
            h, w, batch, tied=tied)}


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--steps", type=int, default=2)
    parser.add_argument("--cells", default="",
                        help="substrings of cell names, comma-separated")
    parser.add_argument("--chunks", default=",".join(map(str, CHUNKS)))
    parser.add_argument("--out")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"head_loss_table: needs a TPU, jax found platform="
                 f"{device.platform}")
    from deepspeed_tpu.models import model
    from scripts.bench_util import timed_unrolled

    wanted = [s for s in args.cells.split(",") if s]
    shapes = {}                         # the cells of one shape share a row
    for cell, shape in CELLS.items():
        if not wanted or any(s in cell for s in wanted):
            shapes.setdefault(shape, []).append(cell)
    rule = model.head_chunk_tokens
    rows = []
    for (t, D, V, tied), cells in shapes.items():
        k = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 3)
        h = jax.random.normal(k[0], (1, t, D)).astype(jnp.bfloat16)
        w = (jax.random.normal(k[1], (V, D) if tied else (D, V)) * 0.02
             ).astype(jnp.bfloat16)
        batch = {"input_ids": jax.random.randint(k[2], (1, t), 0, V),
                 "segment_ids": jnp.arange(t)[None, :] // 1000}
        chunks = sorted({c for c in map(int, args.chunks.split(","))
                         if c < t} | {rule(t, V)})
        # whole float32 logits past half a chip's memory have no row (the
        # looped cell's four passes side by side: 12.9 GB)
        whole = [("whole", None)] if 4 * t * V <= 8 * 2 ** 30 else []
        for name, chunk in whole + [("chunked", c) for c in chunks]:
            # the rule's answer for this row alone (read at trace time)
            model.head_chunk_tokens = (
                rule if chunk is None else lambda *_, c=chunk: c)
            grad = jax.value_and_grad(losses(tied)[name], argnums=(0, 1))

            def step(state, grad=grad):
                h, w = state
                _, (dh, dw) = grad(h, w, batch)
                return h - dh.astype(h.dtype), w - dw.astype(w.dtype)

            ms = timed_unrolled(step, (h, w), args.steps) * 1e3
            temp = jax.jit(grad).lower(h, w, batch).compile() \
                .memory_analysis().temp_size_in_bytes
            rows.append({
                "cells": cells, "tokens": t, "d_model": D, "vocab": V,
                "tied": tied, "loss": name, "chunk": chunk,
                "is_the_rules": chunk == rule(t, V), "ms": round(ms, 3),
                "temp_bytes": int(temp),
                # three products of 2 t D V at the chip's bf16 peak
                "products_floor_ms": round(6 * t * D * V / 197e12 * 1e3, 3)})
            print(json.dumps(rows[-1]), flush=True)
    model.head_chunk_tokens = rule
    out = {"device": device.device_kind, "steps": args.steps, "rows": rows}
    print(json.dumps(out))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
