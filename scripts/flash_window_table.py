"""The windowed flash kernels on the chip: per (block_q, block_k) one JSON
line with the milliseconds of a forward call, of each backward kernel
alone and of a forward + backward call (``ds_flash_win_fwd``;
``ds_flash_win_bwd_dkv``, ``_bwd_dq``) at a sliding layer's shapes — by
default Laguna-S-2.1's cell: one packed sequence of 8,192, 72 query heads
to 8 KV heads of 128, a window of 512 keys, documents drawn as the cell's
traffic draws them — beside the same
shapes under the causal mask alone (the tiles only masked: what the window
saves) and a full layer's call (48 heads, causal).  Slope-timed
(scripts/bench_util.py ``timed_chain``).  The blocks
``ops/attention.py WINDOW_BLOCKS`` holds are the ones chosen from this
table (PERF.md section 6, PR 42).  Any cell's flash call is one
command: its ``--traffic`` (an unpacked one: no segment ids), ``--heads``,
``--kv-heads``, ``--head-dim`` and, where the value head is narrower than
the score head (latent attention: 192 / 128), ``--v-head-dim``; ``--window
0`` leaves the windowed rows out and ``--full-heads 0`` the full layer's.
``tiles`` is a head's ``[interior, boundary]`` tiles
(``ds_flash_attention.tile_counts``: wholly below the diagonal and inside
the window, or crossed by one of them; None at a commit from before it).
The packed column (PR 71): ``--rows`` rows a call, successive rows of the
traffic's stream as a micro-batch holds them, ``tiles_positional`` — what
position alone visits over them, a head's forward — and ``tiles_visited``,
what the kernels timed visit: since PR 71 from each q-block's own documents
(``ds_flash_attention.document_block_bounds``); at a commit from before it
every positional tile.  ``--checkout DIR`` times the library of another
checkout (the parent commit's, ``git archive`` into an ignored directory)
on the same rows, seed and shapes: parent against change is two commands.

    python scripts/flash_window_table.py [--seed 1] [--blocks 512x512,256x256]
    python scripts/flash_window_table.py --traffic packed-s8192-gas2 \
        --heads 32 --kv-heads 32 --head-dim 192 --v-head-dim 128 \
        --window 0 --full-heads 0 --rows 2 [--checkout .chip_checkout/parent]

Fails without a TPU: a time from the CPU is not a time.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import numpy as np
import jax
import jax.numpy as jnp

from scripts.bench_util import timed_chain

DEFAULT_BLOCKS = "512x512,512x256,256x256,256x128,128x128,1024x512"


def segments(traffic, seed, rows=1):
    from harness import datagen
    if not traffic["segment_ids"]:
        return None
    documents = datagen.Documents(np.random.default_rng(seed),
                                  traffic["documents"])
    seg = np.zeros((rows, traffic["seq_len"]), np.int32)
    for row in seg:
        lengths = [n for n, _ in documents.row(traffic["seq_len"])]
        row[:] = np.repeat(np.arange(len(lengths)), lengths)
    return jnp.asarray(seg)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--blocks", default=DEFAULT_BLOCKS)
    ap.add_argument("--traffic", default="packed-s8192-gas4")
    ap.add_argument("--window", type=int, default=512)
    ap.add_argument("--heads", type=int, default=72)
    ap.add_argument("--full-heads", type=int, default=48)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--v-head-dim", type=int, default=None,
                    help="the value head's width (default: --head-dim)")
    ap.add_argument("--rows", type=int, default=1,
                    help="rows of a call: a micro-batch's sequences")
    ap.add_argument("--checkout", default=None,
                    help="time the deepspeed_tpu of this checkout's root")
    args = ap.parse_args()
    if args.checkout:
        sys.path.insert(0, os.path.abspath(args.checkout))
    if jax.devices()[0].platform != "tpu":
        sys.exit("flash_window_table: no TPU here; a kernel's time comes "
                 "from the chip")
    from deepspeed_tpu.ops.pallas import ds_flash_attention as dsf
    from deepspeed_tpu.ops.pallas.ds_flash_attention import (
        ds_flash_attention, window_k_tiles)
    from layer_metrics.readers import window_roofline
    from harness import datagen, device
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           args.traffic + ".json")) as f:
        traffic = json.load(f)
    S, hd = traffic["seq_len"], args.head_dim
    hv = args.v_head_dim or hd
    seg = segments(traffic, args.seed, args.rows)
    peak = device.peaks_for(jax.devices()[0].device_kind)["bf16_flops_per_s"]
    need = {  # required keys a query, times two, as the rooflines count them
        "windowed": window_roofline.keys_times_two(traffic, args.window),
        "causal": datagen.effective_context(traffic)}

    def time_call(heads, window, blocks):
        key = jax.random.split(jax.random.PRNGKey(args.seed), 3)
        q = jax.random.normal(key[0], (args.rows, S, heads, hd),
                              jnp.bfloat16)
        k = jax.random.normal(key[1], (args.rows, S, args.kv_heads, hd),
                              jnp.bfloat16)
        v = jax.random.normal(key[2], (args.rows, S, args.kv_heads, hv),
                              jnp.bfloat16)
        attend = lambda q, k, v: ds_flash_attention(
            q, k, v, segment_ids=seg, window=window, block_q=blocks[0],
            block_k=blocks[1])

        def fwd(state):
            q, k, v = state
            # o is as wide as v, which may be narrower than q
            return q.at[..., :hv].add(1e-3 * attend(q, k, v)), k, v

        def fwd_bwd(state):
            q, k, v = state
            dq, dk, dv = jax.grad(lambda *a: jnp.sum(
                attend(*a).astype(jnp.float32)), (0, 1, 2))(q, k, v)
            return q + 1e-3 * dq, k + 1e-3 * dk, v + 1e-3 * dv

        # a backward kernel alone: the other's call has no reader and
        # XLA drops it; lse and delta are one forward's, held
        o, (_, _, _, _, lse) = dsf._fwd(q, k, v, seg, True, None, *blocks,
                                        window=window)
        delta = jnp.sum(jnp.transpose(o, (0, 2, 1, 3)).astype(jnp.float32),
                        axis=-1)
        grads = lambda q, k, v: dsf._bwd_calls(
            q, k, v, jnp.ones_like(o), lse, delta, seg, True, None, *blocks,
            window=window)

        def dkv(state):
            k, v, q = state
            _, dk, dv = grads(q, k, v)
            return k + 1e-3 * dk, v + 1e-3 * dv, q

        def dq(state):
            q, k, v = state
            return q + 1e-3 * grads(q, k, v)[0], k, v

        return (timed_chain(fwd, (q, k, v), 10) * 1e3,
                timed_chain(dkv, (k, v, q), 10) * 1e3,
                timed_chain(dq, (q, k, v), 10) * 1e3,
                timed_chain(fwd_bwd, (q, k, v), 5) * 1e3)

    blocks = [tuple(int(n) for n in b.split("x"))
              for b in args.blocks.split(",")]
    tile_counts = getattr(dsf, "tile_counts", None)

    def tiles_of_the_rows(bq, bk, window):
        """(visited, positional) tiles of a head's forward over the rows:
        the library's own count, or every positional tile where its tile
        loops know position only."""
        if tile_counts is None:
            return None, None
        positional = sum(tile_counts(S, bq, bk, True, window)) * args.rows
        if seg is None or not hasattr(dsf, "document_block_bounds"):
            return positional, positional
        first, _ = dsf.document_block_bounds(np.asarray(seg), bq, bk)
        return int(dsf.visited_tiles(first, S, bq, bk, True, window)[0]), \
            positional

    for what, heads, window in (("windowed", args.heads, args.window),
                                ("causal_same_heads", args.heads, None),
                                ("full_layer", args.full_heads, None)):
        if not heads or (what == "windowed" and not window):
            continue
        for bq, bk in (blocks if what == "windowed" else [(512, 512)]):
            try:
                fwd_ms, dkv_ms, dq_ms, both_ms = time_call(
                    heads, window, (bq, bk))
            except Exception as e:      # a block shape Mosaic refuses
                print(json.dumps({"call": what, "blocks": [bq, bk],
                                  "error": f"{type(e).__name__}: {e}"[:300]}),
                      flush=True)
                continue
            kind = "windowed" if what == "windowed" else "causal"
            # a forward call 4 * H hd per key, forward + backward 12 (hd
            # the mean of the two widths: scores at one, values at the other)
            flops = 0.5 * args.rows * S * heads * (hd + hv) / 2 * need[kind]
            visited, positional = tiles_of_the_rows(bq, bk, window)
            print(json.dumps({
                "call": what, "rows": args.rows, "library": dsf.__file__,
                "heads": heads, "kv_heads": args.kv_heads,
                "head_dim": hd, "v_head_dim": hv,
                "packed": seg is not None, "window": window,
                "blocks": [bq, bk],
                "tiles": tile_counts and tile_counts(S, bq, bk, True, window),
                "tiles_visited": visited, "tiles_positional": positional,
                "keys_visited_per_query": None if window is None
                else window_k_tiles(window, bq, bk) * bk,
                "required_keys_per_query": need[kind] / 2,
                "fwd_ms": fwd_ms, "bwd_dkv_ms": dkv_ms, "bwd_dq_ms": dq_ms,
                "fwd_bwd_ms": both_ms,
                "fwd_roofline_pct": 100 * 4 * flops / peak / (fwd_ms * 1e-3),
                "fwd_bwd_roofline_pct": 100 * 12 * flops / peak
                / (both_ms * 1e-3),
                "device": jax.devices()[0].device_kind}), flush=True)


if __name__ == "__main__":
    main()
