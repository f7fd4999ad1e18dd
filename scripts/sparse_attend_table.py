"""What the sparse layer's attend stage costs on the chip, alone and by
lowering: ``ops/sparse_attention.py selected_attention`` at the MiniCPM-SALA
cell's shape (one packed sequence of 16,384, 32 query heads to 2 key/value
heads of 128, bfloat16; documents drawn as the cell's traffic draws them,
the selection ``select_blocks``' own on the same q and k) as the XLA form
(``masked_chunks``, the cell's ``attend_query_chunk`` / ``attend_key_spans``)
and as the Mosaic kernels of ``ops/pallas/selected_attention.py`` — per tile
shape the forward call, each backward kernel alone, what makes the mask's
operands, and a forward + backward call — slope-timed
(``scripts/bench_util.py timed_chain``), each beside its share of the bf16
peak over the keys a query *keeps* (the benchmark's
``sparse.attend_roofline`` counts the same) and over the keys the lowering
*visits*, and how far the two lowerings' values and gradients are apart.

    chiprun --chips 1 -- python scripts/sparse_attend_table.py \\
        [--tiles 512x512,256x512] [--seed 1] [--out chiprun_out/<f>.json]

One JSON line a row.  Fails without a TPU: a time from the CPU is not a
time.
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

B, H, G, HD = 1, 32, 2, 128
TRAFFIC = "packed-s16384-longdocs"
QUERY_CHUNK, KEY_SPANS = 128, 4         # the cell's, for the XLA form


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tiles", default="",
                    help="tile shapes beside the library's own, as "
                         "<queries>x<keys>,...")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--skip-xla", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        sys.exit("sparse_attend_table: no TPU here; a kernel's time comes "
                 "from the chip")
    from deepspeed_tpu.ops import sparse_attention as sa
    from deepspeed_tpu.ops.pallas import selected_attention as kernels
    from harness import device
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           TRAFFIC + ".json")) as f:
        traffic = json.load(f)
    S, sel, bf = traffic["seq_len"], sa.BlockSelection(), jnp.bfloat16
    seg = segments(traffic, args.seed)
    key = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    q = jax.random.normal(key[0], (B, S, H, HD), bf)
    k = jax.random.normal(key[1], (B, S, G, HD), bf)
    v = jax.random.normal(key[2], (B, S, G, HD), bf)
    w = jax.random.normal(key[3], (B, S, H, HD), bf)
    blocks, count = jax.jit(lambda q, k: sa.select_blocks(q, k, seg, sel))(
        q, k)
    counts = {n: float(x) for n, x in
              sa.selection_counts(blocks, count, seg, sel).items()}
    required = counts["sparse/required_keys_per_query"]
    peak = device.peaks_for(jax.devices()[0].device_kind)["bf16_flops_per_s"]
    # 4 H hd operations a (query, key) forward, 10 backward
    share = lambda keys, passes, ms: round(
        100 * passes * H * HD * keys * S * B / peak / (ms * 1e-3), 2)

    def attend(interpret):
        return lambda q, k, v: sa.selected_attention(
            q, k, v, blocks, seg, sel, query_chunk=QUERY_CHUNK,
            key_spans=KEY_SPANS, interpret=interpret)

    def fwd_of(fn):
        return lambda s: (s[0] + 1e-3 * fn(*s),) + s[1:]

    def fwd_bwd_of(fn):
        def step(s):
            grads = jax.grad(lambda *a: jnp.sum(
                (fn(*a) * w).astype(jnp.float32)), (0, 1, 2))(*s)
            return tuple(x + 1e-3 * g for x, g in zip(s, grads))
        return step

    rows = []

    def say(row):
        row["device"] = jax.devices()[0].device_kind
        rows.append(row)
        print(json.dumps(row), flush=True)

    def whole_call(name, fn, visited, **more):
        fwd = timed_chain(fwd_of(fn), (q, k, v), args.steps) * 1e3
        both = timed_chain(fwd_bwd_of(fn), (q, k, v), args.steps) * 1e3
        say({"lowering": name, **more, "fwd_ms": round(fwd, 3),
             "fwd_bwd_ms": round(both, 3),
             "visited_keys_per_query": visited,
             "required_keys_per_query": required,
             "fwd_pct_of_peak_required": share(required, 4, fwd),
             "fwd_pct_of_peak_visited": share(visited, 4, fwd),
             "fwd_bwd_pct_of_peak_required": share(required, 14, both),
             "fwd_bwd_pct_of_peak_visited": share(visited, 14, both),
             # a step: the forward, the layer's recompute, the backward
             "step_ms_fwd_fwd_bwd": round(fwd + both, 3)})

    if not args.skip_xla:
        whole_call("masked_chunks", attend(False),
                   sa.visited_keys_per_query(S, QUERY_CHUNK, KEY_SPANS))

    operands = jax.jit(lambda blocks: sa.mask_operands(blocks, seg, sel, bf))
    incol, kept, start = operands(blocks)
    mask_ms = timed_chain(
        lambda s: (s[0] + (jnp.sum(operands(s[0])[1].astype(jnp.float32))
                           > 1e30).astype(jnp.int32),), (blocks,), 5) * 1e3
    say({"call": "mask_operands", "ms": round(mask_ms, 3)})

    own = kernels.blocking(S, H // G, HD, sel.block_size, 2)
    shapes = [(own.block_q, own.block_k)] + [
        tuple(int(n) for n in t.split("x"))
        for t in args.tiles.split(",") if t]
    rule = kernels.TILES
    for bq, bk in shapes:
        kernels.TILES = (bq, bk)
        tiles = kernels.blocking(S, H // G, HD, sel.block_size, 2)
        visited = kernels.visited_keys_per_query(S, bq, bk)
        try:
            whole_call("mosaic_tiles", attend(None), visited,
                       blocks=[bq, bk],
                       tiles=kernels.visited_tiles(S, bq, bk))
            # a kernel alone: the other backward call has no reader and
            # XLA drops it; lse and delta are one forward's, held
            o, res = jax.jit(lambda q, k, v: kernels._selected_fwd(
                q, k, v, incol, kept, start, tiles, False))(q, k, v)
            lse = res[4]
            delta = jnp.transpose(jnp.sum(
                (w * o).astype(jnp.float32), -1).reshape(B, S, G, H // G),
                (0, 2, 3, 1))
            back = lambda q, k, v: kernels._backward(
                kernels._by_group(q, G), kernels._by_group(w, G), lse,
                delta, kernels._flat(k), jnp.transpose(k, (0, 2, 3, 1)),
                kernels._flat(v), incol, kept, start, tiles, False)

            def dq(s):
                return (s[0] + 1e-3 * kernels._from_group(back(*s)[0]),) \
                    + s[1:]

            def dkv(s):
                _, dk, dv = back(s[2], s[0], s[1])
                return (s[0] + 1e-3 * dk.reshape(k.shape),
                        s[1] + 1e-3 * dv.reshape(v.shape), s[2])

            dq_ms = timed_chain(dq, (q, k, v), args.steps) * 1e3
            dkv_ms = timed_chain(dkv, (k, v, q), args.steps) * 1e3
            say({"call": "backward kernels alone", "blocks": [bq, bk],
                 "ds_sel_bwd_dq_ms": round(dq_ms, 3),
                 "ds_sel_bwd_dkv_ms": round(dkv_ms, 3),
                 "dq_pct_of_peak_visited": share(visited, 6, dq_ms),
                 "dkv_pct_of_peak_visited": share(visited, 8, dkv_ms)})
        except Exception as e:          # a tile shape Mosaic refuses
            say({"lowering": "mosaic_tiles", "blocks": [bq, bk],
                 "error": f"{type(e).__name__}: {e}"[-400:]})
        finally:
            kernels.TILES = rule

    # the two lowerings, value and every gradient
    close = lambda a, b: float(
        jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
        / jnp.max(jnp.abs(b.astype(jnp.float32))))
    both = lambda fn: jax.jit(lambda q, k, v: (fn(q, k, v),) + jax.grad(
        lambda *a: jnp.sum((fn(*a) * w).astype(jnp.float32)),
        (0, 1, 2))(q, k, v))
    apart = dict(zip(("o", "dq", "dk", "dv"), (
        close(a, b) for a, b in zip(both(attend(None))(q, k, v),
                                    both(attend(False))(q, k, v)))))
    out = {"device": jax.devices()[0].device_kind, "seed": args.seed,
           "shape": {"b": B, "S": S, "heads": H, "kv_heads": G,
                     "head_dim": HD}, "selection": counts, "rows": rows,
           "kernels_against_xla": apart}
    print(json.dumps({"kernels_against_xla": apart, "selection": counts}))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
