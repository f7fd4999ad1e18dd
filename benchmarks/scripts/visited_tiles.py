"""How far the score tiles a flash forward visits would follow ``--seed``
once a q-block's loop starts at its own document (ROADMAP S2 (a)): a count
on the CPU, by hand, no chip and no metric.

    python benchmarks/scripts/visited_tiles.py [--seeds 12] [--out file.json]

Today a causal q-block row visits every key block up to its diagonal (under
a window: from the window's first block) and masks the other documents'
keys, so the tiles visited are a constant of the shape
(``ds_flash_attention.tile_counts``).  With a first key block taken from
``segment_ids`` — the block that holds the first key of the document of
the q-block's first query, which is the lowest any of its queries needs —
the count follows the documents, and the documents follow the seed.

For every cell of BENCHMARK.json whose traffic is packed and every seed
this sums, over the batches a window of ``run_seconds`` holds at the
ledger's newest rate (the batches after the configuration's warm-up steps,
drawn by ``harness/datagen.BatchStream`` as a run draws them), the
``[block_q, block_k]`` tiles ONE head of ONE layer's forward would visit:
for the full causal call, and where the configuration's ``model`` block
holds a ``sliding_window`` for the windowed call at the program's window
blocks.  It prints the median over the seeds, its share of today's count
and IQR / median (``statistics.quantiles(n=4)``, as the bounds are set) —
and the same for a generator that would hand every seed the SAME documents
in another order (``permuted``: the lengths that fill the window's rows
drawn once, from seed 0, and shuffled by the seed before they are cut into
rows), which is what the contract asks of traffic whose seed changes the
work.  Nothing here names a cell: the cells, shapes and rates are read."""
import argparse
import json
import os
import statistics
import sys

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, ROOT]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from deepspeed_tpu.ops.attention import WINDOW_BLOCKS  # noqa: E402
from deepspeed_tpu.ops.pallas.ds_flash_attention import (  # noqa: E402
    _choose_blocks, tile_counts)
from harness import datagen  # noqa: E402
from harness.manifest import Manifest  # noqa: E402

FIRST_SEED = 6200000101


def newest_rates(root):
    """{cell: tokens/s/chip}, the change's side of the ledger's newest line
    of each cell."""
    rates = {}
    with open(os.path.join(root, "PERF_LEDGER.jsonl")) as f:
        for line in f:
            row = json.loads(line)
            rate = (row.get("end_to_end") or {}).get("tokens_per_s_per_chip")
            if row.get("workload") and rate:
                rates[row["workload"]] = rate[-1]
    return rates


def visited(segments, bq, bk, window=None):
    """Tiles one head's forward visits over the rows ``segments`` [rows, S]
    when q-block i starts at the key block of its first query's document
    (and no earlier than its window's first block) and stops at its
    diagonal."""
    rows, seq = segments.shape
    index = np.arange(seq)
    starts_here = np.ones((rows, seq), bool)
    starts_here[:, 1:] = segments[:, 1:] != segments[:, :-1]
    document_start = np.maximum.accumulate(
        np.where(starts_here, index, 0), axis=1)
    first_query = np.arange(seq // bq) * bq
    first = document_start[:, first_query] // bk
    if window is not None:
        first = np.maximum(
            first, np.maximum(first_query - (window - 1), 0) // bk)
    last = np.minimum((first_query + bq) // bk, seq // bk)
    return int((last - first).sum())


def permuted_rows(traffic, rows, seed):
    """``segment_ids`` [rows, S] of one fixed draw of documents (seed 0,
    as many as fill the rows) laid end to end in the order ``seed``
    shuffles them into, and cut into rows as ``datagen.Documents`` cuts."""
    seq = traffic["seq_len"]
    documents = datagen.Documents(np.random.default_rng(0),
                                  traffic["documents"])
    lengths = [n for _ in range(rows) for n, _ in documents.row(seq)]
    np.random.default_rng(seed).shuffle(lengths)
    ids = np.repeat(np.arange(len(lengths)), lengths)
    return ids.reshape(rows, seq)


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--out")
    args = parser.parse_args()
    manifest = Manifest(ROOT)
    rates = newest_rates(ROOT)
    seconds = manifest.data["run_seconds"]
    table = []
    for entry in manifest.data["workloads"]:
        cell, config, traffic = manifest.cell(entry["name"])
        if not traffic["segment_ids"]:
            continue
        seq, gas = traffic["seq_len"], traffic["gradient_accumulation_steps"]
        micro = traffic["micro_batch_per_chip"] * cell["chips"]
        steps = int(seconds * rates[cell["name"]] * cell["chips"]
                    // (gas * micro * seq))
        calls = {"full": (_choose_blocks(seq, 512, 512), None)}
        window = config["model"].get("sliding_window")
        if window is not None and window < seq:
            calls["window"] = (_choose_blocks(seq, *WINDOW_BLOCKS), window)
        counts = {kind: [] for kind in calls}
        permuted = {kind: [] for kind in calls}
        for seed in range(FIRST_SEED, FIRST_SEED + args.seeds):
            stream = datagen.BatchStream(
                traffic, config["model"]["vocab_size"], micro, seed)
            try:
                batches = [stream.next() for _ in range(
                    config["checks"]["warmup_steps"] + steps)][-steps:]
            finally:
                stream.close()
            segments = np.concatenate(
                [b["segment_ids"].reshape(-1, seq) for b in batches])
            same = permuted_rows(traffic, steps * gas * micro, seed)
            for kind, ((bq, bk), w) in calls.items():
                counts[kind].append(visited(segments, bq, bk, w))
                permuted[kind].append(visited(same, bq, bk, w))
        for kind, ((bq, bk), w) in calls.items():
            today = sum(tile_counts(seq, bq, bk, True, w)) * steps * gas \
                * micro
            table.append({
                "cell": cell["name"], "call": kind, "blocks": [bq, bk],
                "window": w, "steps": steps, "rows": steps * gas * micro,
                "tiles_today": today,
                "tiles_median": statistics.median(counts[kind]),
                "share_of_today": statistics.median(counts[kind]) / today,
                "iqr_over_median": spread(counts[kind]),
                "least_over_most": min(counts[kind]) / max(counts[kind]),
                "permuted_iqr_over_median": spread(permuted[kind]),
                "permuted_least_over_most":
                    min(permuted[kind]) / max(permuted[kind]),
                "by_seed": counts[kind], "permuted_by_seed": permuted[kind]})
            print(json.dumps({k: v for k, v in table[-1].items()
                              if not k.endswith("by_seed")}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
