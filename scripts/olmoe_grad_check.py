"""Gradients of one micro-batch of a benchmark cell on the chip, at the
cell's own size: the engine's (``engine.forward``: its kernels, remat and
precision) against ``jax.grad`` of the cell's plain reference (float32,
``highest``) at the engine's own parameters.

    chiprun --chips 1 -- python scripts/olmoe_grad_check.py \
        --workload <cell> --seed <n> ...

Any training cell of BENCHMARK.json (the file keeps the name of the cell it
was written for; the default is still that one).  One JSON line per seed:
for every gradient leaf ``max |a - b| / max |b|`` and ``|a - b|_2 /
|b|_2``, engine against reference, and the same for the reference with
bf16 products against itself (the noise a bf16 program cannot be under).
A leaf whose gradient never arrived reads 1.0.  Where the model names
parts of a leaf (``meta["gradient_views"]``) each gets a row of its own,
and where its loss comes with step counts (``Model.loss_with_counts_fn``:
the routed rows over an expert layer's bound) the line carries them for
that micro-batch.
"""
import argparse
import gc
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmarks"), ROOT]

from drivers.train_steps import build_engine, build_model    # noqa: E402
from harness import datagen                                   # noqa: E402
from harness.manifest import Manifest                         # noqa: E402


def errors(got, want, views=None):
    """{leaf: max |a - b| / max |b| and |a - b|_2 / |b|_2}; ``views``
    ({name: tree -> array}) adds parts of leaves under names of their own."""
    pairs = [(jax.tree_util.keystr(path), a, b) for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want))]
    pairs += [(name, view(got), view(want))
              for name, view in (views or {}).items()]
    out = {}
    for name, a, b in pairs:
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if not b.any() and not a.any():
            # a leaf the loss does not train (a router's selection bias):
            # exactly zero on both sides
            out[name] = {"max_rel": 0.0, "l2_rel": 0.0}
            continue
        out[name] = {
            "max_rel": float(np.abs(a - b).max() / np.abs(b).max()),
            "l2_rel": float(np.linalg.norm(a - b) / np.linalg.norm(b))}
    return out


def one_seed(workload, config, traffic, seed):
    model = build_model(config)
    sizes = {**config["model"], "n_params": model.meta["n_params"]}
    engine, _ = build_engine(config, traffic, model, seed, jax.devices())
    gas = traffic["gradient_accumulation_steps"]
    first = datagen.BatchStream(
        traffic, sizes["vocab_size"], traffic["micro_batch_per_chip"]
        * engine.topology.dp_world_size, seed).next()
    micro = {k: np.asarray(v)[0] for k, v in first.items()}
    loss = float(engine.forward(micro))
    views = model.meta.get("gradient_views")
    counts = model.loss_with_counts_fn
    if counts is not None:
        counts = {k: int(v) for k, v in jax.jit(counts)(
            engine.state["params"],
            {k: jnp.asarray(v) for k, v in micro.items()})[1].items()}
    # the engine scales a micro-batch's gradient by 1 / gas
    got = jax.tree.map(lambda g: np.asarray(g.astype(jnp.float32)) * gas,
                       engine._pending_grads)
    params = jax.tree.map(np.asarray, engine.state["params"])
    # a cell of more than one chip: the reference's parameters (and so its
    # gradient) stay split as the engine held them, the batch as it takes it
    shardings = jax.tree.map(lambda a: a.sharding, engine.state["params"])
    batch_sharding = engine.batch_sharding
    # the reference's float32 gradient needs the room the engine's state
    # has — all of it: whatever of the engine is still referenced from the
    # telemetry it registered with goes too (everything kept is numpy now)
    del engine
    gc.collect()
    for array in jax.live_arrays():
        array.delete()
    jax.clear_caches()

    reference = importlib.import_module("references." + config["reference"])
    params = jax.tree.map(
        lambda p, s: jax.device_put(np.asarray(p, np.float32), s),
        params, shardings)
    ids, segments = (jax.device_put(np.asarray(micro[k]), batch_sharding)
                     for k in ("input_ids", "segment_ids"))

    def grad(matmul_dtype):
        # (a gradient lies as its parameter does: left to itself the
        # partitioner keeps every leaf's whole on every chip of four)
        fn = jax.jit(jax.value_and_grad(lambda p: reference.micro_batch_loss(
            p, ids, segments, sizes,
            matmul_dtype=matmul_dtype, remat=True)),
            out_shardings=(None, shardings))
        with jax.default_matmul_precision("highest"):
            value, grads = fn(params)
        return float(value), jax.tree.map(np.asarray, grads)

    want_loss, want = grad(None)
    low_loss, low = grad(jnp.bfloat16)
    print(json.dumps({
        "workload": workload, "seed": seed,
        "device": jax.devices()[0].device_kind,
        "micro_batch": list(micro["input_ids"].shape),
        "loss": {"engine": loss, "reference": want_loss,
                 "reference_bf16": low_loss},
        "step_counts": counts,
        "engine_vs_reference": errors(got, want, views),
        "reference_bf16_vs_reference": errors(low, want, views)}),
        flush=True)


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", default="olmoe-1b-7b.packed-s4096-gas8")
    parser.add_argument("--seed", type=int, nargs="+", required=True)
    parser.add_argument("--rehearse", action="store_true",
                        help="the cell's toy sizes, for a run on the CPU")
    parser.add_argument("--micro-batch", type=int,
                        help="sequences per micro-batch, where the cell's "
                             "own is more than the float32 reference's "
                             "gradient fits beside (a TPU program's "
                             "temporaries: 11.09 GiB of a v5e's 15.75)")
    args = parser.parse_args()
    if args.rehearse:
        sys.path.insert(0, os.path.join(ROOT, "benchmarks", "tests"))
        from rehearse import toy
        _, config, traffic = toy(Manifest(ROOT), args.workload)
    else:
        _, config, traffic = Manifest(ROOT).cell(args.workload)
    if args.micro_batch:
        traffic["micro_batch_per_chip"] = args.micro_batch
    for seed in args.seed:
        one_seed(args.workload, config, traffic, seed)
