"""How many passes the optimizer makes over each stacked leaf of a one-chip
cell, read from the cell's whole step (``value_and_grad`` + the update as
``step_programs.apply_grads`` asks for it) compiled for a *described* v5e:
no chip, ≈ 90 s and ≈ 5 GB on the CPU for the Granite cell.

    python scripts/optimizer_passes.py [--entry in_place|optax] \
        [--workload granite-4.0-h-small.packed-s4096-gas1]

A line a leaf shape of three or more axes: the entry computation's fusions
and copies under ``ds.optimizer`` (or under no scope: compiler-inserted
copies) that read or write a bf16 array of that shape, each with the arrays
it reads and writes.  ``in_place`` (``mp_adamw.update_in_place``): one
fusion a leaf, 5 read and 4 written.  ``optax`` (``update`` +
``apply_updates`` beside the norms, the step until PR 67): at Granite's
expert leaves a copy of the residual and five fusions (4 + 4 + 4 + 1 + 1
arrays read).  A count of instructions, not a time: times come from
``scripts/optimizer_table.py`` on the chip.
"""
import argparse
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path[:0] = [os.path.join(ROOT, "benchmarks", "drivers"),
                os.path.join(ROOT, "benchmarks"), ROOT]

import jax
import jax.numpy as jnp
import optax


def passes_over(entry, leaf):
    """(name, arrays read, arrays written, op_name) of each fusion or copy
    of ``entry`` (text) that touches an array whose type starts with
    ``leaf``, outside every scope but ``ds.optimizer``."""
    typed = {}
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) [\w-]+\(", line)
        if m:
            typed[m.group(1)] = m.group(2)
    rows = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) (?:fusion|copy)\((.*?)\)",
                     line)
        if not m:
            continue
        reads = sum(typed.get(name, "").startswith(leaf)
                    for name in re.findall(r"%([\w.\-]+)", m.group(3)))
        writes = m.group(2).count(leaf)
        op = re.search(r'op_name="([^"]*)', line)
        op = op.group(1) if op else ""
        if (reads or writes) and ("ds.optimizer" in op or "ds." not in op):
            rows.append((m.group(1), reads, writes, op))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    default="granite-4.0-h-small.packed-s4096-gas1")
    ap.add_argument("--entry", choices=("in_place", "optax"),
                    default="in_place")
    args = ap.parse_args()
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from deepspeed_tpu.comm.mesh import sharding_pin_scope
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas import vmem
    from deepspeed_tpu.runtime.bf16_optimizer import mp_adamw
    from deepspeed_tpu.runtime.step_programs import global_norm
    from deepspeed_tpu.telemetry.numerics import group_stats
    from train_steps import build_model

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    if cell["chips"] != 1:
        raise SystemExit("optimizer_passes: a one-chip cell")
    file = next(c["file"] for c in bench["configs"]
                if c["name"] == cell["config"])
    with open(os.path.join(ROOT, file)) as f:
        config = {"name": cell["config"], **json.load(f)}
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)

    device = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    one = SingleDeviceSharding(device)
    # the kernels as the chip would take them
    attention._on_tpu = lambda: True
    vmem.device_kind = lambda: device.device_kind.lower()
    vmem.call_on_one_device = lambda: True

    model = build_model(config)
    bf16 = jnp.bfloat16
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, bf16 if jnp.issubdtype(a.dtype, jnp.floating)
            else a.dtype, sharding=one),
        jax.eval_shape(model.init_fn, jax.random.PRNGKey(0)))
    tx = mp_adamw(1e-4, weight_decay=0.01, mu_dtype="bfloat16",
                  nu_dtype="bfloat16", master_dtype="bfloat16")
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(tx.init, params))
    tokens = jax.ShapeDtypeStruct(
        (traffic["micro_batch_per_chip"], traffic["seq_len"]), jnp.int32,
        sharding=one)
    batch = {"input_ids": tokens}
    if traffic.get("segment_ids"):
        batch["segment_ids"] = tokens
    groups = [i % 4 for i in range(len(jax.tree.leaves(params)))]

    def step(params, state, batch):
        with jax.named_scope("ds.fwd_bwd"):
            loss, grads = jax.value_and_grad(lambda p: model.loss_fn(
                p, batch, jax.random.PRNGKey(1)))(params)
            grads = jax.tree.map(lambda g: g.astype(bf16), grads)
        with jax.named_scope("ds.optimizer"):
            if args.entry == "in_place":
                return tx.update_in_place(grads, state, params), loss
            # step_programs._optax_update, numerics tier on
            grads = jax.tree.map(lambda g: g / jnp.float32(1.0), grads)
            sums = [global_norm(grads), group_stats(grads, groups, 4)]
            updates, state = tx.update(grads, state, params)
            sums += [global_norm(updates), global_norm(params)]
            return (optax.apply_updates(params, updates), state, sums), loss

    started = time.time()
    with sharding_pin_scope(False):
        compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
            params, state, batch).compile()
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    memory = compiled.memory_analysis()
    print(json.dumps({
        "workload": args.workload, "entry": args.entry,
        "compiled_for": device.device_kind,
        "compile_s": round(time.time() - started, 1),
        "temp_bytes": memory.temp_size_in_bytes,
        "alias_bytes": memory.alias_size_in_bytes}))
    shapes = sorted({p.shape for p in jax.tree.leaves(params)
                     if len(p.shape) >= 3})
    for shape in shapes:
        leaf = "bf16[%s]" % ",".join(map(str, shape))
        rows = passes_over(entry, leaf)
        print(f"{leaf}: {len(rows)} instructions, "
              f"{sum(r[1] for r in rows)} arrays read, "
              f"{sum(r[2] for r in rows)} written")
        for row in rows:
            print("    %-34s reads %d writes %d  %s" % (
                row[0], row[1], row[2], row[3][-60:]))


if __name__ == "__main__":
    main()
