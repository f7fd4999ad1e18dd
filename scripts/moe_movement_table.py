"""What the routed rows' movement costs on the chip, instruction by
instruction: one traced run of a benchmark cell (``benchmarks/run.py
--trace 1``, unchanged), then the self time of every instruction of the
compiled step whose scope holds ``/mlp/dispatch/`` or ``/mlp/combine/``
(inside the expert-parallel exchange's manual region:
``/mlp/shard_map/dispatch/``, as the cells' own metrics read it), joined
through ``get_program_map("train/step")`` and split by phase.

    chiprun --chips 1 -- python scripts/moe_movement_table.py --seed <n> \
        [--root <checkout>] [--out chiprun_out/<file>.json]
    chiprun --chips 4 -- python scripts/moe_movement_table.py --seed <n> \
        --workload mellum2-12b-a2.5b-ep4.packed-s8192-gas4-ep

(a cell of four chips: the table is device 0's).

``--root`` is the checkout whose benchmark and program run (default: this
one), so that a parent commit unpacked under ``.chip_checkout/`` is read
with the same code.  Standard output: the cell's own lines, then the table
(ms per optimizer step, executions per step, phase, the tail of the
instruction's op_name, result shape), the sum by phase, and every
``scatter`` instruction of the executable's text — fused computations
included — that sits under one of the two scopes; where the cell
exchanges its rows, the same table of every instruction under
``/mlp/shard_map/exchange/`` (a call's collective beside the copies and
fills around it: ``exchange_rows`` of ``--out``), and of every instruction
under no ``ds.*`` scope at all that takes half a millisecond a step — what
``step.unattributed_ms_per_step`` is made of, the compiler's re-tiling
``copy`` of what a collective returned among it (``unattributed_rows``).
"""
import argparse
import json
import os
import re
import runpy
import sys
from collections import defaultdict

SCOPE = re.compile(r"/mlp/(?:shard_map/)?(dispatch|combine)/")
#: the expert-parallel exchange's own scope: its collectives, and the
#: re-tiling copies and zero fills the compiler puts around each
EXCHANGE = re.compile(r"/mlp/(?:shard_map/)?exchange/")
STEP = {"program": "train/step", "module": r"^jit_train_step\("}
_SCATTER = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\S+) scatter\(.*"
                      r'op_name="([^"]*)"')


def scope_rows(dev, table, tr, step_phase, scope=SCOPE,
               below=r"/mlp/(?:shard_map/)?"):
    """(steps traced, [row]) of one device: self time, executions, phase,
    op_name below ``below`` (a pattern) and result shape of each
    instruction whose scope path matches ``scope`` — or, with no ``scope``,
    whose phase is ``other``: no ``ds.*`` scope names it."""
    steps = sum(1 for _, _, text in dev.events(tr.MODULES)
                if re.search(STEP["module"], text))
    ns, calls, shape = defaultdict(int), defaultdict(int), {}

    def moves(text):
        name = step_phase.instruction(text)
        row = table.get(name)
        if not row:
            return None
        named = row["phase"] == "other" if scope is None \
            else scope.search(row["scope"] or "")
        return name if named else None

    for s, e, text in step_phase.in_step(dev, dev.segments(), STEP):
        name = moves(text)
        if name:
            ns[name] += e - s
            shape[name] = text.partition(" = ")[2].split(" ")[0]
    for _, _, text in step_phase.in_step(dev, dev.events(tr.OPS), STEP):
        name = moves(text)
        if name:
            calls[name] += 1
    rows = [{"instruction": name, "phase": table[name]["phase"],
             "ms_per_step": ns[name] * 1e-6 / steps,
             "calls_per_step": calls[name] / steps,
             "op": re.split(below, table[name]["scope"] or "",
                            maxsplit=1)[-1],
             "kernel": table[name].get("kernel"),
             "shape": shape[name]} for name in ns]
    rows.sort(key=lambda r: (r["phase"], -r["ms_per_step"]))
    return steps, rows


def scatters_in(text):
    """(instruction, shape, op_name) of every scatter of the executable's
    text, fused computations included, under one of the two scopes."""
    return [m.groups() for m in map(_SCATTER.match, text.splitlines())
            if m and SCOPE.search(m.group(3))]


def cell_arguments(doc, workload):
    parser = argparse.ArgumentParser(description=doc.split("\n\n")[0])
    parser.add_argument("--workload", default=workload)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    parser.add_argument("--out")
    return parser.parse_args()


def traced_cell(args):
    """Runs the cell's traced run in this process and returns what the
    tables are made from: (device 0 of the trace, the step's program map,
    harness.trace, readers.step_phase, the executable's text or None)."""
    root = os.path.abspath(args.root)
    bench = os.path.join(root, "benchmarks")
    sys.path[:0] = [bench, root]

    # keep the trace that the driver loads, reduces and deletes, and the
    # executable's text while the engine that holds it is alive (the
    # thunk is the one get_program_map parses, asked once more)
    import jax.profiler
    from harness import trace as tr
    from layer_metrics.readers import step_phase
    traces, texts = [], []
    load, start_trace = tr.load, jax.profiler.start_trace
    tr.load = lambda *a, **k: traces.append(load(*a, **k)) or traces[-1]

    def keep_text(*a, **k):
        from deepspeed_tpu.telemetry import tracing
        texts.append(tracing.get_program_text(STEP["program"]))
        return start_trace(*a, **k)
    jax.profiler.start_trace = keep_text
    sys.argv = [os.path.join(bench, "run.py"), "--workload", args.workload,
                "--seed", str(args.seed), "--seconds", str(args.seconds),
                "--trace", "1"]
    runpy.run_path(sys.argv[0], run_name="__main__")

    from deepspeed_tpu.telemetry import tracing
    return (traces[-1].devices[0], tracing.get_program_map(STEP["program"]),
            tr, step_phase, texts[-1] if texts else None)


def print_rows(rows):
    """The table; -> ms a step by phase and the scope's first name."""
    sums = defaultdict(float)
    for r in rows:
        sums[f'{r["phase"]}/{r["op"].split("/", 1)[0]}'] += r["ms_per_step"]
        print(f'{r["phase"]:9s} {r["ms_per_step"]:9.3f} ms '
              f'{r["calls_per_step"]:6.1f}x  {r["instruction"]:28s} '
              f'{r["shape"]:32s} {r["op"][-110:]}')
    return dict(sorted(sums.items()), all=sum(sums.values()))


def main():
    args = cell_arguments(__doc__, "olmoe-1b-7b.packed-s4096-gas8")
    dev, table, tr, step_phase, text = traced_cell(args)
    steps, rows = scope_rows(dev, table, tr, step_phase)
    sums = print_rows(rows)
    print(json.dumps({"steps_traced": steps, "ms_per_step": sums}))
    _, exchange_rows = scope_rows(dev, table, tr, step_phase, scope=EXCHANGE)
    if exchange_rows:
        print(json.dumps({"exchange_ms_per_step": print_rows(exchange_rows)}))
    unattributed_rows = [r for r in scope_rows(
        dev, table, tr, step_phase, scope=None)[1] if r["ms_per_step"] >= 0.5]
    print(json.dumps({"unattributed_ms_per_step":
                      print_rows(unattributed_rows)}))
    scatters = scatters_in(text) if text else None
    print(json.dumps({"scatters_under_dispatch_or_combine":
                      None if scatters is None else len(scatters),
                      "first": (scatters or [])[:8]}))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "ms_per_step": sums,
                       "steps_traced": steps, "scatters": scatters,
                       "exchange_rows": exchange_rows,
                       "unattributed_rows": unattributed_rows}, f, indent=1)


if __name__ == "__main__":
    main()
