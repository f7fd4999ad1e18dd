"""The optimizer phase of a cell's step on the chip, instruction by
instruction: one traced run of a benchmark cell (``benchmarks/run.py
--trace 1``, unchanged), then the self time of every instruction of the
compiled step under ``ds.optimizer`` — a stacked leaf's one fusion behind
its barrier beside a matrix's or the table's update, the join of a stacked
gradient handed over in pieces, the sums' epilogue — and of every
instruction under no ``ds.*`` scope at all that takes a fifth of a
millisecond (what ``step.unattributed_ms_per_step`` is made of).

    chiprun --chips 1 -- python scripts/optimizer_table.py --seed <n> \
        [--workload granite-4.0-h-small.packed-s4096-gas1] \
        [--root .chip_checkout/parent] [--out chiprun_out/<file>.json]

``--root`` as ``scripts/moe_movement_table.py`` takes it, whose traced run
and join this is.  The last line: ``optimizer_fused`` (the step's own
account: leaves and bytes updated behind a barrier and left to XLA; null at
a parent from before PR 67), the phase's sum and the time of the stacked
leaves' bytes at the chip's 819 GB/s.
"""
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import moe_movement_table as mmt

OPTIMIZER = re.compile(r"ds\.optimizer")
HBM_GBPS = 819.0


def main():
    args = mmt.cell_arguments(__doc__,
                              "granite-4.0-h-small.packed-s4096-gas1")
    dev, table, tr, step_phase, _ = mmt.traced_cell(args)
    steps, rows = mmt.scope_rows(dev, table, tr, step_phase, scope=OPTIMIZER,
                                 below=r"ds\.optimizer/")
    rows.sort(key=lambda r: -r["ms_per_step"])
    mmt.print_rows([r for r in rows if r["ms_per_step"] >= 0.05])
    unattributed = [r for r in mmt.scope_rows(
        dev, table, tr, step_phase, scope=None)[1] if r["ms_per_step"] >= 0.2]
    print(json.dumps({"unattributed_ms_per_step":
                      mmt.print_rows(unattributed)}))
    from deepspeed_tpu.telemetry import tracing
    fused = getattr(tracing, "optimizer_fused", lambda name: None)(
        mmt.STEP["program"])
    summary = {
        "steps_traced": steps, "optimizer_fused": fused,
        "optimizer_ms_per_step": sum(r["ms_per_step"] for r in rows),
        # g, m, v, the residual and p read, the last four written: 9 arrays
        # of the parameters' own bytes where all five are bf16
        "stacked_floor_ms": None if not fused else
        fused["param_bytes"] * 9 / HBM_GBPS / 1e6}
    print(json.dumps(summary))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "unattributed_rows": unattributed,
                       **summary}, f, indent=1)


if __name__ == "__main__":
    main()
