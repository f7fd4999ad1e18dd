"""Where the delta rule's time goes on the chip, by part and by phase: one
traced run of a benchmark cell (``benchmarks/run.py --trace 1``,
unchanged), then the self time of every instruction of the compiled step
under ``/linear_attn/delta_rule/``, joined through
``get_program_map("train/step")`` and sorted into the parts of
``ops/linear_attention.py`` — by kernel name where the instruction is one
of ``ops/pallas/gated_delta_rule.py``'s or ``ops/pallas/kda.py``'s calls,
else by what its op_name
holds.

    chiprun --chips 1 -- python scripts/delta_rule_table.py --seed <n> \
        [--root <checkout>] [--out chiprun_out/<file>.json]

``--root`` as in ``scripts/moe_movement_table.py``, whose run and join
this shares.  Standard output: the cell's own lines, the 40 longest
instructions, then one JSON line: ms per optimizer step by part
and phase, the sums by part and by phase, the account's rows
(``tracing.delta_rule_chunks``: which path ran) and whether the step's
text still holds a triangular solve or a ``while`` under the scope.
Refuses the CPU as ``benchmarks/run.py`` does.
"""
import json
import os
import re
from collections import defaultdict

from moe_movement_table import cell_arguments, scope_rows, traced_cell

SCOPE = re.compile(r"/linear_attn/delta_rule/")
#: part <- the first pattern its op_name (below the scope) matches; what
#: the backward of a part runs sits under ``transpose(`` and is told
#: apart by the row's phase
PARTS = (
    ("kernel", re.compile(r"ds_(?:gdr|kda)_\w+")),
    ("scan body", re.compile(r"while|scan|checkpoint")),
    ("solve", re.compile(r"triangular_solve")),
    ("kk / qk", re.compile(r"nbgid,nbgjd->nbgij")),
    ("W / U", re.compile(r"nbgrij,nbgrjd->nbgrid")),
    ("masks and decays", re.compile(
        r"(?:^|/)(?:exp|cumsum|select_n|eq|ge|gt|lt|and|iota|sub|neg|mul"
        r"|broadcast_in_dim)$")),
    ("l2-norms and layout", re.compile(r".")),
)
# (a ``while`` returns a tuple, whose type has spaces in it)
_LEFT = re.compile(r'(InvertDiagBlocksLowerTriangular|triangular-solve'
                   r'|[\])] while\().*op_name="[^"]*/linear_attn/'
                   r'delta_rule/')


def part_of(row):
    if row.get("kernel"):
        return row["kernel"]
    return next(name for name, pat in PARTS if pat.search(row["op"]))


def main():
    args = cell_arguments(__doc__, "qwen3-next-80b-a3b.packed-s8192-gas2")
    dev, table, tr, step_phase, text = traced_cell(args)
    steps, rows = scope_rows(dev, table, tr, step_phase, SCOPE,
                             "/linear_attn/delta_rule/")
    by = defaultdict(lambda: defaultdict(float))
    for r in rows:
        r["part"] = part_of(r)
        by[r["part"]][r["phase"]] += r["ms_per_step"]
    for r in sorted(rows, key=lambda r: -r["ms_per_step"])[:40]:
        print(f'{r["phase"]:9s} {r["ms_per_step"]:9.3f} ms '
              f'{r["calls_per_step"]:7.1f}x  {r["part"]:20s} '
              f'{r["instruction"]:26s} {r["shape"]:34s} {r["op"][-90:]}')
    phases = sorted({p for parts in by.values() for p in parts})
    table_ = {part: {p: round(by[part].get(p, 0.0), 3) for p in phases}
              for part in by}
    from deepspeed_tpu.telemetry import tracing
    left = [m.group(1) for m in map(_LEFT.search, (text or "").splitlines())
            if m]
    out = {"steps_traced": steps, "ms_per_step": table_,
           "by_part": {part: round(sum(v.values()), 3)
                       for part, v in by.items()},
           "by_phase": {p: round(sum(v.get(p, 0.0) for v in by.values()), 3)
                        for p in phases},
           "all": round(sum(r["ms_per_step"] for r in rows), 3),
           "account": tracing.delta_rule_chunks("train/step"),
           "solves_and_whiles_left": None if text is None else len(left)}
    print(json.dumps(out))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({**out, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
