"""Collectives of the compiled step chosen by what the step-program map
says an instruction **is** and **where it was traced**: as
step_collective, for several kinds at once and for those under one scope
alone (the expert-parallel exchange's all-to-alls under
``.../mlp/.../exchange/``, apart from whatever else the partitioner made
an all-to-all of).
params:
  program, module, include: as step_collective
  collectives: the map's kinds that count (``all-to-all`` ...)
  scope:  regular expression over a row's scope path, or absent (any)
  field: "union_ms"    the union of their intervals (XLA Ops and Async XLA
                       Ops; a start..done pair one interval from the
                       start's begin to the done's end), per step
         "exposed_ms"  the part of the union of their events during which
                       no op that is not a collective runs, per step
         "gbps"        sum of their ``wire_bytes`` / that union
Worst device (longest, most exposed, slowest).  None as step_phase, and
where the program's map holds no such collective under that scope (a
program from before the exchange); raises where the map holds one and none
ran in the traced step."""
import re

from harness import trace as tr
from layer_metrics.readers import step_phase


def read(ctx, params):
    table = step_phase.program_map(ctx, params)
    if table is None:
        return None
    kinds = set(params["collectives"])
    scope = re.compile(params["scope"]) if "scope" in params else None
    chosen = {name for name, row in table.items()
              if row["collective"] in kinds
              and (scope is None or scope.search(row["scope"] or ""))}
    if not chosen:
        return None
    include = re.compile(params["include"])
    union_ns, exposed_ns, gbps = [], [], []
    for dev in ctx["trace"].devices:
        hit = []
        for s, e, text in sorted(step_phase.in_step(
                dev, dev.events(tr.OPS, tr.ASYNC_OPS), params)):
            name = step_phase.instruction(text)
            if include.search(text) and (
                    name in chosen
                    or name.replace("-done", "-start") in chosen
                    or name.replace("-start", "-done") in chosen):
                hit.append((s, e, name))
        if not hit:
            continue
        spans, open_at = [], {}
        for s, e, name in hit:
            if "-start" in name:
                open_at[name.replace("-start", "-done")] = s
            spans.append((open_at.pop(name, s), e))
        whole = tr.total(tr.union(spans))
        union_ns.append(whole)
        others = tr.union(s for s in dev.segments()
                          if not include.search(s[2]))
        exposed_ns.append(tr.total(tr.subtract(tr.union(hit), others)))
        sent = sum(table.get(name, {}).get("wire_bytes") or 0
                   for _, _, name in hit)
        gbps.append(sent / whole)                   # bytes/ns = GB/s
    if not union_ns:
        raise step_phase.BrokenJoin(
            f"no {sorted(kinds)} of the program's map under a scope "
            f"matching {params.get('scope')!r} ran in the traced step")
    if params["field"] == "union_ms":
        return max(union_ns) * 1e-6 / ctx["steps"]
    if params["field"] == "exposed_ms":
        return max(exposed_ns) * 1e-6 / ctx["steps"]
    return min(gbps)
