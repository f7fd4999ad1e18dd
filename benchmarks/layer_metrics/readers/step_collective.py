"""One kind of collective of the compiled step, chosen by what the
step-program map says an instruction **is** — the text of an
``%async-collective-start/done`` fusion does not say which collective it
wraps, and the partitioner's gathers appear in no jaxpr.
params:
  program, module: as step_phase
  include:    regular expression over HLO text: "is a collective" (the
              same rule as the metrics of all collectives hold)
  collective: the map's kind that counts (``all-gather`` ...)
  field: "exposed_ms"  the part of the union of their intervals (XLA Ops
                       and Async XLA Ops) during which no op that is not
                       a collective runs, per step — device_op_time's
                       mode "exposed", for this kind alone
         "gbps"        sum of their ``wire_bytes`` / the union of their
                       intervals, a start..done pair taken as one
                       interval from the start's begin to the done's end
Worst device (most exposed, slowest).  None / raises as step_phase."""
import re

from harness import trace as tr
from layer_metrics.readers import step_phase


def read(ctx, params):
    table = step_phase.program_map(ctx, params)
    if table is None:
        return None
    include = re.compile(params["include"])
    exposed_ns, gbps = [], []
    for dev in ctx["trace"].devices:
        hit = []
        for s, e, text in sorted(step_phase.in_step(
                dev, dev.events(tr.OPS, tr.ASYNC_OPS), params)):
            name = step_phase.instruction(text)
            if include.search(text) and table.get(name, {}).get(
                    "collective") == params["collective"]:
                hit.append((s, e, name))
        if not hit:
            continue
        if params["field"] == "exposed_ms":
            others = tr.union(s for s in dev.segments()
                              if not include.search(s[2]))
            exposed_ns.append(tr.total(tr.subtract(tr.union(hit), others)))
            continue
        spans, open_at = [], {}
        for s, e, name in hit:
            if "-start" in name:
                open_at[name.replace("-start", "-done")] = s
            spans.append((open_at.pop(name, s), e))
        sent = sum(table[name]["wire_bytes"] or 0 for _, _, name in hit)
        gbps.append(sent / tr.total(tr.union(spans)))   # bytes/ns = GB/s
    if not exposed_ns and not gbps:
        raise step_phase.BrokenJoin(
            f"no {params['collective']} of the program's map ran in the "
            f"traced step")
    if params["field"] == "exposed_ms":
        return max(exposed_ns) * 1e-6 / ctx["steps"]
    return min(gbps)
