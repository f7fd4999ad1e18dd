"""Device milliseconds per step of the ops whose HLO text matches.
params:
  include / exclude: regular expressions over the op's HLO text
  mode: "self"    self time on XLA Ops (innermost op running), summed
        "union"   union of the matching events' intervals, on XLA Ops and
                  Async XLA Ops together (a start..done pair counts once)
        "exposed" the part of "union" during which no op that does NOT
                  match runs on XLA Ops
Worst device; divided by the steps traced.  None where nothing matches."""
import re

from harness import trace as tr


def read(ctx, params):
    include = re.compile(params["include"]) if "include" in params else None
    exclude = re.compile(params["exclude"]) if "exclude" in params else None

    def match(text):
        return ((include is None or include.search(text) is not None)
                and (exclude is None or exclude.search(text) is None))

    worst = None
    for dev in ctx["trace"].devices:
        segments = dev.segments()
        if params["mode"] == "self":
            hit = [s for s in segments if match(s[2])]
            ns = sum(e - s for s, e, _ in hit)
        else:
            hit = [e for e in dev.events(tr.OPS, tr.ASYNC_OPS) if match(e[2])]
            merged = tr.union(hit)
            if params["mode"] == "exposed":
                others = tr.union(s for s in segments if not match(s[2]))
                merged = tr.subtract(merged, others)
            ns = tr.total(merged)
        if hit:
            worst = ns if worst is None else max(worst, ns)
    return None if worst is None else worst * 1e-6 / ctx["steps"]
