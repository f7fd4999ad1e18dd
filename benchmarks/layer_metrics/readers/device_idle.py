"""Idle time of the device inside the traced window (first op to last op
on that device's ``XLA Ops`` line), worst device.
params: {"field": "idle_pct" | "longest_gap_ms"}"""
from harness import trace as tr


def read(ctx, params):
    worst = None
    for dev in ctx["trace"].devices:
        window = dev.window()
        if window is None:
            continue
        merged = tr.union(dev.events(tr.OPS))
        if params["field"] == "idle_pct":
            value = 100.0 * (1.0 - tr.total(merged) / (window[1] - window[0]))
        else:
            value = max((e - s for s, e in tr.gaps(merged)), default=0) * 1e-6
        worst = value if worst is None else max(worst, value)
    return worst
