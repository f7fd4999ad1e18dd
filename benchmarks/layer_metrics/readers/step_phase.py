"""Device milliseconds per step of the compiled step's own phases: self
time on ``XLA Ops``, inside the runs of the step's module, of the
instructions that the program's step-program map
(``deepspeed_tpu.telemetry.tracing.get_program_map``) puts in one of
``phases``.  The trace and the map share only the instruction's name
(``%fusion.554``): an ``XLA Ops`` event is the instruction's text without
its metadata, so the scope a fusion was traced under is read from the
program, not from the trace.
params:
  program:  the name the program registered its step under
  module:   regular expression over ``XLA Modules`` names: the step's runs
  exclude:  regular expression over an op's HLO text: what is left out
            (collectives, by the same text rule as the metric of all
            XLA ops holds, handed over in the metric's file)
  phases:   the map's phases that count
  unmapped: true = an instruction with no row in the map counts too
  may_be_zero: true = 0.0 is an answer (a remainder); otherwise a phase
            in which nothing ran is a broken join and raises
Worst device; divided by the steps traced.  None where the trace has no
device plane (a CPU rehearsal) or the program publishes no map (a commit
from before it); raises where there is a map and the join finds nothing."""
import bisect
import re

from harness import trace as tr

_NAME = re.compile(r"^%(\S+) = ")


class BrokenJoin(RuntimeError):
    """The trace and the step-program map do not meet."""


def instruction(text):
    """``%fusion.5 = bf16[8]{0} fusion(...)`` -> ``fusion.5``"""
    m = _NAME.match(text)
    return m.group(1) if m else None


def program_map(ctx, params):
    """The program's table, or None where there is nothing to read."""
    if not any(dev.lines.get(tr.OPS) for dev in ctx["trace"].devices):
        return None
    try:
        from deepspeed_tpu.telemetry.tracing import get_program_map
    except ImportError:
        return None             # a program from before the map: left out
    table = get_program_map(params["program"])
    if not table:
        raise BrokenJoin(f"the trace has device planes but the program "
                         f"published no map for {params['program']!r}")
    return table


def in_step(dev, events, params):
    """Those of ``events`` [(start, end, text)] that start inside a run of
    the step's module on this device."""
    module = re.compile(params["module"])
    runs = tr.union((s, e) for s, e, text in dev.events(tr.MODULES)
                    if module.search(text))
    if not runs:
        raise BrokenJoin(f"{dev.name}: no XLA Modules event matches "
                         f"{params['module']!r}")
    starts = [s for s, _ in runs]
    out = []
    for event in events:
        i = bisect.bisect_right(starts, event[0]) - 1
        if i >= 0 and event[0] < runs[i][1]:
            out.append(event)
    return out


def read(ctx, params):
    table = program_map(ctx, params)
    if table is None:
        return None
    exclude = re.compile(params["exclude"])
    phases = set(params["phases"])
    worst, joined = 0, 0
    for dev in ctx["trace"].devices:
        ns = 0
        for s, e, text in in_step(dev, dev.segments(), params):
            if exclude.search(text):
                continue
            row = table.get(instruction(text))
            joined += row is not None
            if (row["phase"] in phases) if row else params.get("unmapped"):
                ns += e - s
        worst = max(worst, ns)
    if not joined:
        raise BrokenJoin("no instruction of the traced step is in the "
                         "program's map: the names do not match")
    if not worst and not params.get("may_be_zero"):
        raise BrokenJoin(
            f"no device time in phases {sorted(phases)} (an executable "
            f"loaded from a compile cache that an older tree filled "
            f"carries that tree's scopes: the cache's key leaves debug "
            f"info out)")
    return worst * 1e-6 / ctx["steps"]
