"""Device milliseconds per step of instructions of the compiled step chosen
by what the step-program map says of them: self time on ``XLA Ops``,
inside the runs of the step's module, of the instructions whose row in
``get_program_map`` carries one of ``kernels`` as its kernel name, or
whose scope path matches ``scope``.  As step_phase, the trace and the map
share only the instruction's name.
params:
  program, module: as step_phase
  kernels: the map's kernel names that count (the ``name=`` of a
           ``pl.pallas_call``), or absent
  scope:   regular expression over a row's scope path (the instruction's
           op_name, ``.../ds.block/mlp/dispatch/scatter``), or absent
An instruction counts if either rule takes it.  Worst device; divided by
the steps traced.  None where the trace has no device plane or the program
publishes no map; raises where there is a map and nothing of it ran."""
import re

from layer_metrics.readers import step_phase


def read(ctx, params):
    table = step_phase.program_map(ctx, params)
    if table is None:
        return None
    kernels = set(params.get("kernels", ()))
    scope = re.compile(params["scope"]) if "scope" in params else None

    def counts(row):
        return row is not None and (
            row["kernel"] in kernels
            or (scope is not None and scope.search(row["scope"] or "")))

    worst = 0
    for dev in ctx["trace"].devices:
        worst = max(worst, sum(
            e - s for s, e, text in step_phase.in_step(
                dev, dev.segments(), params)
            if counts(table.get(step_phase.instruction(text)))))
    if not worst:
        raise step_phase.BrokenJoin(
            f"no instruction of the traced step is of the kernels "
            f"{sorted(kernels)} or under a scope matching "
            f"{params.get('scope')!r} in the program's map")
    return worst * 1e-6 / ctx["steps"]
