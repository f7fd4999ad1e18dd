"""A named kernel's share of its compute roofline: as kernel_roofline, but
the calls are chosen by the **kernel name** the program gave them
(``pl.pallas_call(..., name=...)``, read from the step-program map) and
not by "any Mosaic call", so forward and backward kernels are priced
apart.
params:
  program, module: as step_phase
  include: regular expression over the HLO text of a Mosaic call (the
           same rule as the metric of all kernel time holds)
  kernels: the map's kernel names that count
  flops, passes: as kernel_roofline
None / raises as step_phase does."""
import re

from layer_metrics.readers import kernel_roofline, step_phase


def read(ctx, params):
    table = step_phase.program_map(ctx, params)
    if table is None:
        return None
    include = re.compile(params["include"])
    kernels = set(params["kernels"])
    worst = 0
    for dev in ctx["trace"].devices:
        worst = max(worst, sum(
            e - s for s, e, text in step_phase.in_step(
                dev, dev.segments(), params)
            if include.search(text) and table.get(
                step_phase.instruction(text), {}).get("kernel") in kernels))
    if not worst:
        raise step_phase.BrokenJoin(
            f"no Mosaic call of the traced step is named {sorted(kernels)} "
            f"in the program's map")
    return 100.0 * kernel_roofline.floor_ms(ctx, params) \
        / (worst * 1e-6 / ctx["steps"])
