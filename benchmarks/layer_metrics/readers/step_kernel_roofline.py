"""A named kernel's share of its compute roofline: the least time the chip
could take for the operations the calls made require, over the time they
took.  The calls are chosen by the **kernel name** the program gave them
(``pl.pallas_call(..., name=...)``, read from the step-program map) and
never by "any Mosaic call" (flash, grouped GEMMs, scans and fills are all
Mosaic calls), so every kernel is priced by its own count, forward and
backward apart.
params:
  program, module: as step_phase
  include: regular expression over the HLO text of a Mosaic call
  kernels: the map's kernel names that count
  flops:   the function of required operations, named as
           harness/flops.resolve takes it; called with the tokens per
           step per chip, the configuration's ``model`` sizes and S_eff
  passes:  handed to that function (which calls the step makes)
The bound is compute (operations / peak bf16 FLOP/s): attention at these
sequence lengths does hundreds of operations per byte of q, k, v moved.
None / raises as step_phase does."""
import re

from harness import flops
from layer_metrics.readers import step_phase


def floor_ms(ctx, params):
    """The least milliseconds per step the chip could take for the calls."""
    need = flops.resolve(params["flops"])(
        ctx["tokens_per_step_per_chip"], ctx["model"], ctx["s_eff"],
        params["passes"])
    return need / ctx["peaks"]["bf16_flops_per_s"] * 1e3


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
    return 100.0 * floor_ms(ctx, params) \
        / (worst * 1e-6 / ctx["steps"])
