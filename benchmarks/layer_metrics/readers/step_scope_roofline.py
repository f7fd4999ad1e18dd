"""A scope's share of its roofline where either compute or memory may be
the bound: the larger of the two floors of the required operations over
the device time of the instructions traced under the scope.  As
step_op_time chooses the instructions (by the scope path the step-program
map gives each), as step_kernel_roofline prices them — but the function
named under ``ops`` returns (FLOPs, bytes), and the floor is the larger of
FLOPs / bf16 peak and bytes / HBM bandwidth.  What runs under the scope may be
XLA fusions or a Mosaic kernel: the floor does not know.
params:
  program, module: as step_phase
  scope:  regular expression over a row's scope path
  ops:    the function of required operations and bytes, named as
          harness/flops.resolve takes it; called with the tokens per step
          per chip, the configuration's ``model`` sizes, S_eff and passes
  passes: handed to that function (which calls the step makes)
None / raises as step_op_time does."""
from harness import flops
from layer_metrics.readers import step_op_time


def floor_ms(ctx, params):
    need_flops, need_bytes = flops.resolve(params["ops"])(
        ctx["tokens_per_step_per_chip"], ctx["model"], ctx["s_eff"],
        params["passes"])
    return 1e3 * max(need_flops / ctx["peaks"]["bf16_flops_per_s"],
                     need_bytes / ctx["peaks"]["hbm_bytes_per_s"])


def read(ctx, params):
    ms = step_op_time.read(ctx, {k: params[k]
                                 for k in ("program", "module", "scope")})
    if ms is None:
        return None
    return 100.0 * floor_ms(ctx, params) / ms
