"""A kernel's share of its roofline: the least time the chip could take
for the operations the calls made require, over the time they took.
params:
  include: regular expression over the HLO text of the kernel's ops
  flops:   the function of required operations, named as
           harness/flops.resolve takes it; called with the tokens per
           step per chip, the configuration's ``model`` sizes and S_eff
  passes:  handed to that function (which calls the step makes)
The bound is compute (operations / peak bf16 FLOP/s): attention at these
sequence lengths does hundreds of operations per byte of q, k, v moved."""
from harness import flops
from layer_metrics.readers import device_op_time


def floor_ms(ctx, params):
    """The least milliseconds per step the chip could take for the calls."""
    need = flops.resolve(params["flops"])(
        ctx["tokens_per_step_per_chip"], ctx["model"], ctx["s_eff"],
        params["passes"])
    return need / ctx["peaks"]["bf16_flops_per_s"] * 1e3


def read(ctx, params):
    ms = device_op_time.read(ctx, {"include": params["include"],
                                   "mode": "self"})
    if not ms:
        return None
    return 100.0 * floor_ms(ctx, params) / ms
