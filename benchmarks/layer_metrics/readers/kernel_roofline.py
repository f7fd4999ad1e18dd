"""A kernel's share of its roofline: the least time the chip could take
for the operations the calls made require, over the time they took.
params:
  include: regular expression over the HLO text of the kernel's ops
  flops:   name of a function in harness/flops.py; called with the
           tokens per step per chip, the model's sizes and S_eff
  passes:  handed to that function (which calls the step makes)
The bound is compute (operations / peak bf16 FLOP/s): attention at these
sequence lengths does hundreds of operations per byte of q, k, v moved."""
from harness import flops
from layer_metrics.readers import device_op_time


def read(ctx, params):
    ms = device_op_time.read(ctx, {"include": params["include"],
                                   "mode": "self"})
    if not ms:
        return None
    model = ctx["model"]
    need = getattr(flops, params["flops"])(
        ctx["tokens_per_step_per_chip"], model["num_layers"],
        model["d_model"], ctx["s_eff"], params["passes"])
    floor_ms = need / ctx["peaks"]["bf16_flops_per_s"] * 1e3
    return 100.0 * floor_ms / ms
