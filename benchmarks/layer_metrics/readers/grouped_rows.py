"""What the step's grouped GEMM calls compute that no token needs: a count
the program states about itself while its step is traced
(``deepspeed_tpu.telemetry.tracing.grouped_gemm_rows``: routed and padded
rows per grouped call, both shapes) — no trace, no host callback.
params:
  program: the name the program registered its step under
The value is 100 * (padded - routed) / padded rows: the share of the rows
the kernels compute that are zeros (group padding and the trailing
all-zero tiles).  None where the program has no such account (a commit
from before it, or a step with no grouped dispatch)."""


def read(ctx, params):
    try:
        from deepspeed_tpu.telemetry.tracing import grouped_gemm_rows
    except ImportError:
        return None
    rows = grouped_gemm_rows(params["program"])
    if not rows:
        return None
    padded, routed = rows["padded_rows_per_call"], rows["routed_rows_per_call"]
    return 100.0 * (padded - routed) / padded
