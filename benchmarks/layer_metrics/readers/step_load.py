"""What the router did to the traced steps, from the sums the program's
step returns beside its loss (``deepspeed_tpu.telemetry.tracing.step_load``:
the engine's ``step_load()`` — ``moe/*`` int32 sums over a step's expert
layer-calls, micro-batches and chips; data, where
``tracing.grouped_gemm_rows`` is shapes) — no trace, no host callback.
params:
  program:     the name the program registered its step under
  numerator:   the fact above the line
  denominator: the fact below it
  percent:     optional; true: times 100
The value is sum(numerator) / sum(denominator) over the last
``ctx["steps"]`` entries of the account's ``last`` — the traced steps,
which are the run's last, so a load that drifts is read in the window the
times are read in.  None where the program has no such account (a commit
from before it, a model without experts) or no step of the window has
both facts (a path that does not make them: no exchange, no plan)."""


def read(ctx, params):
    try:
        from deepspeed_tpu.telemetry.tracing import step_load
    except ImportError:
        return None
    account = step_load(params["program"])
    window = (account or {}).get("last", [])[-int(ctx["steps"]):]
    above = sum(step.get(params["numerator"], 0) for step in window)
    below = sum(step.get(params["denominator"], 0) for step in window)
    if not above or not below:
        return None
    return (100.0 if params.get("percent") else 1.0) * above / below
