"""Where the seconds before the first timed step went, from the program's
own account of its start
(``deepspeed_tpu.telemetry.tracing.setup_account``): the spans it opened
at its own boundaries (``engine/init`` and its parts, ``train/step``,
``train/fused_step``, the observers ``costmodel/analyze``,
``memory/compiled``, ``program_map/text``, ``compile/aot``) and one row
per trace, lowering and backend compile that jax reported, each with the
span that caused it — all on one host clock, each with its **self** time:
a jitted function traced inside the step's trace is a row of its own, so
durations would count it twice and self times do not.
params:
  value: which of ``VALUES`` this metric is
**Set-up** is every span, and every row under one, that began before the
first timed step: the ``train/step`` span at step ``account["steps"]`` less
the steps the benchmark timed.  What the caller compiles outside every
span of the program (the benchmark's own reference, its generator) is in
the account under cause None and in no value here.  The eight timings are
disjoint and, with ``dispatch_s``, add up to ``spans_s``, the durations of
the set-up's outermost spans:
  state_init_s    self time of engine/init and of its parts
  trace_s         self time of trace rows that are no retrace
  retrace_s       ... of trace rows with ``retrace``: a program traced that
                  this engine had traced before (the cost report's second
                  walk, a second call that compiles again), and whatever
                  was traced inside one
  lower_s         ... of lower rows
  compile_s       ... of compile rows: the backend compiled
  cache_load_s    ... of cache_load rows: the persistent cache had it
  analysis_s      self time of the observers' spans: their own walk, once
                  their traces, lowerings and compiles are rows
  unattributed_s  spans_s less the seven above and less dispatch_s
and one count: cache_miss_count, the compiles written to the persistent
cache as new entries (0 from a warm cache).
``dispatch_s`` is what ``train/fused_step`` spends calling a compiled
program: its self time where no row lies under it; where rows do (the
first call, a call that compiles again), no more than the median of the
others, or nothing if there are none — so what jax does around its own
timed stages on such a call is unattributed, not dispatch.
None where the program has no such account (a commit from before it);
raises where there is one and the join finds nothing to read."""
import statistics

from layer_metrics.readers.step_phase import BrokenJoin

TIMINGS = ("state_init_s", "trace_s", "retrace_s", "lower_s", "compile_s",
           "cache_load_s", "analysis_s", "unattributed_s")
VALUES = TIMINGS + ("cache_miss_count",)


def reduce(account, timed_steps, names):
    """``VALUES`` and ``dispatch_s``, ``spans_s`` of one account.
    ``names`` is the program's ``telemetry.tracing`` (its fixed span
    names)."""
    first_timed = account["steps"] - timed_steps
    cut = [s["start"] for s in account["spans"]
           if s["name"] == names.SPAN_TRAIN_STEP and s["step"] == first_timed]
    if first_timed < 1 or not cut:
        raise BrokenJoin(
            f"the account began {account['steps']} steps and holds no "
            f"train/step at step {first_timed}, the first of the "
            f"{timed_steps} timed")
    spans = [s for s in account["spans"] if s["start"] < cut[0]]
    rows = [r for r in account["rows"]
            if r["span"] is not None and r["start"] < cut[0]]
    if not any(r["program"] == "train_step" for r in rows):
        raise BrokenJoin("the account holds no row of train_step before "
                         "the first timed step: jax's events did not "
                         "reach the program's listeners")

    def self_s(of):
        return sum((x["self_s"] for x in of), 0.0)

    def stage(name, retrace=False):
        return self_s(r for r in rows if r["stage"] == name
                      and (name != "trace" or r["retrace"] == retrace))

    out = {
        "state_init_s": self_s(
            s for s in spans if s["name"].startswith(names.SPAN_ENGINE_INIT)),
        "trace_s": stage("trace"),
        "retrace_s": stage("trace", retrace=True),
        "lower_s": stage("lower"),
        "compile_s": stage("compile"),
        "cache_load_s": stage("cache_load"),
        "analysis_s": self_s(
            s for s in spans if s["name"] in names.OBSERVER_SPANS),
        "cache_miss_count": float(sum(r["missed"] for r in rows)),
    }
    with_rows = {r["span"] for r in rows}
    fused = [s for s in spans if s["name"] == names.SPAN_FUSED_STEP]
    calls = [s["self_s"] for s in fused if s["id"] not in with_rows]
    steady = statistics.median(calls) if calls else 0.0
    out["dispatch_s"] = sum(calls) + sum(
        min(s["self_s"], steady) for s in fused if s["id"] in with_rows)
    out["spans_s"] = sum(s["end"] - s["start"] for s in spans
                         if s["parent"] is None)
    out["unattributed_s"] = out["spans_s"] - out["dispatch_s"] - sum(
        out[name] for name in TIMINGS[:-1])
    return out


def read(ctx, params):
    try:
        from deepspeed_tpu.telemetry import tracing
    except ImportError:
        return None
    if not hasattr(tracing, "setup_account"):
        return None             # a program from before the account
    return reduce(tracing.setup_account(), ctx["steps"],
                  tracing)[params["value"]]
