"""The program's own account of its start (telemetry/tracing.py
``setup_account``): every trace, lowering and backend compile by program,
by stage and by the span that caused it, on one clock — counts, never
rates.  A toy engine on the CPU; the cache state is a fixture's."""
import json
import logging
import os
import sys
import time

import jax
import numpy as np
import pytest
from jax._src import monitoring as jax_monitoring

import deepspeed_tpu
from deepspeed_tpu.telemetry import (TRACE_ENV, get_registry, get_tracer,
                                     reset_tracer, tracing)
from deepspeed_tpu.utils.logging import logger
from test_step_program_map import fresh_compiles  # noqa: F401 — a fixture
from util import base_config, random_batch, tiny_gpt2

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))

BACKEND = ("compile", "cache_load")
# ROADMAP S5 (d), found by this account (PR 36): ``state["step"]`` and the
# loss scaler were built as host scalars and came back from the first step
# committed to the mesh, so the second train_batch traced, lowered and
# compiled the whole step once more — a constant here held it at one
# recompile a start.  Since PR 53 the engine places them where the step
# returns them; tests/test_state_placement.py and its ``_writers`` hold
# that per ZeRO stage, scaler, mesh and writer, and the two tests below say
# it outright.


@pytest.fixture(autouse=True)
def account():
    tracing.reset_programs()
    yield
    tracing.reset_programs()


@pytest.fixture
def toy_cache(tmp_path):
    """A persistent compile cache of this test's own that takes a toy
    program: jax keeps out compiles under a second by default."""
    from jax.experimental.compilation_cache import compilation_cache
    mine = {"jax_compilation_cache_dir": str(tmp_path / "cache"),
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": -1,
            "jax_enable_compilation_cache": True}
    was = {key: getattr(jax.config, key) for key in mine}
    for key, value in mine.items():
        jax.config.update(key, value)
    compilation_cache.reset_cache()
    yield
    for key, value in was.items():
        jax.config.update(key, value)
    compilation_cache.reset_cache()


def toy_engine(**config):
    engine, *_ = deepspeed_tpu.initialize(model=tiny_gpt2(remat=True),
                                          config=base_config(**config))
    return engine


def batch_of(engine, seq_len=16):
    one = random_batch(batch_size=engine.topology.dp_world_size,
                       seq_len=seq_len)
    return {k: np.stack([v]) for k, v in one.items()}


def started(steps=3):
    engine = toy_engine()
    batch = batch_of(engine)
    for _ in range(steps):
        engine.train_batch(batch=batch)
    return engine, batch


def rows_of(account, program="train_step", **where):
    return [r for r in account["rows"] if r["program"] == program
            and all(r[k] == v for k, v in where.items())]


def recompiles():
    return get_registry().get_counter("compile/recompiles")


# ------------------------------------------------------------ the account
def test_a_start_by_program_stage_and_cause(fresh_compiles):
    started()
    account = tracing.setup_account()
    assert account["steps"] == 3
    # the step is traced once for jit, lowered once, compiled once ...
    first = rows_of(account, stage="trace", retrace=False)
    assert [(r["cause"], r["step"]) for r in first] \
        == [(tracing.SPAN_FUSED_STEP, 0)]
    assert len(rows_of(account, stage="lower", step=0)) == 1
    backend = [r for r in rows_of(account, step=0) if r["stage"] in BACKEND]
    assert [r["stage"] for r in backend] == ["compile"]   # the cache is off
    # ... and never again: the second call found the program the first
    # one compiled (S5 (d)), and nobody has asked for the cost report
    assert rows_of(account, stage="trace", retrace=True) == []
    assert [r for r in rows_of(account) if r["step"] >= 1] == []
    # whoever asks pays for the one more trace, from a fresh closure that
    # jit's tracing cache cannot know (what S5 (a) counted in every start)
    assert tracing.get_program_cost() is not None
    again = rows_of(tracing.setup_account(), stage="trace", retrace=True)
    assert [r["cause"] for r in again] == [tracing.SPAN_COST_ANALYZE]
    assert not again[0]["recompile"]
    for row in account["rows"]:
        assert row["stage"] in tracing.STAGES
        assert row["end"] >= row["start"] and row["self_s"] >= -1e-9


def test_the_programs_own_spans(fresh_compiles):
    started()
    spans = tracing.setup_account()["spans"]
    by_id = {s["id"]: s for s in spans}
    assert all(s["name"] in tracing.SETUP_SPANS for s in spans)
    init = [s for s in spans if s["name"] == tracing.SPAN_ENGINE_INIT]
    assert len(init) == 1 and init[0]["parent"] is None
    children = [s["name"] for s in spans if s["parent"] == init[0]["id"]]
    assert children == [tracing.SPAN_INIT_SHARDINGS, tracing.SPAN_INIT_PARAMS,
                        tracing.SPAN_INIT_OPTIMIZER]
    steps = [s for s in spans if s["name"] == tracing.SPAN_TRAIN_STEP]
    assert [s["step"] for s in steps] == [0, 1, 2]
    for fused in (s for s in spans if s["name"] == tracing.SPAN_FUSED_STEP):
        assert by_id[fused["parent"]]["name"] == tracing.SPAN_TRAIN_STEP
        assert by_id[fused["parent"]]["step"] == fused["step"]
    # an observer's span is its asker's: none is opened by a start
    assert not [s for s in spans if s["name"] in tracing.OBSERVER_SPANS]
    tracing.get_program_cost()
    analyze, = [s for s in tracing.setup_account()["spans"]
                if s["name"] == tracing.SPAN_COST_ANALYZE]
    assert analyze["parent"] is None and analyze["start"] >= steps[-1]["end"]
    for span in spans:
        parent = by_id.get(span["parent"])
        if parent is not None:
            assert parent["start"] <= span["start"] <= span["end"] \
                <= parent["end"]


def subtree_self_s(account, root):
    inside = {root["id"]}
    for span in account["spans"]:         # sorted by start: parents first
        if span["parent"] in inside:
            inside.add(span["id"])
    return sum(s["self_s"] for s in account["spans"] if s["id"] in inside) \
        + sum(r["self_s"] for r in account["rows"] if r["span"] in inside)


def test_self_times_add_up_and_durations_do_not(fresh_compiles):
    started()
    account = tracing.setup_account()
    tops = [s for s in account["spans"] if s["parent"] is None]
    assert len(tops) == 4                 # engine/init and three steps
    for top in tops:
        assert subtree_self_s(account, top) == pytest.approx(
            top["end"] - top["start"], abs=1e-6)
    # a jitted function traced inside the step's trace is an event of its
    # own: summed as durations the first step would be counted twice over
    step0 = tops[1]
    inside = [r for r in account["rows"] if r["step"] == 0
              and r["cause"] in (tracing.SPAN_FUSED_STEP,
                                 tracing.SPAN_COST_ANALYZE)]
    nested = [r for r in inside if r["program"] == "other"
              and r["stage"] == "trace"]
    assert sum(r["count"] for r in nested) > 10
    named = sum(r["end"] - r["start"] for r in inside
                if r["program"] == "train_step")
    assert named <= step0["end"] - step0["start"]
    assert named > sum(r["self_s"] for r in inside
                       if r["program"] == "train_step")


def test_eager_ops_fold_into_other(fresh_compiles):
    started()
    account = tracing.setup_account()
    other = rows_of(account, program="other")
    events = sum(r["count"] for r in other)
    assert events > 100                   # init's and the trace's small jits
    # one row per stage, causing span and retrace: bounded by the spans
    assert len(other) <= 2 * len(tracing.STAGES) * len(account["spans"])
    keys = [(r["stage"], r["span"], r["retrace"]) for r in other]
    assert len(set(keys)) == len(keys)
    causes = {r["cause"] for r in other}
    assert {tracing.SPAN_INIT_PARAMS, tracing.SPAN_INIT_OPTIMIZER,
            tracing.SPAN_FUSED_STEP} <= causes
    assert all(r["count"] >= 1 and r["end"] >= r["start"] for r in other)
    # what the engine named has rows of its own
    assert all("count" not in r for r in rows_of(account))


def test_rows_outside_every_span_are_the_callers(fresh_compiles):
    started()
    before = len(tracing.setup_account()["spans"])
    jax.jit(lambda x: x * 3 + 1)(np.arange(7.0))      # the caller's own
    account = tracing.setup_account()
    mine = rows_of(account, program="other", cause=None)
    assert {r["stage"] for r in mine} >= {"trace", "lower", "compile"}
    assert all(r["span"] is None and r["step"] == 3 for r in mine)
    assert len(account["spans"]) == before


# ------------------------------------------------------- the steady path
def test_steady_steps_call_no_listener_and_add_no_row(fresh_compiles,
                                                      monkeypatch):
    engine, batch = started()
    monkeypatch.setattr(tracing, "SETUP_STEPS_KEPT", 3)
    calls = []
    listeners = (
        (jax.monitoring.register_scalar_listener,
         jax.monitoring.unregister_scalar_listener,
         lambda event, value, **kw: calls.append(event)),
        (jax.monitoring.register_event_time_span_listener,
         jax.monitoring.unregister_event_time_span_listener,
         lambda event, start, end, **kw: calls.append(event)),
        (jax.monitoring.register_event_listener,
         jax.monitoring.unregister_event_listener,
         lambda event, **kw: calls.append(event)),
        (jax.monitoring.register_event_duration_secs_listener,
         jax.monitoring.unregister_event_duration_listener,
         lambda event, seconds, **kw: calls.append(event)))
    before = tracing.setup_account()
    for register, _, listener in listeners:
        register(listener)
    try:
        for _ in range(3):
            engine.train_batch(batch=batch)
    finally:
        for _, unregister, listener in listeners:
            unregister(listener)
    after = tracing.setup_account()
    # jax reported nothing, so the account's listeners were not called
    # either; and a step past the first ones leaves no span behind
    assert calls == []
    assert after["rows"] == before["rows"]
    assert after["spans"] == before["spans"]
    assert after["steps"] == before["steps"] + 3


def test_a_new_sequence_length_is_a_recompile(fresh_compiles):
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    logger.addHandler(handler)
    try:
        count0 = recompiles()
        engine, _ = started()
        assert recompiles() == count0     # three steps settle with none
        engine.train_batch(batch=batch_of(engine, seq_len=32))    # step 4
    finally:
        logger.removeHandler(handler)
    assert recompiles() == count0 + 1
    account = tracing.setup_account()
    at_4 = rows_of(account, step=3)
    assert [r["stage"] for r in at_4] == ["trace", "lower", "compile"]
    assert all(r["recompile"] and r["cause"] == tracing.SPAN_FUSED_STEP
               for r in at_4)
    assert at_4[0]["retrace"]
    # one line a program: the first recompile's step and stage seconds
    said = [line for line in lines if line.startswith("recompile: ")]
    first = min(r["step"] for r in rows_of(account, recompile=True))
    assert len(said) == 1
    assert f"train_step at step {first} " in said[0]
    assert "trace " in said[0] and "lower " in said[0]


def test_observers_recompile_nothing(fresh_compiles):
    engine, batch = started()
    count0 = recompiles()
    engine.compile_train_step(batch)
    assert tracing.get_program_map() is not None
    account = tracing.setup_account()
    by_id = {s["id"]: s for s in account["spans"]}
    aot = [s for s in account["spans"]
           if s["name"] == tracing.SPAN_COMPILE_AOT]
    text, = [s for s in account["spans"]
             if s["name"] == tracing.SPAN_PROGRAM_TEXT]
    assert len(aot) == 2
    assert aot[0]["parent"] is None and aot[0]["step"] == 3
    assert by_id[aot[1]["parent"]] is text and text["parent"] is None
    caused = [r for r in rows_of(account)
              if r["cause"] == tracing.SPAN_COMPILE_AOT]
    assert caused and not any(r["recompile"] for r in caused)
    assert recompiles() == count0


def test_a_second_engine_loads_from_the_cache(toy_cache):
    hits0 = get_registry().get_counter("compile/cache_hits")
    misses0 = get_registry().get_counter("compile/cache_misses")
    started(steps=2)
    first = tracing.setup_account()
    cold = [r for r in rows_of(first) if r["stage"] in BACKEND]
    assert {r["stage"] for r in cold} == {"compile"}
    assert all(r["missed"] == 1 for r in cold)
    assert get_registry().get_counter("compile/cache_misses") - misses0 \
        >= len(cold)
    started(steps=2)                      # the same shapes, a new engine
    account = tracing.setup_account()
    second_init = [s for s in account["spans"]
                   if s["name"] == tracing.SPAN_ENGINE_INIT][1]
    warm = [r for r in rows_of(account) if r["start"] > second_init["end"]]
    loads = [r for r in warm if r["stage"] in BACKEND]
    assert len(loads) == len(cold)
    assert {r["stage"] for r in loads} == {"cache_load"}
    assert all(r["missed"] == 0 and r["retrieval_s"] > 0 and "saved_s" in r
               for r in loads)
    assert get_registry().get_counter("compile/cache_hits") - hits0 \
        >= len(loads)
    # a new engine's programs are new programs: its first trace is a first
    traces = [r for r in warm if r["stage"] == "trace"]
    assert not traces[0]["retrace"] and not traces[0]["recompile"]
    assert traces[0]["step"] == 0


def test_reset_programs_removes_the_listeners():
    getters = (jax_monitoring.get_scalar_listeners,
               jax_monitoring.get_event_time_span_listeners,
               jax_monitoring.get_event_listeners,
               jax_monitoring.get_event_duration_listeners)

    def mine():
        return [fn for get in getters for fn in get()
                if isinstance(getattr(fn, "__self__", None),
                              tracing.SetupAccount)]
    assert mine() == []
    with tracing.setup_span(tracing.SPAN_COMPILE_AOT):
        jax.jit(lambda x: x + 2)(np.arange(3.0))
    assert len(mine()) == 4               # one set, however many spans
    with tracing.setup_span(tracing.SPAN_COMPILE_AOT):
        pass
    assert len(mine()) == 4
    assert tracing.setup_account()["rows"]
    tracing.reset_programs()
    assert mine() == []
    assert tracing.setup_account() == {"spans": [], "rows": [], "steps": 0}


# ------------------------------------------------------ spans and phases
def test_phases_follow_one_another_and_end_with_their_span():
    with tracing.setup_span(tracing.SPAN_ENGINE_INIT) as span:
        span.phase(tracing.SPAN_INIT_SHARDINGS)
        span.phase(tracing.SPAN_INIT_PARAMS)
    with pytest.raises(KeyError):
        with tracing.setup_span(tracing.SPAN_ENGINE_INIT) as span:
            span.phase(tracing.SPAN_INIT_OPTIMIZER)
            raise KeyError("a bad config")
    with tracing.setup_span(tracing.SPAN_TRAIN_STEP, step=0):
        pass
    spans = tracing.setup_account()["spans"]
    assert [(s["name"], s["parent"] is None) for s in spans] == [
        (tracing.SPAN_ENGINE_INIT, True),
        (tracing.SPAN_INIT_SHARDINGS, False),
        (tracing.SPAN_INIT_PARAMS, False),
        (tracing.SPAN_ENGINE_INIT, True),
        (tracing.SPAN_INIT_OPTIMIZER, False),
        (tracing.SPAN_TRAIN_STEP, True)]   # nothing was left open
    shardings, params = spans[1], spans[2]
    assert shardings["end"] <= params["start"]
    assert spans[4]["end"] <= spans[3]["end"]


@pytest.mark.parametrize("step,rows_inside,kept", [
    (0, False, True), (tracing.SETUP_STEPS_KEPT - 1, False, True),
    (tracing.SETUP_STEPS_KEPT, False, False),
    (tracing.SETUP_STEPS_KEPT, True, True), (10 ** 6, True, True)],
    ids=["first", "last_kept", "steady", "steady_compiles", "late_compile"])
def test_which_step_spans_are_kept(step, rows_inside, kept):
    with tracing.setup_span(tracing.SPAN_TRAIN_STEP, step=step):
        with tracing.setup_span(tracing.SPAN_FUSED_STEP):
            if rows_inside:
                jax.jit(lambda x: x - step)(np.arange(5.0))
    account = tracing.setup_account()
    assert [s["name"] for s in account["spans"]] == (
        [tracing.SPAN_TRAIN_STEP, tracing.SPAN_FUSED_STEP] if kept else [])
    assert account["steps"] == step + 1
    assert all(s["step"] == step for s in account["spans"])
    assert all(r["step"] == step and r["cause"] == tracing.SPAN_FUSED_STEP
               for r in account["rows"])


# -------------------------------------------------------- the trace file
def test_interval_is_a_valid_pair(tmp_path):
    import trace_validate
    tracer = tracing.SpanTracer(str(tmp_path / "t.json"))
    with tracer.span("outer", corr="c-1"):
        t0 = time.perf_counter()
        t1 = time.perf_counter()
        tracer.interval("setup/trace", t0, t1, cat="setup",
                        args={"program": "train_step"})
    events = tracer.drain()
    assert trace_validate.validate_events(events) == []
    begin, = [e for e in events if e["name"] == "setup/trace"
              and e["ph"] == "B"]
    assert begin["args"] == {"program": "train_step", "corr": "c-1"}
    assert begin["cat"] == "setup"


def test_the_trace_file_holds_spans_and_rows(tmp_path, monkeypatch,
                                             fresh_compiles):
    import trace_validate
    path = str(tmp_path / "start.json")
    monkeypatch.setenv(TRACE_ENV, path)
    reset_tracer()
    try:
        engine, batch = started(steps=2)
        engine.compile_train_step(batch)
        tracing.get_program_cost()
        get_tracer().flush()
    finally:
        monkeypatch.delenv(TRACE_ENV)
        reset_tracer()
    events = json.load(open(path))["traceEvents"]
    assert trace_validate.validate_events(events) == []
    begun = [e for e in events if e["ph"] == "B"]
    names = {e["name"] for e in begun}
    assert {tracing.SPAN_ENGINE_INIT, tracing.SPAN_INIT_PARAMS,
            tracing.SPAN_TRAIN_STEP, tracing.SPAN_FUSED_STEP,
            tracing.SPAN_COST_ANALYZE, tracing.SPAN_COMPILE_AOT,
            "setup/trace", "setup/lower", "setup/compile"} <= names
    traces = [e for e in begun if e["name"] == "setup/trace"
              and e["args"]["program"] == "train_step"]
    account = tracing.setup_account()
    assert len(traces) == len(rows_of(account, stage="trace"))
    assert sum(e["args"]["retrace"] for e in traces) \
        == len(rows_of(account, stage="trace", retrace=True))
    # a row inside a step carries the step's correlation id
    assert traces[0]["args"]["corr"] == "train-step-1"
    # every event is in the file, where the account folds the unnamed ones
    assert len([e for e in begun if e["name"].startswith("setup/")]) \
        == sum(r.get("count", 1) for r in account["rows"])
