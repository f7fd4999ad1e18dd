"""The train step's cost report is made when somebody asks for it
(``tracing.get_program_cost``), through the registry its program map and
its memory account use, and the trainer's rate gauges are written only
where the engine has itself just waited for the device.  Toy engines on
the CPU: counts of traces, identities and orders — never a rate."""
import json
import os
import time
import types

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.mixtral import mixtral_model
from deepspeed_tpu.resilience.postmortem import write_postmortem
from deepspeed_tpu.telemetry import MetricsRegistry, costmodel, tracing
from deepspeed_tpu.telemetry.debug import perf_payload
from util import base_config, random_batch, tiny_gpt2

STEP = tracing.TRAIN_STEP_PROGRAM
RATES = ("train/tokens_per_s", "train/model_flops_per_s", "train/mfu")
SEQ = 16


@pytest.fixture(autouse=True)
def nothing_asked(monkeypatch):
    # rates resolve on the CPU, so a floor (and a ratio to it) could
    monkeypatch.setenv("DS_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("DS_HBM_GBPS", "819")
    tracing.reset_programs()
    costmodel.reset_reports()
    yield
    tracing.reset_programs()
    costmodel.reset_reports()


def toy_moe():
    return mixtral_model("olmoe-1b-7b", num_layers=2, d_model=32,
                         num_heads=2, num_kv_heads=2, d_ff=32, num_experts=4,
                         top_k=2, vocab_size=128, max_seq_len=64,
                         dtype="float32", remat=True)


MODELS = {"gpt2": lambda: tiny_gpt2(remat=True), "moe": toy_moe}


def started(model="gpt2", steps=3, **config):
    """An engine of its own registry that has stepped, and its batch."""
    engine, *_ = deepspeed_tpu.initialize(model=MODELS[model](),
                                          config=base_config(**config))
    engine.telemetry_registry = MetricsRegistry()
    one = random_batch(batch_size=engine.topology.dp_world_size, seq_len=SEQ)
    batch = {k: np.stack([v]) for k, v in one.items()}
    for _ in range(steps):
        engine.train_batch(batch=batch)
    return engine, batch


def traces_of_the_step():
    return [r for r in tracing.setup_account()["rows"]
            if r["program"] == "train_step" and r["stage"] == "trace"]


def analyses():
    return [s for s in tracing.setup_account()["spans"]
            if s["name"] == tracing.SPAN_COST_ANALYZE]


def by_hand(engine, batch):
    """``analyze_fn`` on the step program, as the thunk calls it."""
    signature = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        engine._shard_batch(batch, stacked=True))
    with engine._train_scope(), engine._ltd_scope(), engine._aq_scope():
        return costmodel.analyze_fn(
            engine._step_program("train_step"),
            *engine._abstract_step_args(signature), name="by hand")


# ------------------------------------------------- nothing until asked
@pytest.mark.parametrize("model", sorted(MODELS))
def test_a_start_traces_the_step_once_and_analyses_nothing(model):
    engine, _ = started(model)
    traces = traces_of_the_step()
    assert len(traces) == 1 and not traces[0]["retrace"]
    assert [r for r in tracing.setup_account()["rows"] if r["retrace"]] == []
    assert analyses() == []
    assert costmodel.get_report(STEP) is None
    assert tracing.get_program_cost(create=False) is None
    assert engine.telemetry_registry.get_gauge(
        "perf/flops", program=STEP) is None


@pytest.mark.parametrize("model", sorted(MODELS))
def test_the_report_is_made_for_its_first_asker_and_kept(model):
    engine, batch = started(model, steps=1)
    report = tracing.get_program_cost()
    assert report.name == STEP and report.flops > 0
    assert report.detail["tokens_per_step"] \
        == engine.train_batch_size() * SEQ
    # one span and, under it, the one more trace of the step
    assert len(analyses()) == 1
    again = [r for r in traces_of_the_step() if r["retrace"]]
    assert [r["cause"] for r in again if r["cause"] is not None] \
        == [tracing.SPAN_COST_ANALYZE]
    # asked once and kept: the same object, and no second walk
    assert tracing.get_program_cost() is report
    assert tracing.get_program_cost(create=False) is report
    assert len(analyses()) == 1
    # whoever asked published it: the peeks have it from now on
    assert costmodel.get_report(STEP) is report
    reg = engine.telemetry_registry
    assert reg.get_gauge("perf/flops", program=STEP) == float(report.flops)
    assert reg.get_gauge("perf/hbm_bytes", program=STEP) \
        == float(report.hbm_bytes)
    assert reg.get_gauge("perf/floor_ms", program=STEP) > 0
    row = perf_payload()["programs"][STEP]
    assert row["flops"] == report.flops and row["floor_ms"] > 0
    # the engine steps on as before: the program it compiled is the one
    engine.train_batch(batch=batch)
    assert [r for r in tracing.setup_account()["rows"]
            if r["recompile"]] == []
    # (last: a trace under no span of the program reads as a recompile)
    hand = by_hand(engine, batch)
    assert (report.flops, report.hbm_bytes, report.pallas_launches,
            report.collectives) == (hand.flops, hand.hbm_bytes,
                                    hand.pallas_launches, hand.collectives)


def test_a_peek_starts_nothing(tmp_path):
    engine, _ = started()
    assert STEP not in perf_payload()["programs"]
    bundle = write_postmortem(str(tmp_path), "a peek", step=3,
                              registry=engine.telemetry_registry,
                              flightrec=engine.flightrec, min_interval_s=0)
    manifest = json.load(open(os.path.join(bundle, "manifest.json")))
    assert not manifest["files"].get("perf.json")     # nothing analysed
    assert analyses() == [] and len(traces_of_the_step()) == 1
    assert costmodel.get_report(STEP) is None
    # and after an ask both show the row, still starting nothing
    report = tracing.get_program_cost()
    bundle = write_postmortem(str(tmp_path), "a peek", step=3,
                              registry=engine.telemetry_registry,
                              flightrec=engine.flightrec, min_interval_s=0)
    perf = json.load(open(os.path.join(bundle, "perf.json")))
    assert perf["programs"][STEP]["flops"] == report.flops
    assert len(analyses()) == 1


def test_an_engine_that_is_gone_has_no_report(monkeypatch):
    # the table holds the engine weakly; a numerics callback of the
    # process holds it too, so here the weak reference is what dies
    from deepspeed_tpu.runtime import engine as engine_module
    monkeypatch.setattr(engine_module, "weakref",
                        types.SimpleNamespace(ref=lambda obj: lambda: None))
    started(steps=1)
    monkeypatch.undo()
    assert tracing.get_program_cost() is None
    assert analyses() == [] and costmodel.get_report(STEP) is None


def test_a_comm_drill_reads_the_wire_bytes_of_a_report_already_made(
        monkeypatch):
    monkeypatch.setenv("DS_COMMSTAT", "1")
    engine, batch = started(steps=2)
    # the drill's window peeks: two steps in, it has started no analysis
    assert analyses() == [] and len(traces_of_the_step()) == 1
    tracing.get_program_cost()
    engine.train_batch(batch=batch)
    assert len(analyses()) == 1


# ------------------------------------------------- rates at synced points
def rates(engine):
    return {name: engine.telemetry_registry.get_gauge(name)
            for name in RATES}


def test_rates_are_written_where_the_engine_waited_for_the_device():
    engine, batch = started(steps=0, steps_per_print=2)
    tokens = engine.train_batch_size() * SEQ
    t0 = time.perf_counter()
    engine.train_batch(batch=batch)
    assert set(rates(engine).values()) == {None}    # no boundary yet
    loss = engine.train_batch(batch=batch)
    jax.block_until_ready(loss)
    synced_loop_s = time.perf_counter() - t0
    at_2 = rates(engine)
    assert all(v is not None and v > 0 for v in at_2.values()), at_2
    # two steps' tokens over a window that holds the compile: never more
    # than a loop that waits for the device says, whatever the dispatch took
    assert at_2["train/tokens_per_s"] <= 1.5 * 2 * tokens / synced_loop_s
    assert at_2["train/model_flops_per_s"] == pytest.approx(
        at_2["train/tokens_per_s"] * engine.model.flops_per_token)
    assert at_2["train/mfu"] == pytest.approx(
        at_2["train/model_flops_per_s"] / engine._peak_flops)
    engine.train_batch(batch=batch)
    assert rates(engine) == at_2                    # kept between boundaries
    engine.train_batch(batch=batch)
    at_4 = rates(engine)
    # the second window holds no compile
    assert at_4["train/tokens_per_s"] > at_2["train/tokens_per_s"]
    # the latency of the call is still observed every step
    assert engine.telemetry_registry.snapshot()[
        "train/step_latency_s_count"] == 4


def test_rates_every_step_under_wall_clock_breakdown():
    engine, batch = started(steps=1, wall_clock_breakdown=True)
    seen = [rates(engine)]
    for _ in range(2):
        engine.train_batch(batch=batch)
        seen.append(rates(engine))
    assert all(v is not None and v > 0 for r in seen for v in r.values())
    assert seen[1] != seen[0] and seen[2] != seen[1]


def test_no_rate_without_a_boundary():
    engine, _ = started(steps=4)                    # steps_per_print: 0
    assert set(rates(engine).values()) == {None}


@pytest.mark.parametrize("asked", [False, True])
def test_the_step_is_never_held_to_its_floor_by_a_dispatch_time(asked):
    engine, batch = started(steps=2, steps_per_print=2)
    if asked:
        assert tracing.get_program_cost() is not None
        assert engine.telemetry_registry.get_gauge(
            "perf/floor_ms", program=STEP) > 0
    for _ in range(2):
        engine.train_batch(batch=batch)
    reg = engine.telemetry_registry
    for name in ("perf/achieved_ms", "perf/achieved_vs_floor",
                 "comm/achieved_vs_floor"):
        assert reg.get_gauge(name, program=STEP) is None
    assert STEP not in costmodel.get_achieved()
