"""The reader of the program's set-up account (setup_account): known
answers on a hand-made account — the cut at the first timed step, nothing
to read without an account, the raise without a row of train_step, the
eight timings disjoint and adding up."""
import copy
import importlib

import pytest

from harness.manifest import Manifest
from layer_metrics.readers import setup_account as reader
from layer_metrics.readers.step_phase import BrokenJoin

tracing = pytest.importorskip("deepspeed_tpu.telemetry.tracing")
METRICS = ["setup." + v for v in reader.VALUES]


def span(id_, name, start, end, parent, step, self_s):
    return {"id": id_, "name": name, "start": start, "end": end,
            "parent": parent, "step": step, "self_s": self_s}


def row(program, stage, start, end, self_s, span_id, cause, step,
        retrace=False, recompile=False, missed=0, **more):
    return {"program": program, "stage": stage, "start": start, "end": end,
            "self_s": self_s, "span": span_id, "cause": cause, "step": step,
            "retrace": retrace, "recompile": recompile, "missed": missed,
            **more}


INIT, SHARD, PARAMS, OPT = ("engine/init", "engine/init/shardings",
                            "engine/init/params", "engine/init/optimizer")
STEP, FUSED = "train/step", "train/fused_step"
ANALYZE, AOT, TEXT = "costmodel/analyze", "compile/aot", "program_map/text"


def synthetic():
    """An engine built in 10 s, the caller's own compile, four warm-up
    steps (the first traces, lowers and compiles the step and walks it
    once more for the cost report; the second compiles again from the
    cache), an ahead-of-time compile asked from outside, then four timed
    steps and, after them, the executable's text."""
    spans = [
        span(0, INIT, 0, 10, None, 0, 2.0),
        span(1, SHARD, 1, 2, 0, 0, 0.5),
        span(2, PARAMS, 2, 6, 0, 0, 1.0),
        span(3, OPT, 6, 9, 0, 0, 2.0),
        span(4, STEP, 20, 40, None, 0, 2.0),
        span(5, FUSED, 21, 33, 4, 0, 1.0),
        span(6, ANALYZE, 33, 39, 4, 0, 2.0),
        span(7, STEP, 40, 50, None, 1, 2.0),
        span(8, FUSED, 41, 49, 7, 1, 1.0),
        span(9, STEP, 50, 51, None, 2, 0.5),
        span(10, FUSED, 50.2, 50.7, 9, 2, 0.5),
        span(11, STEP, 51, 52, None, 3, 0.6),
        span(12, FUSED, 51.2, 51.6, 11, 3, 0.4),
        span(13, AOT, 60, 63, None, 4, 0.5),
        span(14, STEP, 70, 71, None, 4, 0.6),       # the first timed step
        span(15, FUSED, 70.1, 70.5, 14, 4, 0.4),
        span(16, STEP, 71, 72, None, 5, 0.6),
        span(17, FUSED, 71.1, 71.5, 16, 5, 0.4),
        span(18, TEXT, 80, 82, None, 8, 0.25),
        span(19, AOT, 80.25, 82, 18, 8, 0.25),
    ]
    rows = [
        row("other", "trace", 1.0, 1.5, 0.5, 1, SHARD, 0, count=4),
        row("other", "trace", 2.0, 2.5, 0.5, 2, PARAMS, 0, count=9),
        row("other", "lower", 2.5, 3.0, 0.5, 2, PARAMS, 0, count=1),
        row("other", "compile", 3.0, 5.0, 2.0, 2, PARAMS, 0, missed=1,
            count=1),
        row("other", "cache_load", 6.0, 7.0, 1.0, 3, OPT, 0, count=1,
            retrieval_s=0.9, saved_s=5.0),
        # the benchmark's own reference: outside every span
        row("other", "compile", 12.0, 15.0, 3.0, None, None, 0, missed=1,
            count=2),
        row("train_step", "trace", 21, 26, 4.0, 5, FUSED, 0),
        row("other", "trace", 22, 25, 1.0, 5, FUSED, 0, count=30),
        row("train_step", "lower", 26, 28, 2.0, 5, FUSED, 0),
        row("train_step", "compile", 28, 32, 4.0, 5, FUSED, 0, missed=1),
        row("train_step", "trace", 33, 37, 3.5, 6, ANALYZE, 0, retrace=True),
        row("other", "trace", 34, 36, 0.5, 6, ANALYZE, 0, retrace=True,
            count=25),
        row("train_step", "trace", 41, 44, 3.0, 8, FUSED, 1, retrace=True,
            recompile=True),
        row("train_step", "lower", 44, 45, 1.0, 8, FUSED, 1, recompile=True),
        row("train_step", "cache_load", 45, 48, 3.0, 8, FUSED, 1,
            recompile=True, retrieval_s=2.5, saved_s=1.0),
        row("train_step", "lower", 60, 61, 1.0, 13, AOT, 4),
        row("train_step", "cache_load", 61, 62.5, 1.5, 13, AOT, 4,
            retrieval_s=1.4, saved_s=2.0),
        row("train_step", "lower", 80.25, 81, 0.75, 19, AOT, 8),
        row("train_step", "cache_load", 81, 81.75, 0.75, 19, AOT, 8,
            retrieval_s=0.7, saved_s=3.0),
    ]
    return {"spans": spans, "rows": rows, "steps": 8}


KNOWN = {"state_init_s": 5.5, "trace_s": 6.0, "retrace_s": 7.0,
         "lower_s": 4.5, "compile_s": 6.0, "cache_load_s": 5.5,
         "analysis_s": 2.5, "cache_miss_count": 2,
         # two calls that held rows count as the median of the two that
         # held none (0.45); the other 0.55 s of each are unattributed,
         # with train/step's own 2 + 2 + 0.5 + 0.6
         "dispatch_s": 0.5 + 0.4 + 2 * 0.45, "spans_s": 45.0,
         "unattributed_s": 6.2}


def test_known_answers():
    got = reader.reduce(synthetic(), 4, tracing)
    assert got == pytest.approx(KNOWN)
    assert sum(got[v] for v in reader.TIMINGS) + got["dispatch_s"] \
        == pytest.approx(got["spans_s"])
    assert all(got[v] >= 0 for v in reader.VALUES)


@pytest.mark.parametrize("metric", METRICS)
def test_each_metric_reads_its_value(metric, monkeypatch):
    spec = Manifest().layer_metric(metric)
    assert spec["reader"] == "setup_account"
    monkeypatch.setattr(tracing, "setup_account", synthetic)
    value = importlib.import_module(
        "layer_metrics.readers." + spec["reader"]).read(
        {"steps": 4}, spec["params"])
    assert value == pytest.approx(KNOWN[metric.partition(".")[2]])


def test_the_cut_is_the_first_timed_step():
    account = synthetic()
    # what began at or after the first timed step is not set-up ...
    account["rows"].append(
        row("train_step", "compile", 70.2, 70.4, 100.0, 15, FUSED, 4,
            missed=1))
    assert reader.reduce(account, 4, tracing) == pytest.approx(KNOWN)
    # ... and with five steps timed the cut is a step earlier: the
    # ahead-of-time compile and the fourth warm-up step fall out
    got = reader.reduce(synthetic(), 5, tracing)
    assert got["spans_s"] == pytest.approx(45.0 - 3.0 - 1.0)
    assert got["analysis_s"] == pytest.approx(2.0)
    assert got["lower_s"] == pytest.approx(3.5)
    assert got["cache_load_s"] == pytest.approx(4.0)
    assert got["dispatch_s"] == pytest.approx(0.5 + 2 * 0.5)


def test_the_callers_own_compiles_are_not_the_programs():
    account = synthetic()
    account["rows"] = [r for r in account["rows"] if r["span"] is not None]
    assert reader.reduce(account, 4, tracing) == pytest.approx(KNOWN)


def moved(before, after):
    return {k for k in reader.TIMINGS[:-1] + ("dispatch_s",)
            if abs(after[k] - before[k]) > 1e-9}


def test_the_timings_are_disjoint():
    base = reader.reduce(synthetic(), 4, tracing)
    seen = set()
    for kind in ("spans", "rows"):
        for i, item in enumerate(synthetic()[kind]):
            account = synthetic()
            account[kind][i]["self_s"] += 1.0
            got = reader.reduce(account, 4, tracing)
            which = moved(base, got)
            assert len(which) <= 1, (item, which)
            set_up = item["start"] < 70 and (kind == "spans"
                                             or item["span"] is not None)
            if not set_up or item["name" if kind == "spans"
                                  else "program"] == STEP:
                assert which == set()
            elif item.get("name") == FUSED:
                # a call: dispatch alone, by the median where it held rows
                assert which <= {"dispatch_s"}
            else:
                assert len(which) == 1, item
                assert got[min(which)] == pytest.approx(base[min(which)] + 1)
            seen |= which
            # the remainder takes what the others do not
            assert sum(got[v] for v in reader.TIMINGS) + got["dispatch_s"] \
                == pytest.approx(got["spans_s"])
    assert seen == set(reader.TIMINGS[:-1]) | {"dispatch_s"}


def test_nothing_to_read_without_an_account(monkeypatch):
    monkeypatch.delattr(tracing, "setup_account")
    assert reader.read({"steps": 4}, {"value": "trace_s"}) is None


def test_an_account_without_the_step_raises(monkeypatch):
    account = synthetic()
    account["rows"] = [r for r in account["rows"]
                       if r["program"] != "train_step"]
    monkeypatch.setattr(tracing, "setup_account", lambda: account)
    with pytest.raises(BrokenJoin, match="no row of train_step"):
        reader.read({"steps": 4}, {"value": "trace_s"})
    empty = {"spans": [], "rows": [], "steps": 0}
    monkeypatch.setattr(tracing, "setup_account", lambda: empty)
    with pytest.raises(BrokenJoin, match="holds no train/step"):
        reader.read({"steps": 4}, {"value": "trace_s"})


def test_an_account_that_lost_the_first_timed_step_raises():
    account = synthetic()
    account["spans"] = [s for s in account["spans"] if s["id"] != 14]
    with pytest.raises(BrokenJoin, match="at step 4"):
        reader.reduce(account, 4, tracing)
    with pytest.raises(BrokenJoin):       # every step was timed
        reader.reduce(synthetic(), 8, tracing)


def test_the_entries_are_setups_in_every_cell():
    manifest = Manifest()
    cells = [w["name"] for w in manifest.data["workloads"]]
    entries = {m["name"]: m for m in manifest.data["per_layer"]}
    assert sorted(n for n in entries if n.startswith("setup.")) \
        == sorted(METRICS)
    for name in METRICS:
        entry = entries[name]
        assert entry["moves"] == "setup_s" and entry["layer"] == "engine"
        assert entry["better"] == "lower"
        assert entry["workloads"] == cells[:len(entry["workloads"])]
        assert len(entry["workloads"]) >= 6
        count = name == "setup.cache_miss_count"
        assert entry["unit"] == ("count" if count else "s")
        assert entry["source"] == ("program_counter" if count
                                   else "program_span")
    # they are the first metrics under setup_s
    assert {m["name"] for m in manifest.data["per_layer"]
            if m["moves"] == "setup_s"} == set(METRICS)


def test_the_account_of_a_toy_engine_reduces():
    """The program's real account, a toy engine on the CPU: the reader
    takes what the program writes (counts and the identity, no rates)."""
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import gpt2_model
    tracing.reset_programs()
    model = gpt2_model(size="custom", vocab_size=128, max_seq_len=64,
                       num_layers=2, num_heads=4, d_model=32,
                       dtype="float32", attention_impl="xla", remat=True)
    engine, *_ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1, "steps_per_print": 0,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
    batch = {"input_ids": np.zeros(
        (1, engine.topology.dp_world_size, 16), np.int32)}
    for _ in range(5):
        engine.train_batch(batch=batch)
    account = copy.deepcopy(tracing.setup_account())
    tracing.reset_programs()
    got = reader.reduce(account, 2, tracing)
    assert sum(got[v] for v in reader.TIMINGS) + got["dispatch_s"] \
        == pytest.approx(got["spans_s"])
    assert got["trace_s"] > 0 and got["lower_s"] > 0
    assert got["compile_s"] + got["cache_load_s"] > 0
    assert got["state_init_s"] > 0
    # a start analyses nothing it was not asked for: nobody asked for the
    # step's cost, so there is no second trace and no analysis
    assert got["retrace_s"] == 0 and got["analysis_s"] == 0
    assert -1e-6 <= got["unattributed_s"] < got["spans_s"]
