"""Unified telemetry (ISSUE 4 tentpole): metrics registry + Prometheus
exposition, Chrome-trace span tracer with correlation ids, MFU/goodput
gauges, and the satellites (serving quantiles on /metrics, CSV writer
reuse, comms summary as monitor events, trace schema validation).

The acceptance test at the bottom runs a chaos-smoke-style session —
5-step toy train + checkpoint save/restore + 3-request serve with
injected faults, all under one DS_TRACE — and asserts the emitted trace
passes ``scripts/trace_validate.py`` and contains train-step,
serving-iteration, checkpoint, and fault events sharing correlation
ids, while both /metrics surfaces expose the new histograms and an
``mfu`` gauge.
"""
import json
import os
import urllib.request

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.telemetry import (MetricsRegistry, MetricsServer,
                                     configure_tracer, mfu,
                                     peak_flops_per_device, reset_tracer,
                                     serving_goodput, tokens_per_second)
from deepspeed_tpu.telemetry.tracing import SpanTracer
from scripts.trace_validate import load_events, validate, validate_events
from tests.util import base_config, random_batches, tiny_gpt2


@pytest.fixture(autouse=True)
def _tracer_isolation():
    """Every test starts and ends with the null tracer armed."""
    reset_tracer()
    yield
    reset_tracer()


# ----------------------------------------------------------------- registry
def test_registry_counters_gauges_labels():
    r = MetricsRegistry()
    r.inc("requests")
    r.inc("requests", 2)
    r.inc("retry/retries", op="save")
    r.inc("retry/retries", op="load")
    r.inc("retry/retries", op="save")
    r.set_gauge("mfu", 0.42)
    assert r.get_counter("requests") == 3
    assert r.get_counter("retry/retries", op="save") == 2
    assert r.get_gauge("mfu") == 0.42
    assert r.get_gauge("missing") is None
    snap = r.snapshot()
    assert snap["requests"] == 3
    assert snap["retry/retries{op=save}"] == 2


def test_registry_histogram_buckets_and_quantiles():
    r = MetricsRegistry()
    h = r.histogram("lat_s", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count == 5
    assert h.sum == pytest.approx(5.605)
    cum = h.cumulative_counts()
    assert cum == [(0.01, 1), (0.1, 3), (1.0, 4), (float("inf"), 5)]
    # exact quantiles over the reservoir window, not bucket edges
    assert h.quantile(50) == pytest.approx(0.05)
    assert h.quantile(0) == pytest.approx(0.005)
    assert h.quantile(100) == pytest.approx(5.0)
    # same (name, labels) -> same histogram object
    assert r.histogram("lat_s") is h


def test_registry_prometheus_rendering():
    r = MetricsRegistry()
    r.inc("serving/completed", 3)
    r.set_gauge("train/mfu", 0.25, host="a")
    h = r.histogram("serving/ttft_s", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = r.render_prometheus()
    assert "# TYPE serving_completed counter" in text
    assert "serving_completed 3" in text
    assert '# TYPE train_mfu gauge' in text
    assert 'train_mfu{host="a"} 0.25' in text
    assert "# TYPE serving_ttft_s histogram" in text
    assert 'serving_ttft_s_bucket{le="0.1"} 1' in text
    assert 'serving_ttft_s_bucket{le="+Inf"} 2' in text
    assert "serving_ttft_s_count 2" in text
    assert "serving_ttft_s_sum 0.55" in text


def test_registry_to_events_bridge():
    from deepspeed_tpu.monitor.monitor import InMemoryMonitor
    r = MetricsRegistry()
    r.inc("train/steps", 7)
    r.histogram("train/step_latency_s").observe(0.2)
    sink = InMemoryMonitor()
    sink.write_events(r.to_events(step=7))
    assert sink.latest["train/steps"] == (7.0, 7)
    assert sink.latest["train/step_latency_s_count"] == (1.0, 7)
    assert "train/step_latency_s_p50" in sink.latest


# ------------------------------------------------------------------- tracer
def test_tracer_spans_corr_inheritance_and_schema(tmp_path):
    path = str(tmp_path / "trace.json")
    t = SpanTracer(path)
    with t.span("train/step", cat="train", corr="train-step-1"):
        t.instant("fault/train.step", cat="resilience")
        with t.span("ckpt/stage", cat="ckpt"):
            pass
    with t.span("serve/step", cat="serving", corr="serve-step-0"):
        pass
    t.flush()
    assert validate(path, require_corr=True) == []
    evs = load_events(path)
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    # the instant and the nested span inherit the enclosing corr id
    assert by_name["fault/train.step"][0]["args"]["corr"] == "train-step-1"
    assert by_name["ckpt/stage"][0]["args"]["corr"] == "train-step-1"
    assert by_name["serve/step"][0]["args"]["corr"] == "serve-step-0"
    # sorted, balanced
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)


def test_tracer_flush_merges_and_null_tracer(tmp_path):
    path = str(tmp_path / "t.json")
    t = SpanTracer(path)
    with t.span("a"):
        pass
    t.flush()
    with t.span("b"):
        pass
    t.flush()                                 # appends, stays valid
    assert validate(path) == []
    assert {e["name"] for e in load_events(path)} == {"a", "b"}
    # unarmed: configure without a path returns a no-op tracer
    null = configure_tracer(None)
    assert not null.enabled
    with null.span("x"):
        null.instant("y")
    assert null.flush() is None


def test_trace_validator_catches_violations():
    assert validate_events([]) != []
    ok = [{"name": "a", "ph": "B", "ts": 0, "pid": 1, "tid": 1},
          {"name": "a", "ph": "E", "ts": 1, "pid": 1, "tid": 1}]
    assert validate_events(ok) == []
    bad_order = [dict(ok[0], ts=5), dict(ok[1], ts=1)]
    assert any("not sorted" in e for e in validate_events(bad_order))
    unbalanced = [ok[0]]
    assert any("unclosed" in e for e in validate_events(unbalanced))
    mismatched = [ok[0], dict(ok[1], name="z")]
    assert any("does not match" in e for e in validate_events(mismatched))
    missing = [{"ph": "B", "ts": 0}]
    assert any("missing required" in e for e in validate_events(missing))
    bad_x = [{"name": "x", "ph": "X", "ts": 0, "pid": 1, "tid": 1}]
    assert any("dur" in e for e in validate_events(bad_x))


def test_trace_validate_cli(tmp_path):
    from scripts.trace_validate import main
    path = str(tmp_path / "trace.json")
    t = SpanTracer(path)
    with t.span("s", corr="c-1"):
        pass
    t.flush()
    assert main([path, "--require-corr", "-q"]) == 0
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        json.dump({"traceEvents": [{"ph": "E", "name": "x", "ts": 0,
                                    "pid": 1, "tid": 1}]}, f)
    assert main([bad, "-q"]) == 1


# ---------------------------------------------------------------- MFU math
def test_mfu_and_goodput_math():
    assert mfu(2e12, 1.0, 4e12) == pytest.approx(0.5)
    assert mfu(1e12, 2.0, 1e12) == pytest.approx(0.5)
    assert mfu(1e12, 0.0, 1e12) is None          # degenerate, not inf
    assert mfu(1e12, 1.0, 0.0) is None
    assert tokens_per_second(100, 4.0) == pytest.approx(25.0)
    assert tokens_per_second(100, 0.0) is None
    assert serving_goodput(90, 10) == pytest.approx(0.9)
    assert serving_goodput(0, 0) == 1.0          # idle wasted nothing
    assert serving_goodput(0, 5) == 0.0


def test_peak_flops_resolution():
    # env override wins regardless of device kind (CPU here)
    assert peak_flops_per_device(env={"DS_PEAK_FLOPS": "2.5e12"}) \
        == pytest.approx(2.5e12)
    # CPU has no table entry: None, so the MFU gauge is skipped rather
    # than reported against a fictitious peak
    assert peak_flops_per_device(env={}) is None

    class FakeDev:
        device_kind = "TPU v4"
    assert peak_flops_per_device(FakeDev(), env={}) == pytest.approx(275e12)


def test_compiled_cost_known_matmul():
    """Satellite: cost-analysis FLOPs/bytes on a known matmul, CPU-only.
    XLA counts a (M,K)@(K,N) dense matmul as 2*M*K*N flops."""
    import jax.numpy as jnp
    from deepspeed_tpu.profiling.flops_profiler.profiler import \
        compiled_cost
    M, K, N = 64, 128, 32
    a = jnp.zeros((M, K), jnp.float32)
    b = jnp.zeros((K, N), jnp.float32)
    cost = compiled_cost(lambda x, y: x @ y, a, b)
    expect = 2.0 * M * K * N
    assert cost["flops"] == pytest.approx(expect, rel=0.01)
    # bytes accessed covers at least operands + result once
    min_bytes = 4 * (M * K + K * N + M * N)
    assert cost["bytes_accessed"] >= min_bytes * 0.5
    assert cost["analysis"]                    # raw table passes through


def test_flops_profiler_mfu():
    from deepspeed_tpu.profiling.flops_profiler.profiler import \
        FlopsProfiler
    p = FlopsProfiler()
    p.total_flops = 3e12
    p.total_duration = 2.0
    assert p.achieved_flops_per_s() == pytest.approx(1.5e12)
    assert p.mfu(3e12) == pytest.approx(0.5)
    assert p.mfu(0.0) is None


# -------------------------------------------------------- metrics endpoint
def test_metrics_http_endpoint_scrape():
    r = MetricsRegistry()
    r.set_gauge("train/mfu", 0.33)
    r.histogram("train/step_latency_s").observe(0.1)
    srv = MetricsServer(r, port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
            assert resp.status == 200
            text = resp.read().decode()
        assert "train_mfu 0.33" in text
        assert "train_step_latency_s_bucket" in text
        with urllib.request.urlopen(base + "/healthz", timeout=10) as resp:
            assert resp.status == 200
    finally:
        srv.stop()


# -------------------------------------------------------------- telemetry config
def test_telemetry_config_roundtrip_and_validation():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig, \
        TelemetryConfig
    cfg = DeepSpeedConfig({**base_config(),
                           "telemetry": {"trace": "/tmp/t.json",
                                         "metrics_port": 9100,
                                         "monitor_interval": 4,
                                         "peak_flops": 1e12}})
    t = cfg.telemetry_config
    assert (t.trace, t.metrics_port, t.monitor_interval, t.peak_flops) \
        == ("/tmp/t.json", 9100, 4, 1e12)
    assert DeepSpeedConfig(base_config()).telemetry_config.enabled
    with pytest.raises(ValueError, match="metrics_port"):
        TelemetryConfig(metrics_port=-1)
    with pytest.raises(ValueError, match="monitor_interval"):
        TelemetryConfig(monitor_interval=-1)
    with pytest.raises(ValueError, match="peak_flops"):
        TelemetryConfig(peak_flops=-1.0)


# ------------------------------------------------------------- satellites
def test_csv_monitor_reuses_writers(tmp_path):
    """Satellite: CSVMonitor keeps handles open across write_events
    batches instead of reopening per event."""
    from deepspeed_tpu.monitor.monitor import CSVMonitor

    class Cfg:
        enabled = True
        output_path = str(tmp_path)
        job_name = "job"

    mon = CSVMonitor(Cfg())
    mon.write_events([("Train/loss", 1.0, 1), ("Train/lr", 0.1, 1)])
    handle_ids = {name: id(f) for name, (f, _w) in mon._files.items()}
    mon.write_events([("Train/loss", 0.5, 2)])
    # same open handle, not a reopen
    assert id(mon._files["Train/loss"][0]) == handle_ids["Train/loss"]
    mon.close()
    assert mon._files == {}
    loss_csv = os.path.join(str(tmp_path), "job", "Train_loss.csv")
    with open(loss_csv) as f:
        rows = [line.strip().split(",") for line in f if line.strip()]
    assert rows == [["step", "Train/loss"], ["1", "1.0"], ["2", "0.5"]]
    # reopening after close appends (no duplicate header)
    mon2 = CSVMonitor(Cfg())
    mon2.write_events([("Train/loss", 0.25, 3)])
    mon2.close()
    with open(loss_csv) as f:
        assert sum(1 for line in f if line.startswith("step")) == 1


def test_comms_logger_events_and_explicit_op_names():
    """Satellite: log_summary feeds monitor sinks; the sys._getframe
    caller lookup is gone in favor of explicit op names."""
    from deepspeed_tpu.monitor.monitor import InMemoryMonitor
    from deepspeed_tpu.utils import comms_logging
    from deepspeed_tpu.utils.comms_logging import CommsLogger
    assert not hasattr(comms_logging, "get_caller_func")
    cl = CommsLogger()
    cl.append("all_reduce", 1024, 0.001)
    cl.append("all_reduce", 1024, 0.002)
    cl.append("all_gather", 4096, 0.004)
    sink = InMemoryMonitor()
    cl.log_summary(print_log=False, monitor=sink, step=12)
    assert sink.latest["comms/all_reduce/calls"] == (2.0, 12)
    assert sink.latest["comms/all_reduce/total_bytes"] == (2048.0, 12)
    assert sink.latest["comms/all_gather/total_time_ms"] == (4.0, 12)
    # module-level wrapper passes the monitor through
    from deepspeed_tpu import comm as _comm
    _comm.configure(comms_logger=cl)
    try:
        sink2 = InMemoryMonitor()
        _comm.log_summary(monitor=sink2, step=3)
        assert sink2.latest["comms/all_gather/calls"] == (1.0, 3)
    finally:
        _comm.configure(comms_logger=None)


# ----------------------------------------------------- serving /metrics
@pytest.fixture(scope="module")
def served():
    m = tiny_gpt2()
    eng = deepspeed_tpu.init_inference(model=m, config={"dtype": "float32"})
    return m, eng


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 128, (int(L),)).astype(np.int32)
            for L in rng.integers(3, 10, n)]


def test_serving_metrics_quantiles_and_prometheus(served):
    """Satellite: /metrics exposes p50/p90/p99 for TTFT/TPOT/queue-wait
    plus histogram buckets, scraped over real HTTP."""
    import threading
    from deepspeed_tpu.runtime.config import ServingConfig
    from deepspeed_tpu.serving import (ContinuousBatchingScheduler,
                                       SamplingParams)
    from deepspeed_tpu.serving.server import make_server
    m, eng = served
    cfg = ServingConfig(block_size=8, num_blocks=32, max_num_seqs=2)
    sched = ContinuousBatchingScheduler(m, eng.params, cfg,
                                        registry=MetricsRegistry())
    for p in _prompts(3, seed=5):
        sched.submit(p, SamplingParams(max_new_tokens=3))
    sched.run_until_idle()
    snap = sched.metrics_snapshot()
    for stem in ("ttft", "token_latency", "queue_wait"):
        for q in ("p50", "p90", "p99"):
            assert f"serving/{stem}_{q}_ms" in snap, (stem, q, snap)
    assert snap["serving/goodput"] == 1.0     # nothing preempted
    # the requests already drained synchronously: scrape the endpoint
    # without starting the serving loop thread
    httpd, _loop = make_server(sched, port=0)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        url = f"http://127.0.0.1:{httpd.server_port}/metrics"
        with urllib.request.urlopen(url, timeout=10) as resp:
            text = resp.read().decode()
        assert "# TYPE serving_ttft_s histogram" in text
        assert 'serving_ttft_s_bucket{le="+Inf"} 3' in text
        assert "serving_queue_wait_s_count 3" in text
        assert "serving_token_latency_s_bucket" in text
        assert "serving_ttft_p99_ms" in text
        assert "serving_decode_occupancy_bucket" in text
        assert "serving_goodput 1" in text
    finally:
        httpd.shutdown()
        httpd.server_close()


# ------------------------------------------------- acceptance: one timeline
def test_chaos_session_trace_and_metrics(tmp_path, monkeypatch, served):
    """ISSUE 4 acceptance: a chaos-smoke-style run with DS_TRACE set
    produces ONE trace that trace_validate accepts, containing
    train-step, serving-iteration, checkpoint, and fault events sharing
    correlation ids; /metrics (serve) and the training endpoint both
    expose the new histograms and an mfu gauge."""
    from deepspeed_tpu.resilience.faults import FaultInjected, FaultInjector
    from deepspeed_tpu.runtime.config import ServingConfig
    from deepspeed_tpu.serving import (ContinuousBatchingScheduler,
                                       SamplingParams)
    from deepspeed_tpu.telemetry import get_registry
    trace_path = str(tmp_path / "chaos_trace.json")
    monkeypatch.setenv("DS_TRACE", trace_path)
    monkeypatch.setenv("DS_PEAK_FLOPS", "1e12")   # CPU: MFU needs a peak
    tracer = configure_tracer()

    # ---- train: 5 steps + checkpoint save/restore, faults armed ------
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=tiny_gpt2(),
        config=base_config(
            # the rate gauges are written where the engine waits for the
            # device: a print boundary inside the five steps
            steps_per_print=4,
            telemetry={"metrics_port": 0},
            resilience={"faults": "train.step:stall=0@2"}))
    for i in range(5):
        engine.train_batch(iter(random_batches(1, batch_size=8, seed=i)))
    engine.save_checkpoint(str(tmp_path / "ckpt"))
    engine.load_checkpoint(str(tmp_path / "ckpt"))

    # ---- serve: 3 requests with a fault on the second iteration,
    # speculative (ngram) mode so the trace carries draft/verify spans
    # (ISSUE 5 acceptance) ---------------------------------------------
    m, eng = served
    sched = ContinuousBatchingScheduler(
        m, eng.params,
        ServingConfig(block_size=8, num_blocks=32, max_num_seqs=2,
                      spec={"mode": "ngram", "max_draft_tokens": 4},
                      prefix_cache={"enabled": True}),
        registry=MetricsRegistry(),
        injector=FaultInjector("serve.step:raise@1"))
    for p in _prompts(2, seed=7):
        sched.submit(p, SamplingParams(max_new_tokens=3))
    # a repetitive prompt so the ngram proposer actually drafts —
    # submitted twice so the second admission hits the prefix cache
    # (ISSUE 6: its serve/prefix_match span joins the timeline)
    for _ in range(2):
        sched.submit(np.tile(np.asarray([9, 23, 4], np.int32), 5),
                     SamplingParams(max_new_tokens=8))
    faults_seen = 0
    while sched.has_work():
        try:
            sched.step()
        except FaultInjected:
            faults_seen += 1
    assert faults_seen == 1

    # ---- the one coherent timeline -----------------------------------
    tracer.flush()
    assert validate(trace_path, require_corr=True) == []
    evs = load_events(trace_path)
    spans = [e for e in evs if e["ph"] == "B"]
    instants = [e for e in evs if e["ph"] == "i"]

    def corrs(events, name):
        return {e.get("args", {}).get("corr")
                for e in events if e["name"] == name}

    train_corrs = corrs(spans, "train/step")
    serve_corrs = corrs(spans, "serve/step")
    ckpt_corrs = corrs(spans, "ckpt/stage") | corrs(spans, "ckpt/publish") \
        | corrs(spans, "ckpt/restore")
    fault_corrs = {e.get("args", {}).get("corr") for e in instants
                   if e["name"].startswith("fault/")}
    assert {f"train-step-{i}" for i in range(1, 6)} <= train_corrs
    assert serve_corrs and ckpt_corrs
    assert ckpt_corrs == {"ckpt-global_step5"}
    # faults fired INSIDE a train step and a serve iteration inherit
    # those spans' correlation ids — the timeline reads as one story
    assert fault_corrs & train_corrs
    assert fault_corrs & serve_corrs
    # ISSUE 5: the spec-mode session's draft and verify spans share the
    # request correlation id (one request's speculation reads as one
    # story too)
    from scripts.trace_validate import correlated_spans
    spec_corrs = correlated_spans(evs, ("serve/draft", "serve/verify"))
    assert any(names == {"serve/draft", "serve/verify"}
               for names in spec_corrs.values())
    assert all(c.startswith("req-") for c in spec_corrs)
    # ISSUE 6: every cache lookup runs inside a serve/prefix_match span
    # under its request's correlation id
    match_corrs = corrs(spans, "serve/prefix_match")
    assert match_corrs and all(c.startswith("req-") for c in match_corrs)

    # ---- both metrics surfaces ---------------------------------------
    reg = get_registry()
    snap = reg.snapshot()
    assert snap.get("train/step_latency_s_count", 0) >= 5
    assert snap.get("ckpt/save_duration_s_count", 0) >= 1
    assert snap.get("ckpt/restore_duration_s_count", 0) >= 1
    assert 0 < snap["train/mfu"] < 1
    url = f"http://127.0.0.1:{engine.metrics_server.port}/metrics"
    with urllib.request.urlopen(url, timeout=10) as resp:
        text = resp.read().decode()
    assert "train_mfu" in text
    assert "train_step_latency_s_bucket" in text
    assert "ckpt_save_duration_s_bucket" in text
    serve_text = sched.render_metrics()
    assert "serving_ttft_s_bucket" in serve_text
    assert "serving_goodput" in serve_text
    # ISSUE 5: /metrics exposes the spec accept-length histogram with
    # quantile gauges
    assert "# TYPE serve_spec_accept_len histogram" in serve_text
    assert "serve_spec_accept_len_p50" in serve_text
    assert "serve_spec_accept_len_p99" in serve_text
    # ISSUE 6: prefix-cache counters + hit-rate/cached-blocks gauges ride
    # the same exposition (the duplicated prompt above guarantees a hit)
    assert "serving_prefix_cache_hit" in serve_text
    assert "serving_prefix_cache_miss" in serve_text
    assert "serving_prefix_cache_hit_rate" in serve_text
    assert "serving_cached_blocks" in serve_text
    assert sched.metrics.counters["prefix_cache_hit"] > 0
    engine.metrics_server.stop()
