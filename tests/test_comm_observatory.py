"""Communication observatory (ISSUE 19): per-collective cost
attribution, the interconnect roofline, CommStat runtime telemetry,
and the comm chaos drill.

Acceptance (tier-1):

- **parity** — the costmodel's per-axis collective attribution prices a
  2-device data-parallel gradient all-reduce at the ring-wire formula
  ``2*(N-1)/N * param_bytes`` within 2%;
- **no fictitious floors** — ``comm/floor_ms`` and
  ``comm/achieved_vs_floor`` publish ONLY when an interconnect rate is
  declared (``DS_ICI_GBPS``) or known from the device table — never on
  bare CPU;
- **chaos drill** — a multi-device CPU-mesh training run with an
  injected ``comm.collective`` stall raises ``anomaly/comm_*`` carrying
  the wedged step's ``train-step-N`` corr id, answers ``/debug/comm``
  over live HTTP while wedged, and lands ``comm.json`` in the
  post-mortem bundle; the DS_TRACE file validates clean including the
  ``comm/*`` span schema.
"""
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from deepspeed_tpu.utils.jax_compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.telemetry import (MetricsRegistry, configure_tracer,
                                     reset_tracer)
from deepspeed_tpu.telemetry import costmodel, roofline
from deepspeed_tpu.telemetry.commstat import (CommStat, commstat_enabled,
                                              get_commstat, peek_commstat,
                                              reset_commstat)
from deepspeed_tpu.telemetry.debug import comm_payload
from scripts.trace_validate import load_events, validate
from tests.util import base_config, random_batch, tiny_gpt2


@pytest.fixture(autouse=True)
def _comm_isolation():
    reset_commstat()
    costmodel.reset_reports()
    yield
    reset_commstat()
    costmodel.reset_reports()


def _mesh(n):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} devices")
    return Mesh(np.array(devs[:n]), ("data",))


# ------------------------------------------------ costmodel attribution
def test_dp_grad_allreduce_parity_acceptance():
    """ISSUE 19 acceptance: a 2-device DP gradient psum prices at
    2*(N-1)/N * param_bytes on the wire, within 2%."""
    mesh = _mesh(2)
    w = jnp.zeros((32, 64), jnp.float32)
    x = jnp.zeros((8, 32), jnp.float32)

    def grad_shard(w, x):
        g = jax.grad(lambda w: jnp.sum((x @ w) ** 2))(w)
        return jax.lax.psum(g, "data")

    f = shard_map(grad_shard, mesh=mesh, in_specs=(P(), P("data")),
                  out_specs=P(), check_vma=False)
    rep = costmodel.analyze_fn(f, w, x, name="train/dp_grad")
    row = rep.collectives["all_reduce|data|float32"]
    param_bytes = w.size * w.dtype.itemsize
    expect = 2 * (2 - 1) / 2 * param_bytes
    assert abs(row["wire_bytes"] - expect) / expect < 0.02
    assert row["axis_size"] == 2
    assert row["payload_bytes"] == param_bytes
    assert rep.comm_wire_bytes() == row["wire_bytes"]


def test_collective_family_accounting():
    """all_gather / psum_scatter / ppermute canonicalize and take their
    ring wire factors (gather/scatter (N-1)/N of the logical payload,
    ppermute 1.0)."""
    mesh = _mesh(4)
    n = 4

    def body(x):
        g = jax.lax.all_gather(x, "data")
        s = jax.lax.psum_scatter(x, "data")
        p = jax.lax.ppermute(x, "data",
                             [(i, (i + 1) % n) for i in range(n)])
        return jnp.sum(g) + jnp.sum(s) + jnp.sum(p)

    x = jnp.zeros((n * 4,), jnp.float32)
    f = shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P(),
                  check_vma=False)
    rep = costmodel.analyze_fn(f, x, name="probe/collectives")
    shard_bytes = (x.size // n) * x.dtype.itemsize
    ag = rep.collectives["all_gather|data|float32"]
    assert ag["payload_bytes"] == shard_bytes * n   # logical full tensor
    assert ag["wire_bytes"] == round(shard_bytes * n * (n - 1) / n)
    rs = rep.collectives["reduce_scatter|data|float32"]
    assert rs["wire_bytes"] == round(rs["payload_bytes"] * (n - 1) / n)
    pp = rep.collectives["ppermute|data|float32"]
    assert pp["wire_bytes"] == pp["payload_bytes"] == shard_bytes
    assert rep.comm_wire_bytes() == (ag["wire_bytes"] + rs["wire_bytes"]
                                     + pp["wire_bytes"])


def test_ring_wire_factor_formulas():
    assert costmodel.ring_wire_factor("all_reduce", 8) == 2 * 7 / 8
    assert costmodel.ring_wire_factor("all_gather", 8) == 7 / 8
    assert costmodel.ring_wire_factor("reduce_scatter", 4) == 3 / 4
    assert costmodel.ring_wire_factor("ppermute", 4) == 1.0
    # unknown axis size never inflates
    assert costmodel.ring_wire_factor("all_reduce", None) == 1.0


# ------------------------------------------------- interconnect roofline
def test_ici_rate_resolution(monkeypatch):
    monkeypatch.delenv(roofline.ICI_GBPS_ENV, raising=False)
    monkeypatch.delenv(roofline.DCN_GBPS_ENV, raising=False)
    # CPU: no table entry, no env -> None (never a fictitious rate)
    assert roofline.ici_bytes_per_s() is None
    assert roofline.dcn_bytes_per_s() is None

    class FakeV4:
        device_kind = "TPU v4"
    assert roofline.ici_bytes_per_s(FakeV4()) == 300.0 * 1e9
    monkeypatch.setenv(roofline.ICI_GBPS_ENV, "100")
    assert roofline.ici_bytes_per_s(FakeV4()) == 100.0 * 1e9  # env wins
    assert roofline.ici_bytes_per_s() == 100.0 * 1e9
    monkeypatch.setenv(roofline.DCN_GBPS_ENV, "25")
    assert roofline.dcn_bytes_per_s() == 25.0 * 1e9


def test_comm_floor_and_classification():
    rep = costmodel.CostReport(
        name="p", flops=int(1e9), hbm_bytes=int(1e6),
        collective_bytes=0,
        collectives={"all_reduce|data|float32": {
            "calls": 1, "payload_bytes": 10_000_000,
            "wire_bytes": 10_000_000, "axis_size": 4}})
    assert roofline.comm_floor_seconds(rep, None) is None
    assert roofline.comm_floor_seconds(rep, 1e9) == pytest.approx(0.01)
    # comm term dominates -> comm_bound; without an ICI rate the same
    # program classifies by the compute/memory comparison alone
    assert roofline.classify(rep, peak_flops=1e12, hbm_bps=1e12,
                             ici_bps=1e9) == "comm_bound"
    assert roofline.classify(rep, peak_flops=1e12, hbm_bps=1e12,
                             ici_bps=None) == "compute_bound"
    # still None when the compute/memory rates are unknown
    assert roofline.classify(rep, peak_flops=None, hbm_bps=None,
                             ici_bps=1e9) is None


def test_achieved_vs_floor_only_under_declared_bandwidth(monkeypatch):
    """ISSUE 19 acceptance: ``comm/achieved_vs_floor`` publishes ONLY
    when DS_ICI_GBPS (or a known device kind) prices the link — a CPU
    run without the declaration must not invent the gauge."""
    rep = costmodel.CostReport(
        name="train/dp", flops=0, hbm_bytes=64, collective_bytes=0,
        collectives={"all_reduce|data|float32": {
            "calls": 1, "payload_bytes": 8192, "wire_bytes": 8192,
            "axis_size": 2}})
    monkeypatch.delenv(roofline.ICI_GBPS_ENV, raising=False)
    reg = MetricsRegistry()
    roofline.publish_report(reg, rep)
    roofline.observe_achieved(reg, "train/dp", 0.002)
    assert reg.get_gauge("comm/floor_ms", program="train/dp") is None
    assert reg.get_gauge("comm/achieved_vs_floor",
                         program="train/dp") is None
    # wire bytes themselves are declaration-free facts
    assert reg.get_gauge("comm/wire_bytes", program="train/dp") == 8192.0

    monkeypatch.setenv(roofline.ICI_GBPS_ENV, "1")   # 1 GB/s declared
    reg2 = MetricsRegistry()
    roofline.publish_report(reg2, rep)
    roofline.observe_achieved(reg2, "train/dp", 0.002)
    floor_ms = reg2.get_gauge("comm/floor_ms", program="train/dp")
    assert floor_ms == pytest.approx(8192 / 1e9 * 1e3)
    assert reg2.get_gauge("comm/achieved_vs_floor", program="train/dp") \
        == pytest.approx(2.0 / floor_ms)


# ------------------------------------------------------- CommStat runtime
def test_commstat_enabled_resolution(monkeypatch):
    monkeypatch.delenv("DS_COMMSTAT", raising=False)
    assert commstat_enabled() is True
    assert commstat_enabled(False) is False
    monkeypatch.setenv("DS_COMMSTAT", "0")
    assert commstat_enabled(True) is False
    monkeypatch.setenv("DS_COMMSTAT", "1")
    assert commstat_enabled(False) is True


def test_commstat_observe_summary_and_anomaly_feed():
    reg = MetricsRegistry()
    cs = CommStat()
    cs.attach(registry=reg)
    for _ in range(3):
        cs.observe("all_reduce", 1 << 20, 0.001, axis="data")
    cs.record_traced("all_gather", "model", 4096)
    s = cs.summary()
    row = s["ops"]["all_reduce|data"]
    assert row["calls"] == 3 and row["bytes"] == 3 * (1 << 20)
    assert row["last_gbps"] == pytest.approx((1 << 20) / 0.001 / 1e9,
                                             rel=1e-3)
    assert s["traced"]["all_gather|model"]["bytes"] == 4096
    assert reg.get_gauge("comm/achieved_gbps", op="all_reduce") \
        == pytest.approx(row["last_gbps"], rel=1e-3)


def test_commstat_overlap_meter_classifies_threads():
    cs = CommStat()
    cs.step_begin()
    cs.observe("all_reduce", 0, 0.010)            # step thread: exposed
    t = threading.Thread(
        target=lambda: cs.observe("all_gather", 0, 0.030))
    t.start()
    t.join(timeout=30)                            # other thread: hidden
    frac = cs.step_end(0.05)
    assert frac == pytest.approx(0.75, abs=0.01)
    assert cs.summary()["overlap_fraction"] == frac
    # a window that saw no comm publishes nothing (not 0.0)
    cs.step_begin()
    assert cs.step_end(0.05) is None


def test_commstat_fault_gate_deny():
    from deepspeed_tpu.resilience.faults import FaultInjector
    from deepspeed_tpu.telemetry import FlightRecorder
    cs = CommStat()
    assert cs.fault_gate() is False               # no injector: no-op
    fr = FlightRecorder(capacity=64)
    cs.attach(injector=FaultInjector("comm.collective:deny@0"),
              flightrec=fr)
    assert cs.fault_gate() is True
    assert cs.summary()["denied"] == 1
    assert any(e["kind"] == "comm/denied"
               for e in fr.events(kind_prefix="comm/"))


# --------------------------------------------- CommsLogger counters (sat)
def test_comms_logger_registry_counters():
    from deepspeed_tpu.utils.comms_logging import CommsLogger
    reg = MetricsRegistry()
    log = CommsLogger(registry=reg)
    log.append("all_reduce", 1 << 20, duration_s=0.002)
    log.append("all_reduce", 1 << 20, duration_s=0.004)
    assert reg.get_counter("comm/calls", op="all_reduce") == 2.0
    assert reg.get_counter("comm/total_bytes", op="all_reduce") \
        == float(2 << 20)
    assert reg.get_counter("comm/total_time_ms", op="all_reduce") \
        == pytest.approx(6.0)


# ----------------------------------------------------- /debug/comm payload
def test_comm_payload_peeks_never_creates(monkeypatch):
    payload = comm_payload()
    assert payload["armed"] is False
    assert payload["ops"] == {} and payload["programs"] == {}
    assert peek_commstat() is None                # scrape did not arm
    cs = get_commstat()
    cs.observe("barrier", 0, 0.001)
    cs.observe("all_reduce", 1024, 0.001, axis="data")
    monkeypatch.setenv(roofline.ICI_GBPS_ENV, "1")
    rep = costmodel.CostReport(
        name="train/dp", flops=0, hbm_bytes=0, collective_bytes=0,
        collectives={"all_reduce|data|float32": {
            "calls": 1, "payload_bytes": 8192, "wire_bytes": 8192,
            "axis_size": 2}})
    costmodel.register_report(rep)
    payload = comm_payload()
    assert payload["armed"] is True
    assert payload["ici_gbps"] == 1.0
    prog = payload["programs"]["train/dp"]
    assert prog["comm_wire_bytes"] == 8192
    assert prog["comm_floor_ms"] == pytest.approx(8192 / 1e6, rel=1e-3)
    filtered = comm_payload({"op": "all_reduce"})
    assert list(filtered["ops"]) == ["all_reduce|data"]
    assert comm_payload({"program": "nope"})["programs"] == {}


# ------------------------------------------------------ comm_report script
def test_comm_report_script(tmp_path, capsys):
    from scripts.comm_report import main as comm_report_main
    cs = get_commstat()
    cs.observe("all_reduce", 1 << 20, 0.002, axis="data")
    path = tmp_path / "comm.json"
    path.write_text(json.dumps(comm_payload()))
    assert comm_report_main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "all_reduce|data" in out
    assert "no ICI bandwidth" in out
    assert comm_report_main([str(path), "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["armed"] is True
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"not": "a comm payload"}))
    assert comm_report_main([str(bad)]) == 2
    assert comm_report_main([str(tmp_path / "missing.json")]) == 2


# ------------------------------------------------- bench detail fields (sat)
def test_bench_comm_fields():
    from scripts.bench_util import comm_fields
    assert comm_fields() == {}
    rep = costmodel.CostReport(
        name="train/dp", flops=0, hbm_bytes=0, collective_bytes=0,
        collectives={"all_reduce|data|float32": {
            "calls": 1, "payload_bytes": 8192, "wire_bytes": 8192,
            "axis_size": 2}})
    costmodel.register_report(rep)
    cs = get_commstat()
    cs.observe("all_reduce", 1 << 20, 0.001, axis="data")
    fields = comm_fields()
    assert fields["comm_wire_data_bytes"] == 8192
    assert fields["comm_all_reduce_gbps"] > 0


# --------------------------------------------- chaos acceptance (HTTP)
def _batch(seed=0):
    # leading gas=1; inner batch 8 divides the virtual 8-device mesh
    return {"input_ids": random_batch(seed=seed)["input_ids"][None]}


def test_comm_chaos_stall_acceptance(tmp_path, monkeypatch):
    """ISSUE 19 acceptance: an injected ``comm.collective`` stall in a
    multi-device CPU-mesh training run under DS_TRACE (a) raises
    ``anomaly/comm_*`` carrying the wedged step's ``train-step-N``
    corr, (b) answers ``/debug/comm`` over live HTTP *while the step is
    wedged* (the lock-free debug contract), and (c) lands ``comm.json``
    in the post-mortem bundle."""
    from deepspeed_tpu.resilience.postmortem import (reset_rate_limit,
                                                     write_postmortem)
    reset_rate_limit()
    trace_path = str(tmp_path / "comm_trace.json")
    monkeypatch.setenv("DS_TRACE", trace_path)
    monkeypatch.setenv("DS_COMMSTAT", "1")
    reset_tracer()
    tracer = configure_tracer()
    # stall invocation 18 == train step 19: the 18 warm steps feed the
    # comm_step_gate MAD baseline past min_samples=16 first
    eng, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(),
        config=base_config(
            telemetry={"metrics_port": 0},
            resilience={"faults": "comm.collective:stall=1.5@18"}))
    try:
        assert eng._commstat is not None
        for i in range(18):
            eng.train_batch(batch=_batch(seed=i))
        port = eng.metrics_server.port
        wedged = threading.Thread(
            target=lambda: eng.train_batch(batch=_batch(seed=18)))
        wedged.start()
        time.sleep(0.4)                 # step 19 is inside the stall now
        assert wedged.is_alive(), "stall did not wedge the step"
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/comm", timeout=10) as r:
            dbg = json.loads(r.read())
        assert dbg["armed"] is True
        assert "step_gate|step" in dbg["ops"]
        assert dbg["ops"]["step_gate|step"]["calls"] >= 18
        wedged.join(timeout=60)
        assert not wedged.is_alive()
        # the stall step's gate latency is the MAD outlier, attributed
        # to ITS step
        anomalies = eng.flightrec.events(kind_prefix="anomaly/comm_")
        assert any(e.get("corr") == "train-step-19" for e in anomalies)
        assert eng.telemetry_registry.get_counter(
            "anomaly/comm_step_gate") >= 1.0
        # the comm/* gauges ride the same /metrics exposition
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            prom = r.read().decode()
        assert "comm_op_latency_s_bucket{" in prom
        # post-mortem: the DEGRADED-style bundle carries comm.json
        pm_dir = str(tmp_path / "pm")
        bundle = write_postmortem(
            pm_dir, "degraded: comm.collective stall drill",
            step=19, registry=eng.telemetry_registry,
            flightrec=eng.flightrec)
        assert bundle is not None
        man = json.load(open(os.path.join(bundle, "manifest.json")))
        assert man["files"]["comm.json"] is True
        bundle_comm = json.load(open(os.path.join(bundle, "comm.json")))
        assert bundle_comm["armed"] is True
        assert bundle_comm["ops"]["step_gate|step"]["calls"] >= 19
    finally:
        if eng.metrics_server is not None:
            eng.metrics_server.stop()
    # validator-clean trace including the comm/* schema; the stalled
    # step's comm anomaly instant is on the timeline with its corr
    tracer.flush()
    assert validate(trace_path, require_corr=True) == []
    evs = load_events(trace_path)
    window_spans = [e for e in evs if e.get("name") == "comm/step_window"
                    and e.get("ph") == "B"]
    assert window_spans and all(e.get("cat") == "comm"
                                for e in window_spans)
    comm_anoms = [e for e in evs
                  if str(e.get("name", "")).startswith("anomaly/comm_")]
    assert any(e["args"].get("corr") == "train-step-19"
               for e in comm_anoms)
