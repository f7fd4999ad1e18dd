"""Memory observatory (ISSUE 14): tiered byte ledger, OOM forensics,
and offload I/O bandwidth telemetry.

Acceptance (tier-1):

- ledger owner attribution sums EXACTLY to the pool's pytree bytes on
  a live scheduler (tier totals parity vs BlockManager/costmodel
  ground truth, well inside the 2% contract);
- an injected ``kv.alloc`` deny produces a forensic ledger snapshot in
  BOTH the flight recorder and the post-mortem bundle's
  ``memory.json``, and ``/debug/memory`` answers over live HTTP while
  a thread holds the scheduler lock (the lock-free debug contract);
- a tmpfs-backed aio round trip lands in the ``swap/*`` bandwidth
  histograms with the ``DS_NVME_GBPS``-declared floor ratio;
- ``scripts/mem_report.py`` renders a bundle's ``memory.json`` as the
  where-did-the-bytes-go table (subprocess smoke).
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.runtime.config import ServingConfig, TelemetryConfig
from deepspeed_tpu.serving import ContinuousBatchingScheduler, SamplingParams
from deepspeed_tpu.telemetry import (FlightRecorder, IoStat, MemoryLedger,
                                     MetricsRegistry, get_iostat,
                                     get_memory_ledger, memory_enabled,
                                     memory_payload, reset_iostat,
                                     reset_memory_ledger, tree_bytes)
from deepspeed_tpu.telemetry.memory import (attribute_params,
                                            device_memory_stats,
                                            hbm_used_fraction)
from tests.util import tiny_gpt2

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _ledger_isolation():
    reset_memory_ledger()
    reset_iostat()
    yield
    reset_memory_ledger()
    reset_iostat()


@pytest.fixture(scope="module")
def served():
    m = tiny_gpt2()
    eng = deepspeed_tpu.init_inference(model=m, config={"dtype": "float32"})
    return m, eng


def _prompts(n, seed=0, lo=4, hi=10):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 128, (int(L),)).astype(np.int32)
            for L in rng.integers(lo, hi, n)]


# ------------------------------------------------------------ ledger unit
def test_owner_attribution_sums_to_tier_totals():
    led = MemoryLedger()
    led.set_bytes("device", "params", 1000, plain_bytes=1000)
    led.set_bytes("device", "kv_pool", 600)
    led.set_bytes("host", "optimizer", 4000)
    assert led.tier_bytes("device") == 1600
    assert led.tier_bytes("host") == 4000
    snap = led.snapshot()
    for tier, t in snap["tiers"].items():
        assert t["total_bytes"] == sum(
            r["bytes"] for r in t["owners"].values())
    # re-set is absolute, not cumulative (per-step tap semantics)
    led.set_bytes("device", "kv_pool", 200)
    assert led.tier_bytes("device") == 1200
    # add_bytes is relative, floors at zero, and survives a hammering
    # from multiple threads without losing increments (atomic RMW)
    led.add_bytes("device", "kv_pool", -50)
    assert led.owner_bytes("device", "kv_pool") == 150
    led.add_bytes("device", "kv_pool", -1000)
    assert led.owner_bytes("device", "kv_pool") == 0
    ts = [threading.Thread(
        target=lambda: [led.add_bytes("device", "kv_pool", 1)
                        for _ in range(500)]) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert led.owner_bytes("device", "kv_pool") == 2000
    # detail rides into the snapshot
    assert snap["tiers"]["device"]["owners"]["params"]["detail"] == \
        {"plain_bytes": 1000}


def test_watermark_monotonicity():
    led = MemoryLedger()
    led.set_bytes("device", "kv_pool", 500)
    led.set_bytes("device", "kv_pool", 900)
    led.set_bytes("device", "kv_pool", 100)
    snap = led.snapshot()
    dev = snap["tiers"]["device"]
    assert dev["owners"]["kv_pool"]["bytes"] == 100
    assert dev["owners"]["kv_pool"]["watermark_bytes"] == 900
    assert dev["watermark_bytes"] == 900
    # a second owner peaks the TIER above any single owner's peak
    led.set_bytes("device", "params", 300)
    led.set_bytes("device", "params", 0)
    assert led.snapshot()["tiers"]["device"]["watermark_bytes"] == 900
    led.set_bytes("device", "kv_pool", 900)
    led.set_bytes("device", "params", 300)
    assert led.snapshot()["tiers"]["device"]["watermark_bytes"] == 1200


def test_a_finalizer_may_write_the_ledger_inside_a_write():
    """``SwapEngine.__del__`` closes and accounts, and the cyclic
    collector runs it wherever an allocation tips it over — on this
    thread inside ``set_bytes``, where a plain lock deadlocked (the
    whole suite hung in tests/test_offload.py, PR 64)."""
    led = MemoryLedger()

    class Engine:
        def __del__(self):
            led.set_bytes("nvme", "gone", 0)

    store = led._store_locked

    def collecting(key, v, detail):
        if key[1] == "live":
            Engine()                    # dropped at once: finalized here
        return store(key, v, detail)

    led._store_locked = collecting
    writer = threading.Thread(
        target=lambda: led.set_bytes("host", "live", 5), daemon=True)
    writer.start()
    writer.join(timeout=10)
    assert not writer.is_alive(), "the ledger's write waits for itself"
    assert led._owners[("host", "live")] == 5
    assert led._owners[("nvme", "gone")] == 0


def test_alloc_failure_snapshot_ring_and_flightrec():
    led = MemoryLedger(max_failures=4)
    fr = FlightRecorder(64)
    led.set_bytes("device", "kv_pool", 777)
    for i in range(6):
        ev = led.record_alloc_failure("kv.alloc", flightrec=fr,
                                      needed_blocks=i)
        assert ev["tiers"]["device"] == 777
        assert ev["owners"]["device/kv_pool"] == 777
    # ring is bounded, counter is not
    assert led.alloc_failures == 6
    assert len(led.failures()) == 4
    assert [e["detail"]["needed_blocks"] for e in led.failures()] == \
        [2, 3, 4, 5]
    kinds = [e["kind"] for e in fr.events()]
    assert kinds.count("mem/alloc_failure") == 6
    ev = fr.events(kind_prefix="mem/")[0]
    assert ev["site"] == "kv.alloc" and ev["tiers"]["device"] == 777


def test_publish_gauges_and_counter():
    led = MemoryLedger()
    reg = MetricsRegistry()
    led.set_bytes("device", "params", 1234)
    led.set_bytes("nvme", "swap:opt", 99)
    led.record_alloc_failure("kv.alloc", flightrec=FlightRecorder(8))
    led.publish(reg)
    assert reg.get_gauge("mem/owner_bytes", tier="device",
                         owner="params") == 1234
    assert reg.get_gauge("mem/tier_bytes", tier="nvme") == 99
    assert reg.get_counter("mem/alloc_failures") == 1
    prom = reg.render_prometheus()
    assert 'mem_owner_bytes{owner="params",tier="device"} 1234' in prom
    assert "# TYPE mem_tier_bytes gauge" in prom


def test_memory_enabled_resolution(monkeypatch):
    monkeypatch.delenv("DS_MEM_LEDGER", raising=False)
    assert memory_enabled() is True
    assert memory_enabled(False) is False
    assert memory_enabled(True) is True
    monkeypatch.setenv("DS_MEM_LEDGER", "0")
    assert memory_enabled(True) is False
    monkeypatch.setenv("DS_MEM_LEDGER", "1")
    assert memory_enabled(False) is True
    # config key exists and round-trips
    assert TelemetryConfig().memory is True
    assert TelemetryConfig(memory=False).memory is False


def test_device_stats_graceful_on_cpu():
    # the CPU backend has no memory_stats: the probe degrades to {} and
    # every fraction-dependent output is None — no fictitious limits
    stats = device_memory_stats()
    assert isinstance(stats, dict)
    if not stats.get("bytes_limit"):
        assert hbm_used_fraction(stats) is None
    assert hbm_used_fraction({"bytes_in_use": 50, "bytes_limit": 200}) \
        == 0.25


def test_attribute_params_matches_costmodel(served):
    from deepspeed_tpu.telemetry.costmodel import param_stream_bytes
    _, eng = served
    led = MemoryLedger()
    stream = attribute_params(led, eng.params)
    want = (stream["dense_int8_bytes"] + stream["expert_int8_bytes"]
            + stream["plain_bytes"])
    assert want == param_stream_bytes(eng.params)["weights_floor_bytes"]
    assert led.owner_bytes("device", "params") == want
    detail = led.snapshot()["tiers"]["device"]["owners"]["params"]["detail"]
    assert detail["plain_bytes"] == stream["plain_bytes"]


# --------------------------------------------------------------- iostat
def test_iostat_observe_and_floor(monkeypatch):
    reg = MetricsRegistry()
    io = IoStat(registry=reg)
    monkeypatch.delenv("DS_NVME_GBPS", raising=False)
    io.observe("read", 1 << 20, 0.001)          # ~1.05 GB/s
    io.observe("write", 1 << 20, 0.004)
    assert reg.get_counter("swap/in_bytes") == 1 << 20
    assert reg.get_counter("swap/out_bytes") == 1 << 20
    assert reg.get_counter("swap/ops", op="read") == 1
    assert reg.get_gauge("swap/achieved_gbps", op="read") == \
        pytest.approx(1.0486, abs=1e-3)
    # no declared floor -> no vs_floor gauge (no fictitious floors)
    assert reg.get_gauge("swap/achieved_vs_floor", op="read") is None
    assert "vs_floor" not in io.summary()["ops"]["read"]
    monkeypatch.setenv("DS_NVME_GBPS", "2.0")
    io.observe("read", 1 << 21, 0.001)
    assert reg.get_gauge("swap/achieved_vs_floor", op="read") == \
        pytest.approx(1.0486, abs=1e-3)
    s = io.summary()
    assert s["floor_gbps"] == 2.0
    assert s["ops"]["read"]["count"] == 2
    h = reg.histogram("swap/op_gbps", op="read", window="op")
    assert h.count == 2


def test_iostat_anomaly_feed_inverse_bandwidth():
    from deepspeed_tpu.telemetry import AnomalyMonitor
    reg = MetricsRegistry()
    mon = AnomalyMonitor(registry=reg, min_samples=8, threshold=5.0)
    io = IoStat(registry=reg, anomaly=mon)
    # steady ~1 GB/s reads, then a collapse to ~10 MB/s: the inverse
    # (ms-per-MB) spikes and the one-sided MAD detector flags it
    for _ in range(16):
        io.observe("read", 1 << 20, 0.001)
    assert reg.get_counter("anomaly/mem_swap_read") == 0
    io.observe("read", 1 << 20, 0.1)
    assert reg.get_counter("anomaly/mem_swap_read") == 1
    assert reg.get_counter("anomaly/mem_swap_write") == 0


def test_aio_roundtrip_lands_in_swap_histograms(tmp_path, monkeypatch):
    """ISSUE 14 acceptance: a tmpfs-backed aio round trip through the
    per-request queue-depth API shows up as per-op latency/bandwidth
    histogram samples, byte counters, and the declared-floor ratio."""
    monkeypatch.setenv("DS_NVME_GBPS", "1.0")
    reg = MetricsRegistry()
    io = get_iostat().attach(registry=reg)
    from deepspeed_tpu.ops.aio import AsyncIOHandle
    h = AsyncIOHandle(thread_count=2)
    buf = np.arange(1 << 16, dtype=np.uint8)
    path = str(tmp_path / "t0.bin")
    h.wait_req(h.submit_pwrite(buf, path))
    out = np.empty_like(buf)
    h.wait_req(h.submit_pread(out, path))
    assert np.array_equal(buf, out)
    assert reg.get_counter("swap/out_bytes") == buf.nbytes
    assert reg.get_counter("swap/in_bytes") == buf.nbytes
    for op in ("read", "write"):
        hist = reg.histogram("swap/op_latency_s", op=op, window="op")
        assert hist.count == 1
        assert reg.get_gauge("swap/achieved_vs_floor", op=op) is not None
    # the batched path reports one drain-window bandwidth sample
    assert h.async_pwrite(buf, str(tmp_path / "t1.bin")) == 0
    assert h.wait() == 0
    drain = reg.histogram("swap/op_gbps", op="write", window="drain")
    assert drain.count == 1
    assert io.summary()["ops"]["write"]["count"] == 2


def test_aio_duration_is_completion_not_reap_time(tmp_path):
    """Review regression: per-request windows use the BACKEND's
    submit→completion duration.  A fire-and-forget write reaped 0.25 s
    later must NOT report its bandwidth collapsed by the caller's
    delay (the old submit→wait window did exactly that)."""
    reg = MetricsRegistry()
    get_iostat().attach(registry=reg)
    from deepspeed_tpu.ops.aio import AsyncIOHandle
    h = AsyncIOHandle(thread_count=1)
    buf = np.arange(1 << 20, dtype=np.uint8)
    rid = h.submit_pwrite(buf, str(tmp_path / "slow_reap.bin"))
    time.sleep(0.25)                      # the "optimizer step"
    assert h.wait_req(rid) == 0
    hist = reg.histogram("swap/op_latency_s", op="write", window="op")
    assert hist.count == 1
    # the observed latency is the I/O itself, not I/O + 0.25 s reap lag
    assert hist.sum < 0.2, hist.sum


def test_drain_windows_do_not_drive_gauges_or_anomaly(tmp_path):
    from deepspeed_tpu.telemetry import AnomalyMonitor
    reg = MetricsRegistry()
    mon = AnomalyMonitor(registry=reg, min_samples=4, threshold=5.0)
    io = IoStat(registry=reg, anomaly=mon)
    for _ in range(8):
        io.observe("read", 1 << 20, 0.001)
    gauge = reg.get_gauge("swap/achieved_gbps", op="read")
    # a glacial DRAIN window (batched wait behind a compute step) must
    # not move the achieved gauge nor trip the collapse detector
    io.observe("read", 1 << 20, 5.0, window="drain")
    assert reg.get_gauge("swap/achieved_gbps", op="read") == gauge
    assert reg.get_counter("anomaly/mem_swap_read") == 0
    # but its bytes still count, in the drain-labeled histogram
    assert reg.get_counter("swap/in_bytes") == 9 * (1 << 20)
    assert reg.histogram("swap/op_gbps", op="read",
                         window="drain").count == 1
    # and the mean excludes the drain window's misleading seconds
    assert io.summary()["ops"]["read"]["mean_gbps"] == \
        pytest.approx(1.0486, abs=1e-3)


def test_memory_config_default_reaches_configless_taps(tmp_path,
                                                      monkeypatch):
    """Review regression: an engine configured with telemetry.memory:
    false installs the process default, so the swapper (which has no
    telemetry config of its own) skips nvme accounting too."""
    from deepspeed_tpu.runtime.swap_tensor import AsyncTensorSwapper
    from deepspeed_tpu.telemetry.memory import set_memory_config_default
    monkeypatch.delenv("DS_MEM_LEDGER", raising=False)
    set_memory_config_default(False)
    try:
        assert memory_enabled() is False
        sw = AsyncTensorSwapper(str(tmp_path / "off"))
        sw.swap_out("t0", np.arange(64, dtype=np.float32))
        sw.drain()
        assert get_memory_ledger().tier_bytes("nvme") == 0
        # the env override still wins over the process default
        monkeypatch.setenv("DS_MEM_LEDGER", "1")
        assert memory_enabled() is True
    finally:
        set_memory_config_default(None)


def test_memory_payload_without_iostat():
    """/debug/memory answers from the ledger alone when no IoStat was
    ever armed (peek, never create/install)."""
    get_memory_ledger().set_bytes("device", "params", 77)
    payload = memory_payload()
    assert payload["swap"] == {"ops": {}}
    assert payload["tiers"]["device"]["owners"]["params"]["bytes"] == 77


def test_grow_exhaustion_forensics_precede_eviction(served):
    """Review regression: the self-eviction forensic snapshot is taken
    BEFORE the grower's blocks are returned — the record must show who
    held the bytes at the moment of failure, not post-eviction state.
    With max_fused_steps=1 and one request, kv.alloc invocation 1 is
    the first decode-write growth (invocation 0 is the admission)."""
    from deepspeed_tpu.resilience.faults import FaultInjector
    m, eng = served
    fr = FlightRecorder(256)
    cfg = ServingConfig(block_size=4, num_blocks=16, max_num_seqs=1,
                        max_fused_steps=1)
    s = ContinuousBatchingScheduler(
        m, eng.params, cfg, registry=MetricsRegistry(), flightrec=fr,
        injector=FaultInjector("kv.alloc:deny@1"))
    s.submit(np.arange(1, 8, dtype=np.int32),
             SamplingParams(max_new_tokens=6))
    s.run_until_idle()
    evs = fr.events(kind_prefix="mem/")
    assert evs, "grow self-eviction never recorded forensics"
    led = get_memory_ledger()
    fail = led.failures()[0]
    assert fail["detail"]["phase"] == "grow"
    # pre-eviction: the grower's own blocks still show as allocated
    assert fail["owners"]["device/kv_pool"] > 0


def test_swapper_accounts_nvme_tier(tmp_path):
    from deepspeed_tpu.runtime.swap_tensor import AsyncTensorSwapper
    led = get_memory_ledger()
    sw = AsyncTensorSwapper(str(tmp_path / "swap"))
    a = np.arange(1 << 14, dtype=np.float32)
    b = np.arange(1 << 12, dtype=np.float32)
    sw.swap_out("t0", a)
    sw.swap_out("t1", b)
    sw.drain()
    assert led.tier_bytes("nvme") == a.nbytes + b.nbytes
    got = sw.swap_in("t0")
    assert np.array_equal(a, got)
    sw.drain()
    # re-writing the same tensor does not double-count
    sw.swap_out("t0", a)
    sw.drain()
    assert led.tier_bytes("nvme") == a.nbytes + b.nbytes
    owners = led.snapshot()["tiers"]["nvme"]["owners"]
    # keyed by the FULL normalized dir path: two swappers over
    # distinct dirs sharing a basename must not overwrite each other
    key = "swap:" + os.path.normpath(str(tmp_path / "swap"))
    assert owners[key]["detail"]["tensors"] == 2
    sw2 = AsyncTensorSwapper(str(tmp_path / "other" / "swap"))
    sw2.swap_out("t0", b)
    sw2.drain()
    assert led.tier_bytes("nvme") == a.nbytes + 2 * b.nbytes


# ------------------------------------------------- scheduler acceptance
def test_scheduler_pool_parity_and_gauges(served):
    """Acceptance: /debug/memory and the mem/* gauges account
    KV-pool + prefix-cache + param bytes such that the totals match
    the costmodel/BlockManager ground truth within 2% (here: exactly —
    the four pool owners partition the pool pytree's bytes)."""
    from deepspeed_tpu.telemetry.costmodel import param_stream_bytes
    m, eng = served
    reg = MetricsRegistry()
    cfg = ServingConfig(block_size=8, num_blocks=32, max_num_seqs=2,
                        prefix_cache={"enabled": True})
    s = ContinuousBatchingScheduler(m, eng.params, cfg, registry=reg)
    for p in _prompts(3, seed=1):
        s.submit(p, SamplingParams(max_new_tokens=4))
    s.step()                      # mid-flight: live tables + free blocks
    led = get_memory_ledger()
    pool_bytes = tree_bytes(s.pool)
    bm = s.block_mgr

    def pool_owner_sum():
        return sum(led.owner_bytes("device", o) for o in
                   ("kv_pool", "prefix_cache", "kv_free", "kv_reserved"))

    assert pool_owner_sum() == pytest.approx(pool_bytes, rel=0.02)
    assert led.owner_bytes("device", "kv_pool") == pytest.approx(
        bm.num_allocated_blocks * pool_bytes / cfg.num_blocks, rel=1e-9)
    s.run_until_idle()            # retire: blocks move into the cache
    assert pool_owner_sum() == pytest.approx(pool_bytes, rel=0.02)
    assert bm.num_cached_blocks > 0
    assert led.owner_bytes("device", "prefix_cache") == pytest.approx(
        bm.num_cached_blocks * pool_bytes / cfg.num_blocks, rel=1e-9)
    # params parity vs the costmodel walk
    stream = param_stream_bytes(eng.params)
    assert led.owner_bytes("device", "params") == pytest.approx(
        stream["weights_floor_bytes"], rel=0.02)
    # gauges are on the scheduler's /metrics exposition
    prom = s.render_metrics()
    assert "mem_owner_bytes{" in prom
    assert "mem_tier_bytes{" in prom
    # and /debug/memory reports the same totals
    payload = memory_payload()
    dev = payload["tiers"]["device"]
    assert dev["total_bytes"] == pytest.approx(
        pool_bytes + stream["weights_floor_bytes"], rel=0.02)


def test_scheduler_memory_off(served):
    m, eng = served
    cfg = ServingConfig(block_size=8, num_blocks=16, max_num_seqs=2)
    os.environ["DS_MEM_LEDGER"] = "0"
    try:
        s = ContinuousBatchingScheduler(m, eng.params, cfg,
                                        registry=MetricsRegistry())
        assert s._mem_on is False
        s.submit(_prompts(1)[0], SamplingParams(max_new_tokens=2))
        s.run_until_idle()
        assert get_memory_ledger().tier_bytes("device") == 0
    finally:
        del os.environ["DS_MEM_LEDGER"]


def test_hbm_fraction_gauge_with_fake_accelerator(served):
    """A backend that DOES report memory stats drives the
    mem/hbm_used_fraction gauge (the anomaly/mem_hbm leak feed)."""
    from deepspeed_tpu.accelerator import (get_accelerator,
                                           set_accelerator)

    class _FakeAcc:
        def memory_stats(self, device_index: int = 0):
            return {"bytes_in_use": 750, "bytes_limit": 1000}

    m, eng = served
    real = get_accelerator()
    set_accelerator(_FakeAcc())
    try:
        reg = MetricsRegistry()
        cfg = ServingConfig(block_size=8, num_blocks=16, max_num_seqs=2)
        s = ContinuousBatchingScheduler(m, eng.params, cfg, registry=reg)
        s.submit(_prompts(1)[0], SamplingParams(max_new_tokens=2))
        s.run_until_idle()
        assert reg.get_gauge("mem/hbm_used_fraction") == 0.75
        assert reg.get_gauge("mem/hbm_used_bytes") == 750
        payload = memory_payload()
        assert payload["device_stats"]["used_fraction"] == 0.75
    finally:
        set_accelerator(real)


# --------------------------------------------------- chaos acceptance
def test_chaos_alloc_deny_forensics_and_debug_memory(tmp_path, served):
    """ISSUE 14 acceptance: an injected ``kv.alloc`` deny snapshots the
    ledger into the flight recorder AND the post-mortem bundle's
    ``memory.json``, and ``/debug/memory`` answers over live HTTP while
    another thread holds the scheduler lock (lock-free contract)."""
    from deepspeed_tpu.resilience.faults import FaultInjector
    from deepspeed_tpu.resilience.postmortem import (reset_rate_limit,
                                                     write_postmortem)
    from deepspeed_tpu.serving.server import make_server
    m, eng = served
    reset_rate_limit()
    fr = FlightRecorder(1024)
    reg = MetricsRegistry()
    cfg = ServingConfig(block_size=8, num_blocks=32, max_num_seqs=2)
    sched = ContinuousBatchingScheduler(
        m, eng.params, cfg, registry=reg,
        injector=FaultInjector("kv.alloc:deny@0"), flightrec=fr)
    sched.submit(_prompts(1, seed=3)[0], SamplingParams(max_new_tokens=3))
    sched.step()                      # the denied admission
    sched.run_until_idle()            # then the request still finishes
    evs = fr.events(kind_prefix="mem/")
    assert evs and evs[0]["kind"] == "mem/alloc_failure"
    assert evs[0]["site"] == "kv.alloc"
    assert evs[0]["tiers"]["device"] > 0
    led = get_memory_ledger()
    assert led.alloc_failures >= 1
    assert led.failures()[0]["site"] == "kv.alloc"
    assert reg.get_counter("mem/alloc_failures") >= 1

    # DEGRADED-style bundle: memory.json with the forensic ring
    bundle = write_postmortem(str(tmp_path), "degraded: oom test",
                              scheduler=sched, flightrec=fr,
                              registry=reg, min_interval_s=0)
    assert bundle is not None
    mem = json.load(open(os.path.join(bundle, "memory.json")))
    assert mem["alloc_failures"] >= 1
    assert mem["failures"][0]["site"] == "kv.alloc"
    assert "kv_pool" in mem["tiers"]["device"]["owners"]
    man = json.load(open(os.path.join(bundle, "manifest.json")))
    assert man["files"]["memory.json"] is True

    # /debug/memory over live HTTP while the scheduler lock is HELD
    httpd, loop = make_server(sched, port=0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        with sched._lock:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{httpd.server_port}/debug/memory",
                    timeout=10) as r:
                live = json.loads(r.read())
        assert live["alloc_failures"] >= 1
        assert live["tiers"]["device"]["total_bytes"] > 0
        assert "swap" in live
    finally:
        loop.shutdown()
        httpd.shutdown()
        httpd.server_close()


def test_metrics_server_debug_memory_route():
    """The training-side MetricsServer exposes the same /debug/memory
    surface as ds_serve (one payload function, two front doors)."""
    from deepspeed_tpu.telemetry import MetricsServer
    led = get_memory_ledger()
    led.set_bytes("device", "params", 4321)
    srv = MetricsServer(MetricsRegistry(), port=0).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/memory?tier=device",
                timeout=10) as r:
            payload = json.loads(r.read())
        assert payload["tiers"]["device"]["owners"]["params"]["bytes"] \
            == 4321
        # the ?tier= filter drops other tiers
        led.set_bytes("host", "optimizer", 1)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/memory?tier=host",
                timeout=10) as r:
            filtered = json.loads(r.read())
        assert list(filtered["tiers"]) == ["host"]
    finally:
        srv.stop()


def test_postmortem_skips_memory_json_when_ledger_idle(tmp_path):
    from deepspeed_tpu.resilience.postmortem import (reset_rate_limit,
                                                     write_postmortem)
    reset_rate_limit()
    bundle = write_postmortem(str(tmp_path), "idle", min_interval_s=0)
    assert bundle is not None
    assert not os.path.exists(os.path.join(bundle, "memory.json"))


# ----------------------------------------------------------- satellites
def test_autotuner_memory_stats_via_accelerator():
    """ISSUE 14 satellite: the autotuner's HBM ceiling probe rides the
    accelerator abstraction (CPU-degraded probes stay consistent), not
    a raw jax.devices()[0].memory_stats() poke."""
    from deepspeed_tpu.accelerator import (get_accelerator,
                                           set_accelerator)
    from deepspeed_tpu.autotuning.autotuner import Autotuner

    class _FakeAcc:
        def memory_stats(self, device_index: int = 0):
            return {"bytes_in_use": 0, "bytes_limit": 123456789}

    tuner = Autotuner(base_config={}, model_factory=lambda **kw:
                      tiny_gpt2())
    real = get_accelerator()
    set_accelerator(_FakeAcc())
    try:
        cm = tuner._build_cost_model()
        assert cm.hbm == 123456789
    finally:
        set_accelerator(real)
    # CPU-degraded: no stats -> unbounded cost model, no crash
    cm = tuner._build_cost_model()
    if not device_memory_stats().get("bytes_limit"):
        assert cm.hbm is None


def test_mem_report_subprocess_smoke(tmp_path):
    """Tier-1 satellite: mem_report renders a memory.json bundle
    artifact; unreadable/contentless sources exit 2."""
    led = MemoryLedger()
    led.set_bytes("device", "kv_pool", 4096, blocks=16)
    led.set_bytes("device", "params", 1 << 20)
    led.record_alloc_failure("kv.alloc", flightrec=FlightRecorder(8),
                             needed_blocks=2)
    payload = led.snapshot()
    payload["swap"] = IoStat(registry=MetricsRegistry()).summary()
    path = tmp_path / "memory.json"
    path.write_text(json.dumps(payload))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "mem_report.py"),
         str(path)], capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "tier device" in out.stdout
    assert "kv_pool" in out.stdout and "params" in out.stdout
    assert "allocation failures: 1" in out.stdout
    bad = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "mem_report.py"),
         str(tmp_path / "nope.json")],
        capture_output=True, text=True, timeout=120)
    assert bad.returncode == 2
    notpayload = tmp_path / "other.json"
    notpayload.write_text("{}")
    bad2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "mem_report.py"),
         str(notpayload)], capture_output=True, text=True, timeout=120)
    assert bad2.returncode == 2


def test_bench_mem_peak_fields(served):
    """serve_bench/decode_profile/ckpt_bench records carry mem_peak_*
    watermarks (via the shared bench_util helper) once a scheduler has
    driven the ledger."""
    sys.path.insert(0, REPO)
    from scripts.bench_util import mem_peak_fields
    m, eng = served
    cfg = ServingConfig(block_size=8, num_blocks=32, max_num_seqs=2,
                        prefix_cache={"enabled": True})
    s = ContinuousBatchingScheduler(m, eng.params, cfg,
                                    registry=MetricsRegistry())
    for p in _prompts(2, seed=5):
        s.submit(p, SamplingParams(max_new_tokens=3))
    s.run_until_idle()
    fields = mem_peak_fields()
    assert fields["mem_peak_device_bytes"] > 0
    assert fields["mem_peak_kv_pool_bytes"] > 0
    assert "mem_peak_prefix_cache_bytes" in fields
    # the serve_bench emit() funnel merges them into every record's
    # detail — the half bench_compare lifts into comparable metrics
    from scripts.serve_bench import emit
    rec = emit({"metric": "smoke", "value": 1.0})
    assert rec["detail"]["mem_peak_device_bytes"] == \
        fields["mem_peak_device_bytes"]


def test_host_offload_optimizer_tier_accounting(tmp_path):
    """The ZeRO host/NVMe offload tier accounts its fp32 state: DRAM
    copies via host_dram_bytes, swapped moments via the swapper's
    nvme-tier ledger rows, and the swap traffic via swap/* counters."""
    import jax.numpy as jnp
    from deepspeed_tpu.runtime.swap_tensor import AsyncTensorSwapper
    from deepspeed_tpu.runtime.zero.offload import HostOffloadOptimizer
    reg = MetricsRegistry()
    get_iostat().attach(registry=reg)
    params = {"w": jnp.ones((64, 8), jnp.float32),
              "b": jnp.zeros((8,), jnp.float32)}
    sw = AsyncTensorSwapper(str(tmp_path / "nvme"))
    opt = HostOffloadOptimizer(params, "adamw", {"lr": 1e-3},
                               nvme_swapper=sw)
    numel = 64 * 8 + 8
    # masters stay in DRAM (1 copy), both moments swap to NVMe
    assert opt.host_dram_bytes == 4 * numel
    assert opt.nvme_bytes == 2 * 4 * numel
    led = get_memory_ledger()
    assert led.tier_bytes("nvme") == opt.nvme_bytes
    grads = {"w": jnp.full((64, 8), 0.1, jnp.float32),
             "b": jnp.full((8,), 0.1, jnp.float32)}
    opt.step(grads, 1, jnp.float32)
    # the step swapped both moments in and back out
    assert reg.get_counter("swap/in_bytes") >= opt.nvme_bytes
    assert reg.get_counter("swap/out_bytes") >= opt.nvme_bytes


def test_engine_publishes_memory_gauges():
    import jax
    from deepspeed_tpu.models.gpt2 import gpt2_model
    model = gpt2_model("custom", vocab_size=128, num_layers=2,
                       num_heads=2, d_model=16, max_seq_len=32)
    mbs = max(2, len(jax.devices()))
    engine, *_ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": mbs,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "steps_per_print": 0})
    rng = np.random.default_rng(0)
    engine.train_batch(batch={"input_ids": rng.integers(
        0, 128, size=(1, mbs, 16), dtype=np.int32)})
    led = get_memory_ledger()
    assert led.owner_bytes("device", "params") > 0
    # Adam m+v (fp32) alongside the fp32 params: ~2x the param bytes
    assert led.owner_bytes("device", "optimizer") >= \
        2 * led.owner_bytes("device", "params") * 0.9
    snap = engine.telemetry_registry.snapshot()
    assert any(k.startswith("mem/owner_bytes") for k in snap)
    assert any(k.startswith("mem/tier_watermark_bytes") for k in snap)
