"""Chaos tests for the resilience subsystem (ISSUE 3).

The load-bearing contracts:
- an injected crash or write-failure at ANY point inside
  ``save_checkpoint`` (sync and async engines) never leaves ``latest``
  resolving to a tag that fails manifest verification —
  ``load_checkpoint`` always restores the newest VALID tag (the seeded
  fault matrix below);
- a torn/empty ``latest`` file no longer poisons ``load_checkpoint``;
- ``keep_last_k`` retention never deletes the fallback;
- SIGTERM drains training through an emergency checkpoint + the distinct
  exit code the elastic agent resumes from;
- serving: consecutive step failures and scheduler stalls flip health to
  DEGRADED (metrics surfaced) instead of hanging forever; a drain
  finishes in-flight requests while new ones get 503.

The slow group runs the full kill → elastic-agent → resume → identical
final loss pipeline in subprocesses.
"""
import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time
import urllib.request
import urllib.error

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.resilience import (CheckpointCorruptError, FaultInjected,
                                      FaultInjector, FaultSpec, HealthMonitor,
                                      HealthState, NULL_INJECTOR,
                                      PREEMPTED_EXIT_CODE, PreemptionHandler,
                                      RetryDeadlineExceeded, SchedulerWatchdog,
                                      parse_spec, resolve_injector,
                                      retry_call, run_resilient_training,
                                      verify_tag)
from deepspeed_tpu.resilience import ckpt as rckpt
from deepspeed_tpu.runtime.config import ServingConfig
from deepspeed_tpu.serving import ContinuousBatchingScheduler, RequestState, \
    SamplingParams
from deepspeed_tpu.serving.scheduler import ServingMetrics
from tests.util import tiny_gpt2, base_config, random_batches


# ------------------------------------------------------------ fault specs
def test_fault_spec_grammar():
    s = FaultSpec.parse("ckpt.save:raise@1")
    assert (s.site, s.action, s.start, s.repeat) == \
        ("ckpt.save", "raise", 1, False)
    s = FaultSpec.parse("train.step:kill=9@5")
    assert s.action == "kill" and s.param == 9
    s = FaultSpec.parse("serve.step:stall=0.25@3+")
    assert s.param == 0.25 and s.start == 3 and s.repeat
    s = FaultSpec.parse("kv.alloc:deny@*")
    assert s.repeat and s.fires_at(0) and s.fires_at(100)
    s = FaultSpec.parse("train.step:raise@p0.5s42")
    fires = [s.fires_at(i) for i in range(200)]
    assert any(fires) and not all(fires)
    # seeded => deterministic
    assert fires == [FaultSpec.parse("train.step:raise@p0.5s42").fires_at(i)
                     for i in range(200)]
    assert len(parse_spec("a.b:raise@0; c.d:deny@*  e.f:stall=1@2+")) == 3
    assert parse_spec(None) == [] and parse_spec("") == []
    for bad in ("nocolon@1", "a.b:explode@1", "a.b:raise", "a.b:raise@x"):
        with pytest.raises(ValueError):
            FaultSpec.parse(bad)


def test_fault_injector_actions():
    inj = FaultInjector("s.a:raise@1; s.b:deny@0; s.c:truncate=3@0")
    inj.check("s.a")                      # invocation 0: no fire
    with pytest.raises(FaultInjected):
        inj.check("s.a")                  # invocation 1: fires
    inj.check("s.a")                      # one-shot: done firing
    assert inj.deny("s.b") and not inj.deny("s.b")
    assert inj.truncate_bytes("s.c", 10) == 3
    assert inj.truncate_bytes("s.c", 10) is None
    assert inj.fired == {"s.a": 1, "s.b": 1, "s.c": 1}
    assert not NULL_INJECTOR
    NULL_INJECTOR.check("anything")       # no-op, no state explosion


def test_corrupt_action_grammar_and_injector():
    """ISSUE 18: the corrupt action — spec grammar, default/clamped
    byte counts, one-shot firing, and NULL_INJECTOR passthrough."""
    s = FaultSpec.parse("kv.swap:corrupt=16@2")
    assert (s.site, s.action, s.param, s.start) == \
        ("kv.swap", "corrupt", 16, 2)
    inj = FaultInjector("s.k:corrupt@0; s.m:corrupt=4@*")
    assert inj.corrupt_bytes("s.k", 100) == 8      # default: 8 bytes
    assert inj.corrupt_bytes("s.k", 100) is None   # one-shot: done
    assert inj.corrupt_bytes("s.m", 2) == 2        # clamped to payload
    assert inj.corrupt_bytes("s.m", 0) is None     # empty payload
    assert inj.fired == {"s.k": 1, "s.m": 2}
    assert NULL_INJECTOR.corrupt_bytes("s.m", 100) is None
    # raise specs still raise through the corrupt hook
    with pytest.raises(FaultInjected):
        FaultInjector("s.r:raise@0").corrupt_bytes("s.r", 10)


def test_corrupt_seeded_probabilistic():
    """pPsS mode is deterministic per (seed, invocation) for corrupt
    like every other action — a corruption storm is replayable."""
    inj = FaultInjector("s.p:corrupt=2@p0.5s7")
    hits = [inj.corrupt_bytes("s.p", 64) for _ in range(200)]
    fired = [h for h in hits if h]
    assert fired and len(fired) < 200 and all(h == 2 for h in fired)
    inj2 = FaultInjector("s.p:corrupt=2@p0.5s7")
    assert hits == [inj2.corrupt_bytes("s.p", 64) for _ in range(200)]


def test_flip_bytes_size_preserving_involution():
    """The flip itself: size-preserving by construction, exact flip
    count, and an involution (two applications restore the payload)."""
    from deepspeed_tpu.resilience.faults import flip_bytes
    rng = np.random.default_rng(0)
    buf = rng.integers(0, 256, size=257, dtype=np.uint8)
    orig = buf.copy()
    assert flip_bytes(buf, 16) == 16
    assert buf.shape == orig.shape                  # size-preserving
    assert int(np.count_nonzero(buf != orig)) == 16
    flip_bytes(buf, 16)
    assert np.array_equal(buf, orig)                # involution
    assert flip_bytes(buf[:0], 4) == 0              # empty payload
    small = orig[:3].copy()
    assert flip_bytes(small, 100) == 3              # clamped to len


def test_resolve_injector_merges_env(monkeypatch):
    monkeypatch.setenv("DS_FAULTS", "env.site:deny@0")
    inj = resolve_injector("cfg.site:raise@0")
    assert {s.site for s in inj.specs} == {"cfg.site", "env.site"}
    monkeypatch.delenv("DS_FAULTS")
    assert not resolve_injector("")       # nothing armed -> falsy no-op


def test_retry_call_backoff_and_deadline():
    calls, sleeps = [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert retry_call(flaky, attempts=4, base_delay_s=0.01,
                      _sleep=sleeps.append) == "ok"
    assert len(calls) == 3 and len(sleeps) == 2

    def always():
        raise OSError("down")

    with pytest.raises(OSError):
        retry_call(always, attempts=3, base_delay_s=0.0, _sleep=lambda s: 0)

    with pytest.raises(RetryDeadlineExceeded):
        retry_call(always, attempts=100, base_delay_s=0.0, deadline_s=0.0,
                   _sleep=lambda s: 0)

    def type_err():
        raise TypeError("bug, not weather")

    calls.clear()
    with pytest.raises(TypeError):       # non-retryable: no second call
        retry_call(type_err, attempts=5, _sleep=calls.append)
    assert calls == []


def test_verify_restored_catches_corruption():
    state = {"a": np.arange(8, dtype=np.float32),
             "b": np.ones((2, 3), np.int32)}
    manifest = {"leaves": rckpt.leaf_summary(state, checksums=True)}
    assert rckpt.verify_restored(state, manifest) == []
    state["a"] = state["a"].copy()
    state["a"][3] += 1.0
    assert any("checksum" in m
               for m in rckpt.verify_restored(state, manifest))


# ------------------------------------------------ checkpoint crash-safety
def _make_engine(overrides=None):
    cfg = base_config(**(overrides or {}))
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_gpt2(), config=cfg)
    return engine


def _train(engine, steps=1, seed=0):
    for i in range(steps):
        engine.train_batch(data_iter=iter(random_batches(1, seed=seed + i)))


def _qkv(engine):
    return np.asarray(engine.state["params"]["blocks"]["qkv_w"]).copy()


def test_torn_latest_falls_back(devices8, tmp_path):
    """ISSUE 3 satellite regression: a torn/empty `latest` file no longer
    poisons load_checkpoint — it resolves the newest valid tag anyway."""
    engine = _make_engine()
    _train(engine, 1, seed=3)
    engine.save_checkpoint(str(tmp_path))
    _train(engine, 1, seed=4)
    engine.save_checkpoint(str(tmp_path))
    want = _qkv(engine)
    for torn in (b"", b"global_st"):     # empty and truncated pointers
        with open(tmp_path / "latest", "wb") as f:
            f.write(torn)
        loader = _make_engine()
        path, _ = loader.load_checkpoint(str(tmp_path))
        assert path is not None and loader.global_steps == 2
        np.testing.assert_array_equal(_qkv(loader), want)


def test_latest_pointer_written_atomically(devices8, tmp_path):
    """The publish goes through tmp + os.replace: no window where the
    pointer file exists torn.  A truncate fault models the OLD writer."""
    engine = _make_engine()
    _train(engine, 1, seed=5)
    engine.save_checkpoint(str(tmp_path))
    assert (tmp_path / "latest").read_text() == "global_step1"
    assert not (tmp_path / "latest.tmp").exists()
    ok, reason = verify_tag(str(tmp_path / "global_step1"))
    assert ok, reason


# The seeded fault matrix (acceptance): (spec, second_save_survives).
# second_save_survives=True means the fault cannot prevent the new tag
# from publishing validly, so load must restore step 2; False means the
# new tag must NOT be restorable and load falls back to step 1.
FAULT_MATRIX = [
    ("ckpt.save:raise@0", False),
    ("ckpt.save:stall=0.01@0", True),
    ("ckpt.aux:raise@0", False),
    ("ckpt.manifest:raise@0", False),
    ("ckpt.manifest:truncate@0", False),
    ("ckpt.publish:raise@0", False),     # crash before the tag rename
    ("ckpt.latest:truncate@0", True),    # torn pointer, valid tag
    ("ckpt.latest:raise@0", True),       # pointer never written
]


@pytest.mark.parametrize("async_save", [False, True])
def test_fault_matrix_save_never_poisons_load(devices8, tmp_path,
                                              async_save):
    """Acceptance: injected crash/write-failure at any point during
    save_checkpoint never leaves `latest` resolving to an invalid tag —
    load_checkpoint always restores the newest valid tag."""
    overrides = {"checkpoint": {"async_save": async_save}}
    engine = _make_engine(overrides)
    loader = _make_engine(overrides)
    for i, (spec, second_survives) in enumerate(FAULT_MATRIX):
        if async_save and spec.startswith("ckpt.aux"):
            # no host-optimizer aux payload -> the async path never
            # starts an aux thread and the site is unreachable
            continue
        save_dir = tmp_path / f"case{i}"
        _train(engine, 1, seed=10 + i)
        engine.save_checkpoint(str(save_dir))
        engine.wait_pending_checkpoint()
        step1, snap1 = engine.global_steps, _qkv(engine)
        _train(engine, 1, seed=40 + i)
        step2, snap2 = engine.global_steps, _qkv(engine)
        engine.fault_injector = FaultInjector(spec)
        try:
            engine.save_checkpoint(str(save_dir))
            engine.wait_pending_checkpoint()
        except (FaultInjected, OSError, RetryDeadlineExceeded):
            pass
        finally:
            engine.fault_injector = NULL_INJECTOR
        path, _ = loader.load_checkpoint(str(save_dir))
        assert path is not None, f"{spec}: no tag restorable"
        ok, reason = verify_tag(path)
        assert ok, f"{spec}: restored tag failed verification: {reason}"
        want_step = step2 if second_survives else step1
        want_snap = snap2 if second_survives else snap1
        assert loader.global_steps == want_step, \
            f"{spec}: restored step {loader.global_steps} != {want_step}"
        np.testing.assert_array_equal(_qkv(loader), want_snap,
                                      err_msg=spec)


def test_raise_fault_during_save_leaves_only_staging(devices8, tmp_path):
    """A failed save leaves a .tmp staging dir at most — never a
    published tag, and `latest` still names the previous good one."""
    engine = _make_engine()
    _train(engine, 1, seed=6)
    engine.save_checkpoint(str(tmp_path))
    _train(engine, 1, seed=7)
    engine.fault_injector = FaultInjector("ckpt.save:raise@0")
    with pytest.raises(FaultInjected):
        engine.save_checkpoint(str(tmp_path))
    engine.fault_injector = NULL_INJECTOR
    assert rckpt.list_tags(str(tmp_path)) == ["global_step1"]
    assert rckpt.read_latest(str(tmp_path)) == "global_step1"


def test_same_tag_overwrite_crash_window(devices8, tmp_path):
    """Overwriting a fixed tag is crash-safe: a crash between "move old
    aside" and "move new in" leaves the old checkpoint under
    `<tag>.prev` — a normal, discoverable tag the fallback restores
    (a .tmp staging name would hide BOTH copies)."""
    engine = _make_engine()
    _train(engine, 1, seed=30)
    engine.save_checkpoint(str(tmp_path), tag="ckpt")
    snap1 = _qkv(engine)
    _train(engine, 1, seed=31)
    engine.fault_injector = FaultInjector("ckpt.publish:raise@0")
    with pytest.raises(FaultInjected):
        engine.save_checkpoint(str(tmp_path), tag="ckpt")
    engine.fault_injector = NULL_INJECTOR
    assert rckpt.list_tags(str(tmp_path)) == ["ckpt.prev"]
    loader = _make_engine()
    path, _ = loader.load_checkpoint(str(tmp_path))
    assert path.endswith("ckpt.prev") and loader.global_steps == 1
    np.testing.assert_array_equal(_qkv(loader), snap1)
    # a successful overwrite cleans the .prev staging up again
    engine.save_checkpoint(str(tmp_path), tag="ckpt")
    assert rckpt.list_tags(str(tmp_path)) == ["ckpt"]
    loader2 = _make_engine()
    path, _ = loader2.load_checkpoint(str(tmp_path))
    assert path.endswith("ckpt") and loader2.global_steps == 2


def test_keep_last_k_retention(devices8, tmp_path):
    engine = _make_engine({"resilience": {"keep_last_k": 2}})
    for i in range(4):
        _train(engine, 1, seed=20 + i)
        engine.save_checkpoint(str(tmp_path))
    tags = rckpt.list_tags(str(tmp_path))
    assert tags == ["global_step3", "global_step4"]
    assert rckpt.read_latest(str(tmp_path)) == "global_step4"
    loader = _make_engine()
    path, _ = loader.load_checkpoint(str(tmp_path))
    assert loader.global_steps == 4
    # retention must never delete the fallback: corrupt the newest tag's
    # manifest; the next resolve falls back to the OTHER retained tag
    manifest = tmp_path / "global_step4" / rckpt.MANIFEST_FILE
    manifest.write_text("{torn")
    loader2 = _make_engine()
    path, _ = loader2.load_checkpoint(str(tmp_path))
    assert path.endswith("global_step3") and loader2.global_steps == 3


def test_requested_tag_verification(devices8, tmp_path):
    engine = _make_engine()
    _train(engine, 1, seed=8)
    engine.save_checkpoint(str(tmp_path), tag="good")
    (tmp_path / "good" / rckpt.MANIFEST_FILE).write_text("{torn")
    loader = _make_engine()
    with pytest.raises(CheckpointCorruptError):
        loader.load_checkpoint(str(tmp_path), tag="good")


def test_train_step_fault_site(devices8):
    engine = _make_engine({"resilience": {"faults": "train.step:raise@1"}})
    _train(engine, 1, seed=9)             # invocation 0: clean
    with pytest.raises(FaultInjected):
        _train(engine, 1, seed=9)         # invocation 1: fires


def test_npz_engine_save_is_atomic(tmp_path, monkeypatch):
    from deepspeed_tpu.runtime.checkpoint_engine.engine import \
        NpzCheckpointEngine
    eng = NpzCheckpointEngine()
    state = {"w": np.arange(6, dtype=np.float32)}
    target = tmp_path / "flat.npz"

    real_savez = np.savez

    def torn_savez(path, **kw):
        with open(path, "wb") as f:       # half-written file, then death
            f.write(b"PK\x03\x04garbage")
        raise OSError("disk died mid-write")

    monkeypatch.setattr(np, "savez", torn_savez)
    with pytest.raises(OSError):
        eng.save(state, str(target))
    monkeypatch.setattr(np, "savez", real_savez)
    assert not target.exists()            # no torn file at the real name
    assert list(tmp_path.iterdir()) == []  # staging cleaned up
    eng.save(state, str(target))
    out = eng.load(str(target), template={"w": np.zeros(6, np.float32)})
    np.testing.assert_array_equal(out["w"], state["w"])


# ------------------------------------------------------------- preemption
def test_preemption_handler_latches_sigterm():
    handler = PreemptionHandler(signals=(signal.SIGTERM,))
    before = signal.getsignal(signal.SIGTERM)
    with handler:
        assert not handler.should_stop
        signal.raise_signal(signal.SIGTERM)
        assert handler.should_stop and handler.signum == signal.SIGTERM
    assert signal.getsignal(signal.SIGTERM) == before


def test_resilient_training_drains_and_resumes(devices8, tmp_path):
    """In-process acceptance: preemption mid-run → emergency checkpoint +
    distinct exit code; the resume path restores the drained step, the
    params, and the rng chain EXACTLY.

    (The resumed engine deliberately does no further training here: on
    this container's jaxlib, training on restored state under the warm
    persistent compile cache corrupts the glibc heap — the documented
    test_universal_checkpoint abort class.  The same-final-loss
    acceptance runs in the slow e2e tests, whose subprocess workers
    disable the persistent cache.)"""
    overrides = {"optimizer": {"type": "Adam", "params": {"lr": 1e-2}}}
    batches = [random_batches(1, seed=100 + s)[0] for s in range(6)]

    def batch_for(step):
        return {"input_ids": batches[step]["input_ids"][None]}

    # interrupted at step 3: the handler latch is set as if SIGTERM
    # arrived mid-step; the loop finishes the step then drains
    exit_codes = []
    handler = PreemptionHandler(signals=())
    eng = _make_engine(overrides)
    run_dir = tmp_path / "run"

    def on_step(step, loss):
        if step == 3:
            handler.requested.set()

    run_resilient_training(eng, batch_for, str(run_dir), num_steps=6,
                           handler=handler, on_step=on_step,
                           _exit=exit_codes.append)
    assert exit_codes == [PREEMPTED_EXIT_CODE]
    assert eng.global_steps == 3
    tags = rckpt.list_tags(str(run_dir))
    assert "emergency_step3" in tags
    ok, reason = verify_tag(str(run_dir / "emergency_step3"))
    assert ok, reason

    # resume exactly where the drain left off (what the elastic agent
    # does via DS_RESUME=latest): run_resilient_training with num_steps
    # == the drained step restores and immediately returns
    eng2 = _make_engine(overrides)
    run_resilient_training(eng2, batch_for, str(run_dir), num_steps=3,
                           resume="latest")
    assert eng2.global_steps == 3
    np.testing.assert_array_equal(_qkv(eng2), _qkv(eng))
    # the rng chain rides the metadata, so step 4 would draw the exact
    # key the uninterrupted run would have drawn
    np.testing.assert_array_equal(np.asarray(eng2._rng),
                                  np.asarray(eng._rng))


# ---------------------------------------------------------- elastic agent
def _run_agent_child(tmp_path, body, **agent_kw):
    from deepspeed_tpu.elasticity.elastic_agent import DSElasticAgent
    script = tmp_path / "child.py"
    script.write_text(textwrap.dedent(body))
    agent = DSElasticAgent([sys.executable, str(script), str(tmp_path)],
                           **agent_kw)
    return agent


def test_elastic_agent_backoff_sequence(tmp_path):
    """Delays grow exponentially from restart_delay_s, capped at
    backoff_max_s; jitter=0 makes the ladder exact."""
    agent = _run_agent_child(tmp_path, """
        import os, sys
        marker = os.path.join(sys.argv[1], "n")
        n = int(open(marker).read()) if os.path.exists(marker) else 0
        open(marker, "w").write(str(n + 1))
        sys.exit(0 if n >= 4 else 1)
    """, max_restarts=8, restart_delay_s=0.01, backoff_factor=2.0,
        backoff_max_s=0.05, backoff_jitter=0.0, monitor_interval_s=0.001)
    sleeps = []
    real_sleep = time.sleep
    agent._sleep = lambda s: (sleeps.append(s), real_sleep(min(s, 0.01)))
    result = agent.run()
    assert result.success and result.restarts == 4
    backoffs = [s for s in sleeps if s > 0.005]   # monitor polls filtered
    np.testing.assert_allclose(backoffs, [0.01, 0.02, 0.04, 0.05])
    assert [a.backoff_s for a in result.history] == \
        pytest.approx([0.01, 0.02, 0.04, 0.05, 0.0])


def test_elastic_agent_window_budget_exhausts_on_crash_loop(tmp_path):
    """Crash-looping inside the window burns the budget and fails — it
    can never succeed by simply outlasting a naive counter."""
    agent = _run_agent_child(tmp_path, """
        import sys
        sys.exit(3)
    """, max_restarts=2, restart_delay_s=0.01, backoff_jitter=0.0,
        restart_window_s=60.0, monitor_interval_s=0.01)
    result = agent.run()
    assert not result.success and result.restarts == 2
    assert result.return_code == 3 and len(result.history) == 3


def test_elastic_agent_window_budget_refills(tmp_path):
    """Failures spaced wider than the window stop counting against the
    budget: a long-lived job that dies occasionally outlives
    max_restarts total failures."""
    agent = _run_agent_child(tmp_path, """
        import os, sys, time
        marker = os.path.join(sys.argv[1], "n")
        n = int(open(marker).read()) if os.path.exists(marker) else 0
        open(marker, "w").write(str(n + 1))
        time.sleep(0.35)              # outlive the agent's budget window
        sys.exit(0 if n >= 2 else 1)
    """, max_restarts=1, restart_delay_s=0.01, backoff_jitter=0.0,
        restart_window_s=0.25, monitor_interval_s=0.02)
    result = agent.run()
    # two failures total but never two inside one window
    assert result.success and result.restarts == 2


def test_elastic_agent_preemption_resume_env(tmp_path):
    """A worker exiting with the preemption code is restarted with
    DS_RESUME=latest and does not consume the failure budget."""
    agent = _run_agent_child(tmp_path, """
        import os, sys
        sys.exit(0 if os.environ.get("DS_RESUME") == "latest" else 86)
    """, max_restarts=0, restart_delay_s=0.01, monitor_interval_s=0.01)
    result = agent.run()
    assert result.success
    assert result.restarts == 0 and result.preempt_restarts == 1
    assert result.history[0].preempted and result.history[1].resumed


# ---------------------------------------------------------------- serving
class _StubScheduler:
    """Just enough scheduler surface for loop/watchdog tests — no model,
    no compile."""

    def __init__(self, cfg, step_fn=None):
        self.cfg = cfg
        self.metrics = ServingMetrics()
        self._step_fn = step_fn
        self._step_count = 0
        self.monitor = None

    def has_work(self):
        return True

    def has_work_unlocked(self):
        return True

    @property
    def step_count(self):
        return self._step_count

    def step(self):
        if self._step_fn is not None:
            self._step_fn()
        self._step_count += 1

    def metrics_snapshot(self):
        return self.metrics.snapshot()


def _wait_for(pred, timeout=10.0, interval=0.01):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return True
        time.sleep(interval)
    return False


def test_serving_loop_failure_cap_degrades():
    """ISSUE 3 satellite: N consecutive step() failures → DEGRADED +
    serving/loop_failures metric, instead of log-and-sleep forever."""
    from deepspeed_tpu.serving.server import ServingLoop

    def boom():
        raise RuntimeError("injected step failure")

    cfg = ServingConfig(max_loop_failures=3, stall_timeout_s=0)
    sched = _StubScheduler(cfg, step_fn=boom)
    loop = ServingLoop(sched)
    loop.FAILURE_SLEEP_S = 0.001
    loop.start()
    try:
        assert loop.join(timeout=60)       # the loop exits, not spins:
        assert loop.health.is_degraded()   # its thread's end is the event
        assert sched.metrics.counters["loop_failures"] == 3
        assert "consecutive step failures" in loop.health.reason
        assert sched.metrics.snapshot()["serving/loop_failures"] == 3.0
    finally:
        loop.shutdown()


def test_serving_loop_failures_reset_on_success():
    from deepspeed_tpu.serving.server import ServingLoop
    state = {"n": 0}

    def flaky():
        state["n"] += 1
        if state["n"] % 2:                 # alternate fail/succeed
            raise RuntimeError("transient")

    cfg = ServingConfig(max_loop_failures=3, stall_timeout_s=0)
    sched = _StubScheduler(cfg, step_fn=flaky)
    loop = ServingLoop(sched)
    loop.FAILURE_SLEEP_S = 0.001
    loop.start()
    try:
        assert _wait_for(lambda: sched.step_count >= 8)
        assert not loop.health.is_degraded()
        assert sched.metrics.counters["loop_failures"] >= 4
    finally:
        loop.shutdown()


def test_scheduler_watchdog_marks_stall_degraded_and_recovers():
    """ISSUE 3 tentpole: the watchdog (not per-handler polling) detects a
    frozen step_count and degrades the server, with a metrics counter —
    and clears its own verdict when progress resumes (a minutes-long XLA
    compile must not brick the replica until restart)."""
    cfg = ServingConfig()
    sched = _StubScheduler(cfg)            # step_count never advances
    health = HealthMonitor()
    health.mark_ready()
    dog = SchedulerWatchdog(sched, health, stall_timeout_s=0.15,
                            poll_interval_s=0.03)
    dog.start()
    try:
        assert _wait_for(health.is_degraded, timeout=5)
        assert "stalled" in health.reason
        assert sched.metrics.counters["stalls"] == 1
        sched._step_count += 1             # the wedged step completed
        assert _wait_for(lambda: health.state is HealthState.READY,
                         timeout=5)
        assert "recovered" in health.reason
    finally:
        dog.stop()


def test_scheduler_watchdog_survives_held_scheduler_lock():
    """Regression: a wedged step() holds the scheduler lock; the watchdog
    must detect the stall through lock-free reads instead of blocking on
    has_work() and joining the deadlock."""
    cfg = ServingConfig()
    sched = _StubScheduler(cfg)
    wedged = threading.Event()

    def locked_has_work():                 # what acquiring the real lock
        wedged.wait(60)                    # under a wedged step becomes
        return True

    sched.has_work = locked_has_work
    health = HealthMonitor()
    health.mark_ready()
    dog = SchedulerWatchdog(sched, health, stall_timeout_s=0.1,
                            poll_interval_s=0.03)
    dog.start()
    try:
        assert _wait_for(health.is_degraded, timeout=5), \
            "watchdog blocked on the scheduler lock"
    finally:
        wedged.set()
        dog.stop()


def test_health_state_machine():
    h = HealthMonitor()
    assert h.state is HealthState.STARTING and h.http_status() == 503
    assert h.mark_ready() and h.http_status() == 200 and h.is_accepting()
    assert h.begin_drain("test") and not h.is_accepting()
    assert h.http_status() == 503 and h.drain_started.is_set()
    assert not h.mark_ready()              # no un-draining
    assert h.mark_stopped()
    assert not h.begin_drain("late")       # terminal


def test_stall_timeout_env_override(monkeypatch):
    cfg = ServingConfig(stall_timeout_s=5.0)
    assert cfg.resolved_stall_timeout_s() == 5.0
    monkeypatch.setenv("DS_SERVE_STALL_TIMEOUT_S", "42.5")
    assert cfg.resolved_stall_timeout_s() == 42.5
    monkeypatch.delenv("DS_SERVE_STALL_TIMEOUT_S")
    assert ServingConfig().stall_timeout_s == 600.0   # legacy 10 x 60 s
    with pytest.raises(ValueError, match="stall_timeout_s"):
        ServingConfig(stall_timeout_s=-1)
    with pytest.raises(ValueError, match="max_loop_failures"):
        ServingConfig(max_loop_failures=-1)


def test_install_drain_handlers_sigterm():
    """SIGTERM → DRAINING; a second SIGTERM → immediate server stop."""
    from deepspeed_tpu.serving.server import install_drain_handlers
    health = HealthMonitor()
    health.mark_ready()
    stopped = threading.Event()

    class FakeHttpd:
        def shutdown(self):
            stopped.set()

    before = signal.getsignal(signal.SIGTERM)
    try:
        install_drain_handlers(health, FakeHttpd(),
                               signals=(signal.SIGTERM,))
        signal.raise_signal(signal.SIGTERM)
        assert health.is_draining()
        assert not stopped.is_set()
        signal.raise_signal(signal.SIGTERM)
        assert stopped.wait(timeout=5)
    finally:
        signal.signal(signal.SIGTERM, before)


@pytest.fixture(scope="module")
def served():
    m = tiny_gpt2()
    eng = deepspeed_tpu.init_inference(model=m, config={"dtype": "float32"})
    return m, eng


def test_kv_alloc_deny_fault_forces_preemption(served):
    """kv.alloc deny faults drive the evict/recompute-on-resume path
    deterministically — no need to actually exhaust the pool."""
    m, eng = served
    # max_fused_steps=1 routes growth through the allocate-on-decode
    # path whose exhaustion handler preempts (a denied window
    # reservation would just shrink the fused window instead)
    cfg = ServingConfig(block_size=4, num_blocks=64, max_num_seqs=2,
                        max_num_batched_tokens=64, max_fused_steps=1)
    inj = FaultInjector("kv.alloc:deny@2")
    sched = ContinuousBatchingScheduler(m, eng.params, cfg, injector=inj)
    rng = np.random.default_rng(0)
    pa = rng.integers(1, 128, (6,)).astype(np.int32)
    pb = rng.integers(1, 128, (6,)).astype(np.int32)
    ra = sched.submit(pa, SamplingParams(max_new_tokens=8), priority=1)
    rb = sched.submit(pb, SamplingParams(max_new_tokens=8), priority=0)
    sched.run_until_idle()
    assert inj.fired.get("kv.alloc") == 1
    assert sched.metrics.counters["preemptions"] >= 1
    assert ra.state == RequestState.FINISHED
    assert rb.state == RequestState.FINISHED
    for p, r in ((pa, ra), (pb, rb)):
        ref = np.asarray(eng.generate(p[None], max_new_tokens=8,
                                      do_sample=False))[0, p.size:]
        np.testing.assert_array_equal(np.asarray(r.output_ids), ref)


def _post(base, payload, timeout=60):
    req = urllib.request.Request(
        base + "/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_drain_finishes_inflight_rejects_new(served):
    """Acceptance: during a drain, in-flight requests complete and new
    /generate calls get 503; the loop then exits cleanly."""
    from deepspeed_tpu.serving.server import make_server
    m, eng = served
    cfg = ServingConfig(block_size=8, num_blocks=64, max_num_seqs=2,
                        stall_timeout_s=120)
    # pace the loop so the in-flight request is still decoding when the
    # drain begins (deterministic via the injector, not sleeps)
    inj = FaultInjector("serve.step:stall=0.02@*")
    sched = ContinuousBatchingScheduler(m, eng.params, cfg, injector=inj)
    httpd, loop = make_server(sched, port=0)
    loop.start()
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    base = f"http://127.0.0.1:{httpd.server_port}"
    try:
        with urllib.request.urlopen(base + "/healthz", timeout=10) as resp:
            assert resp.status == 200
            assert json.loads(resp.read())["status"] == "ready"
        prompt = np.random.default_rng(1).integers(1, 128, (5,))
        result = {}

        def _inflight():
            result["resp"] = _post(base, {"input_ids": prompt.tolist(),
                                          "max_new_tokens": 48})

        worker = threading.Thread(target=_inflight, daemon=True)
        worker.start()
        assert _wait_for(lambda: sched.active_requests(), timeout=30)
        assert loop.health.begin_drain("test drain")
        # healthz flips to 503/draining immediately
        code, body = _post(base, {"input_ids": [1, 2], "max_new_tokens": 2})
        assert code == 503 and "not accepting" in body["error"]
        try:
            with urllib.request.urlopen(base + "/healthz", timeout=10):
                pytest.fail("healthz should be 503 during drain")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert json.loads(e.read())["status"] == "draining"
        worker.join(timeout=120)
        code, body = result["resp"]
        assert code == 200 and len(body["output_ids"]) == 48
        assert sched.metrics.counters["rejected_not_accepting"] == 1
        # loop exits on its own once drained; health lands on STOPPED
        assert loop.join(timeout=30)
        assert loop.health.state is HealthState.STOPPED
    finally:
        httpd.shutdown()
        loop.shutdown()
        httpd.server_close()


# -------------------------------------------------------- slow e2e chaos
E2E_TRAIN_SCRIPT = """
import json, os, sys

# lean single-device CPU child (the parent env forces an 8-dev mesh and
# the heap-sensitive thunk flag; neither is needed here).  NOTE: the
# persistent compile cache stays OFF — on this container's jaxlib,
# donated train steps over freshly RESTORED state under a warm
# persistent cache corrupt the glibc heap (the documented
# test_universal_checkpoint abort class), and resume-after-restart is
# this script's whole job.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_backend_optimization_level=0"
sys.path.insert(0, {root!r})

import jax
jax.config.update("jax_platforms", "cpu")

import numpy as np
import deepspeed_tpu
from deepspeed_tpu.resilience import resume_tag_from_env, \\
    run_resilient_training
from tests.util import tiny_gpt2, base_config

save_dir, out_path, num_steps = sys.argv[1], sys.argv[2], int(sys.argv[3])
resumed = resume_tag_from_env() is not None
if resumed:
    # a resumed run must not replay the injected fault (the preempting
    # host is gone); counters are per-process, so drop the spec entirely
    os.environ.pop("DS_FAULTS", None)

cfg = base_config(**{{"optimizer": {{"type": "Adam",
                                    "params": {{"lr": 1e-2}}}},
                     "resilience": {{"keep_last_k": 3}}}})
engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_gpt2(), config=cfg)

def batch_for(step):
    rng = np.random.default_rng(1000 + step)
    return {{"input_ids": rng.integers(0, 128, size=(1, 4, 16),
                                       dtype=np.int32)}}

loss = run_resilient_training(engine, batch_for, save_dir,
                              num_steps=num_steps, save_interval=2)
json.dump({{"loss": float(loss), "steps": int(engine.global_steps),
            "resumed": resumed}}, open(out_path, "w"))
"""


def _write_e2e_script(tmp_path):
    script = tmp_path / "train_child.py"
    script.write_text(E2E_TRAIN_SCRIPT.format(
        root=os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    return script


def _run_reference(script, tmp_path, num_steps=8):
    out = tmp_path / "ref.json"
    env = {k: v for k, v in os.environ.items() if k != "DS_FAULTS"}
    r = subprocess.run(
        [sys.executable, str(script), str(tmp_path / "ref_ckpt"),
         str(out), str(num_steps)],
        env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(out.read_text())


@pytest.mark.slow
def test_e2e_hard_kill_resume_same_loss(tmp_path):
    """Acceptance: a training run hard-killed mid-step by the injector,
    supervised by DSElasticAgent with always_resume, restarts from the
    last periodic checkpoint and reaches the SAME final loss as an
    uninterrupted run."""
    from deepspeed_tpu.elasticity.elastic_agent import DSElasticAgent
    script = _write_e2e_script(tmp_path)
    ref = _run_reference(script, tmp_path)
    assert ref["steps"] == 8 and not ref["resumed"]

    out = tmp_path / "killed.json"
    env = dict(os.environ,
               DS_FAULTS="train.step:kill=9@5")   # dies at the 6th step
    agent = DSElasticAgent(
        [sys.executable, str(script), str(tmp_path / "ckpt"),
         str(out), "8"],
        env=env, max_restarts=2, restart_delay_s=0.05,
        monitor_interval_s=0.05, always_resume=True)
    result = agent.run()
    assert result.success and result.restarts == 1
    assert result.return_codes == [9, 0]
    assert result.history[1].resumed
    got = json.loads(out.read_text())
    assert got["steps"] == 8 and got["resumed"]
    np.testing.assert_allclose(got["loss"], ref["loss"], rtol=1e-6)


@pytest.mark.slow
def test_e2e_sigterm_drain_emergency_resume(tmp_path):
    """Acceptance: SIGTERM (self-delivered by the injector) drains
    through an emergency checkpoint + PREEMPTED exit code; the agent
    resumes WITHOUT burning the failure budget and the run converges to
    the uninterrupted loss."""
    from deepspeed_tpu.elasticity.elastic_agent import DSElasticAgent
    script = _write_e2e_script(tmp_path)
    ref = _run_reference(script, tmp_path)

    out = tmp_path / "preempted.json"
    env = dict(os.environ, DS_FAULTS="train.step:sigterm@5")
    agent = DSElasticAgent(
        [sys.executable, str(script), str(tmp_path / "ckpt"),
         str(out), "8"],
        env=env, max_restarts=0,          # resume must not need budget
        restart_delay_s=0.05, monitor_interval_s=0.05)
    result = agent.run()
    assert result.success
    assert result.restarts == 0 and result.preempt_restarts == 1
    assert result.return_codes == [PREEMPTED_EXIT_CODE, 0]
    # the drain wrote an emergency tag at the preempted step
    tags = rckpt.list_tags(str(tmp_path / "ckpt"))
    assert any(t.startswith("emergency_step") for t in tags)
    got = json.loads(out.read_text())
    assert got["steps"] == 8 and got["resumed"]
    np.testing.assert_allclose(got["loss"], ref["loss"], rtol=1e-6)


@pytest.mark.slow
def test_e2e_kill_during_save_falls_back(tmp_path):
    """Acceptance (process-kill flavor of the fault matrix): a hard kill
    DURING the checkpoint publish leaves the previous tag restorable."""
    script = _write_e2e_script(tmp_path)
    out = tmp_path / "out.json"
    env = dict(os.environ,
               # step-2 periodic save survives; the step-4 save is killed
               # mid-manifest — the process dies inside save_checkpoint
               DS_FAULTS="ckpt.manifest:kill=9@1")
    r = subprocess.run(
        [sys.executable, str(script), str(tmp_path / "ckpt"),
         str(out), "8"],
        env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 9
    save_dir = str(tmp_path / "ckpt")
    tag = rckpt.find_valid_tag(save_dir)
    assert tag == "global_step2"
    ok, reason = verify_tag(os.path.join(save_dir, tag))
    assert ok, reason
