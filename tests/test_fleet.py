"""Fleet serving (ISSUE 11 tentpole): replica router with health-gated,
prefix-cache-aware dispatch.

The load-bearing contracts:

- N-replica greedy output is token-identical to single-replica cb (and
  so to static ``generate``) for the same request stream — routing is a
  placement decision, never a math decision — including with the prefix
  cache on and across a mid-flight drain with session-affine resubmit;
- membership is health-gated: DRAINING/DEGRADED replicas receive no new
  work, and their in-flight requests are resubmitted to a healthy
  replica through the existing evict/resume machinery, losing nothing;
- the policy stack routes as configured: least-loaded prefers the idle
  replica, session affinity sticks, prefix-aware scoring follows the
  replica cache digest;
- the ``fleet.dispatch`` fault site chaos-tests misroutes (deny — the
  request still completes correctly) and dispatch failure (raise);
- /metrics merges per-replica registries under a ``replica`` label.
"""
import json
import subprocess
import sys
import threading
import urllib.request

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.runtime.config import FleetConfig, ServingConfig
from deepspeed_tpu.serving import (BlockManager,
                                   ContinuousBatchingScheduler,
                                   SamplingParams)
from deepspeed_tpu.serving.fleet import (FleetUnavailableError, Replica,
                                         Router)
from tests.util import tiny_gpt2


@pytest.fixture(autouse=True)
def _debug_invariant(monkeypatch):
    """Every replica scheduler asserts the block-accounting invariant
    per step (same arming as the serving/spec suites) — drain
    extraction and resubmission must never leak or double-free."""
    monkeypatch.setenv("DS_SERVE_DEBUG", "1")


@pytest.fixture(scope="module")
def served():
    m = tiny_gpt2()
    eng = deepspeed_tpu.init_inference(model=m, config={"dtype": "float32"})
    return m, eng


def _mixed_prompts(n=6, seed=0, lo=4, hi=14):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 128, (int(L),)).astype(np.int32)
            for L in rng.integers(lo, hi, n)]


def _static_reference(eng, prompt, max_new):
    return np.asarray(eng.generate(prompt[None], max_new_tokens=max_new,
                                   do_sample=False))[0, prompt.size:]


def _fleet(served, n=2, injector=None, flightrec=None, **serving_kw):
    m, eng = served
    kw = dict(block_size=8, num_blocks=32, max_num_seqs=2,
              max_fused_steps=1)
    kw.update(serving_kw)
    fleet_kw = kw.pop("fleet", {})
    fleet_kw.setdefault("num_replicas", n)
    fleet_kw.setdefault("digest_refresh_s", 0)   # deterministic tests
    cfg = ServingConfig(**kw, fleet=fleet_kw)
    replicas = [Replica(i, m, eng.params, cfg, injector=injector,
                        flightrec=flightrec) for i in range(n)]
    return Router(replicas, cfg.fleet, injector=injector,
                  flightrec=flightrec), replicas


# ------------------------------------------------------------------ config
def test_fleet_config_validation():
    cfg = ServingConfig(fleet={"num_replicas": 3, "policy": "round_robin"})
    assert isinstance(cfg.fleet, FleetConfig)
    assert cfg.fleet.num_replicas == 3
    assert ServingConfig().fleet.num_replicas == 1     # default: no fleet
    with pytest.raises(ValueError, match="num_replicas"):
        ServingConfig(fleet={"num_replicas": 0})
    with pytest.raises(ValueError, match="policy"):
        ServingConfig(fleet={"policy": "static"})
    with pytest.raises(ValueError, match="prefix_weight"):
        ServingConfig(fleet={"prefix_weight": -1})
    with pytest.raises(ValueError, match="digest_max_entries"):
        ServingConfig(fleet={"digest_max_entries": 0})
    with pytest.raises(ValueError, match="resubmit_budget"):
        ServingConfig(fleet={"resubmit_budget": -1})


# ----------------------------------------------------- cache digest (sat.)
def test_cache_digest_tracks_published_blocks():
    """Satellite: the digest is exactly the published hash set, newest
    last, and bounded by max_entries."""
    bm = BlockManager(num_blocks=16, block_size=4, cache_enabled=True)
    toks = np.arange(12, dtype=np.int32)       # 3 full blocks
    bm.allocate(1, 3)
    bm.register_committed(1, toks, materialized=12)
    d = bm.cache_digest()
    assert d["cached_blocks"] == 3 and len(d["hashes"]) == 3
    # bounded: the NEWEST entries survive — later blocks pin longer
    # prefixes, which is what the router scores on
    d2 = bm.cache_digest(max_entries=2)
    assert d2["hashes"] == d["hashes"][-2:]
    assert d2["cached_blocks"] == 3            # count stays the truth
    # chain hashes match a router-side recomputation of the same prompt
    h, chain = None, []
    for i in range(3):
        h = BlockManager._chain_hash(h, toks[i * 4:(i + 1) * 4])
        chain.append(h)
    assert d["hashes"] == chain


def test_cache_digest_stable_across_acquire_evict_cow():
    """Satellite: ref bumps and COW forks never change the digest;
    only eviction removes entries."""
    bm = BlockManager(num_blocks=8, block_size=4, cache_enabled=True)
    toks = np.arange(8, dtype=np.int32)        # 2 full blocks
    bm.allocate(1, 2)
    bm.register_committed(1, toks, materialized=8)
    before = bm.cache_digest()["hashes"]
    # acquire with COW fork of the last matched block: the shared
    # source stays published — digest unchanged
    matched = bm.match_prefix(toks)
    assert len(matched) == 2
    got = bm.acquire_prefix(2, matched, n_fresh=1, fork_last=True)
    assert got is not None and got[1] is not None
    assert bm.cache_digest()["hashes"] == before
    # release everything, then drain the pool: LRU eviction removes
    # exactly the evicted entries from the digest
    bm.free(1)
    bm.free(2)
    assert bm.cache_digest()["hashes"] == before     # retained on LRU
    assert bm.allocate(3, bm.num_usable_blocks) is not None
    assert bm.cache_digest() == {"hashes": [], "tiers": [],
                                 "cached_blocks": 0}
    bm.check_invariant()


# ------------------------------------------------------------------ policy
def test_router_least_loaded_prefers_idle(served):
    router, reps = _fleet(served, n=2)
    # load replica 0 with queued work (never stepped)
    for p in _mixed_prompts(3, seed=1):
        reps[0].submit(p, SamplingParams(max_new_tokens=32))
    assert reps[0].outstanding_tokens() > 0
    assert reps[1].outstanding_tokens() == 0
    h = router.submit(_mixed_prompts(1, seed=2)[0],
                      SamplingParams(max_new_tokens=4))
    assert h.replica_id == 1
    router.run_until_idle()


def test_router_session_affinity_sticks(served):
    router, _ = _fleet(served, n=3,
                       fleet={"affinity_weight": 10.0})
    prompts = _mixed_prompts(6, seed=3)
    first = router.submit(prompts[0], SamplingParams(max_new_tokens=3),
                          session_id="alice")
    router.run_until_idle()
    home = first.replica_id
    for p in prompts[1:]:
        h = router.submit(p, SamplingParams(max_new_tokens=3),
                          session_id="alice")
        assert h.replica_id == home
        router.run_until_idle()
    assert router.registry.get_counter("fleet/affinity_hits") >= 5


def test_router_prefix_aware_routing_follows_digest(served):
    """Seed one replica's cache with a long shared prefix; a fresh
    same-prefix request must route to it even when round-robin or load
    would say otherwise."""
    router, reps = _fleet(served, n=2, num_blocks=48,
                          prefix_cache={"enabled": True},
                          fleet={"prefix_weight": 10.0})
    rng = np.random.default_rng(4)
    shared = rng.integers(1, 128, (24,)).astype(np.int32)  # 3 full blocks
    # seed replica 1 directly (bypass the router) so the digest is the
    # only thing that can steer the next dispatch
    reps[1].submit(np.concatenate([shared, [5]]),
                   SamplingParams(max_new_tokens=2))
    while reps[1].scheduler.has_work():
        reps[1].scheduler.step()
    tail = rng.integers(1, 128, (4,)).astype(np.int32)
    h = router.submit(np.concatenate([shared, tail]),
                      SamplingParams(max_new_tokens=4))
    assert h.replica_id == 1
    router.run_until_idle()
    assert router.registry.get_counter("fleet/prefix_routed") >= 1
    assert reps[1].scheduler.metrics.counters["prefix_cache_hit"] >= 3


# ------------------------------------------------------------------ parity
def test_fleet_parity_vs_single_replica(served):
    """Acceptance: a mixed stream over 2 replicas is token-identical to
    the single-replica cb scheduler (itself parity-tested vs static)."""
    m, eng = served
    prompts = _mixed_prompts(8, seed=5)
    max_new = [5, 3, 7, 4, 6, 3, 8, 4]
    # single-replica reference
    cfg = ServingConfig(block_size=8, num_blocks=64, max_num_seqs=4)
    sched = ContinuousBatchingScheduler(m, eng.params, cfg)
    refs = [sched.submit(p, SamplingParams(max_new_tokens=mn))
            for p, mn in zip(prompts, max_new)]
    sched.run_until_idle()
    router, _ = _fleet(served, n=2, max_num_seqs=4, num_blocks=64)
    handles = [router.submit(p, SamplingParams(max_new_tokens=mn))
               for p, mn in zip(prompts, max_new)]
    router.run_until_idle()
    spread = {h.replica_id for h in handles}
    assert spread == {0, 1}, f"stream never spread: {spread}"
    for h, r in zip(handles, refs):
        assert h.state == "finished"
        np.testing.assert_array_equal(np.asarray(h.output_ids),
                                      np.asarray(r.output_ids))


def test_fleet_parity_prefix_cache_on(served):
    """Shared-prefix stream with per-replica prefix caches on: outputs
    still token-identical to static generate, and the caches hit."""
    m, eng = served
    rng = np.random.default_rng(6)
    shared = rng.integers(1, 128, (16,)).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(1, 128, (3 + i,)).astype(
                                   np.int32)]) for i in range(6)]
    router, reps = _fleet(served, n=2, num_blocks=48,
                          prefix_cache={"enabled": True})
    handles = [router.submit(p, SamplingParams(max_new_tokens=5))
               for p in prompts]
    router.run_until_idle()
    for p, h in zip(prompts, handles):
        np.testing.assert_array_equal(np.asarray(h.output_ids),
                                      _static_reference(eng, p, 5))
    assert router.aggregate_prefix_hit_rate() > 0


def test_fleet_drain_resubmits_midflight(served):
    """Acceptance: draining a replica mid-flight loses no request — the
    extracted streams finish token-identically on the survivor, and the
    flight recorder shows route/dispatch -> route/drain ->
    route/resubmit under ONE fleet corr id."""
    from deepspeed_tpu.telemetry.flight_recorder import FlightRecorder
    m, eng = served
    rec = FlightRecorder(4096)
    router, reps = _fleet(served, n=2, flightrec=rec)
    prompts = _mixed_prompts(4, seed=7)
    handles = [router.submit(p, SamplingParams(max_new_tokens=10),
                             session_id=f"s{i}")
               for i, p in enumerate(prompts)]
    # let every stream commit a few tokens, then drain replica 0
    for _ in range(4):
        for rep in reps:
            if rep.scheduler.has_work():
                rep.scheduler.step()
    victims = [h for h in handles if h.replica_id == 0]
    assert victims, "nothing routed to replica 0"
    moved = router.drain_replica(0)
    assert moved == len(victims)
    assert not reps[0].is_accepting()
    router.run_until_idle()
    for p, h in zip(prompts, handles):
        assert h.state == "finished"
        np.testing.assert_array_equal(np.asarray(h.output_ids),
                                      _static_reference(eng, p, 10))
    for h in victims:
        assert h.resubmits == 1 and h.replica_id == 1
        kinds = [e["kind"] for e in rec.events(corr=h.corr)]
        assert kinds[0] == "route/dispatch"
        assert kinds.index("route/drain") < kinds.index("route/resubmit")
        assert kinds[-1] == "route/retire"
        # session affinity followed the stream to the survivor
        assert router._sessions[h.session_id] == 1
    # the drained replica receives nothing new
    h2 = router.submit(prompts[0], SamplingParams(max_new_tokens=3))
    assert h2.replica_id == 1
    router.run_until_idle()


def test_fleet_replica_loss_resubmits(served):
    """A DEGRADED (lost) replica's in-flight request is detected at
    poll() and resubmitted; the merged stream is token-identical."""
    m, eng = served
    router, reps = _fleet(served, n=2)
    p = _mixed_prompts(1, seed=8)[0]
    h = router.submit(p, SamplingParams(max_new_tokens=8))
    victim = reps[h.replica_id]
    while len(h.current.output_ids) < 2:
        victim.scheduler.step()
    victim.health.mark_degraded("test: lost")
    router.run_until_idle()
    assert h.state == "finished" and h.resubmits == 1
    np.testing.assert_array_equal(np.asarray(h.output_ids),
                                  _static_reference(eng, p, 8))


def test_fleet_resubmit_budget_exhausted(served):
    """With resubmit_budget=0 a lost replica's request fails terminally
    (done fires with a reject, never a hang)."""
    router, reps = _fleet(served, n=2, fleet={"resubmit_budget": 0})
    p = _mixed_prompts(1, seed=9)[0]
    h = router.submit(p, SamplingParams(max_new_tokens=8))
    reps[h.replica_id].health.mark_degraded("test: lost")
    router.poll()
    assert h.done.is_set() and h.state == "rejected"
    assert "budget" in h.reject_reason


def test_fleet_unavailable_when_all_drained(served):
    router, reps = _fleet(served, n=2)
    for rep in reps:
        rep.health.begin_drain("test")
    with pytest.raises(FleetUnavailableError):
        router.submit(_mixed_prompts(1)[0], SamplingParams())
    assert router.registry.get_counter("fleet/unroutable") == 1


def test_scored_dispatch_never_blocks_on_wedged_replica(served):
    """A wedged replica (step() holding its scheduler lock) must not
    stall dispatch to the REST of the fleet: the digest refresh is a
    non-blocking snapshot (stale/empty on a miss), so a scored submit
    bound for a healthy replica completes immediately."""
    import time as _time
    router, reps = _fleet(served, n=2, num_blocks=48,
                          prefix_cache={"enabled": True},
                          fleet={"affinity_weight": 10.0})
    p = _mixed_prompts(1, seed=13, lo=20, hi=28)[0]  # >= 1 full block:
    # the dispatch reaches the digest-refresh path for every candidate
    first = router.submit(p, SamplingParams(max_new_tokens=2),
                          session_id="wedge")
    router.run_until_idle()
    other = first.replica_id
    victim = next(r for r in reps if r.replica_id != other)
    held, release = threading.Event(), threading.Event()

    def wedge():
        with victim.scheduler._lock:      # a step() that never returns
            held.set()
            release.wait(10)

    t = threading.Thread(target=wedge, daemon=True)
    t.start()
    assert held.wait(5)
    try:
        t0 = _time.monotonic()
        h = router.submit(p, SamplingParams(max_new_tokens=2),
                          session_id="wedge")
        assert _time.monotonic() - t0 < 2.0, \
            "dispatch queued behind the wedged replica's lock"
        assert h.replica_id == other      # affinity steered it home
    finally:
        release.set()
        t.join(timeout=30)
    router.run_until_idle()
    assert h.state == "finished"


# ------------------------------------------------------------------- chaos
def test_fleet_dispatch_fault_deny_misroutes(served):
    """fleet.dispatch deny = policy-blind misroute: the request lands
    on an arbitrary replica and still completes correctly."""
    from deepspeed_tpu.resilience import FaultInjector
    m, eng = served
    router, _ = _fleet(served, n=2,
                       injector=FaultInjector("fleet.dispatch:deny@*"))
    prompts = _mixed_prompts(4, seed=10)
    handles = [router.submit(p, SamplingParams(max_new_tokens=4))
               for p in prompts]
    router.run_until_idle()
    assert router.registry.get_counter("fleet/misroutes") == 4
    for p, h in zip(prompts, handles):
        np.testing.assert_array_equal(np.asarray(h.output_ids),
                                      _static_reference(eng, p, 4))


def test_fleet_dispatch_fault_raise_surfaces(served):
    from deepspeed_tpu.resilience import FaultInjector
    from deepspeed_tpu.resilience.faults import FaultInjected
    router, _ = _fleet(served, n=2,
                       injector=FaultInjector("fleet.dispatch:raise@0"))
    with pytest.raises(FaultInjected):
        router.submit(_mixed_prompts(1)[0], SamplingParams())
    assert not router.has_inflight()       # no handle leaked
    h = router.submit(_mixed_prompts(1)[0],
                      SamplingParams(max_new_tokens=3))
    router.run_until_idle()
    assert h.state == "finished"


# --------------------------------------------------------------- telemetry
def test_fleet_metrics_merge_under_replica_label(served):
    router, _ = _fleet(served, n=2)
    handles = [router.submit(p, SamplingParams(max_new_tokens=3))
               for p in _mixed_prompts(4, seed=11)]
    router.run_until_idle()
    text = router.render_metrics()
    assert 'replica="0"' in text and 'replica="1"' in text
    assert "fleet_dispatches" in text
    assert text.count("# TYPE serving_completed counter") == 1
    # per-replica completed counts sum to the stream
    total = sum(
        r.scheduler.metrics.counters["completed"]
        for r in router.replicas)
    assert total == len(handles)
    dbg = router.debug_fleet()
    assert dbg["num_replicas"] == 2 and dbg["inflight"] == 0
    assert len(dbg["replicas"]) == 2


def test_outstanding_tokens_estimate(served):
    m, eng = served
    cfg = ServingConfig(block_size=8, num_blocks=32, max_num_seqs=2)
    sched = ContinuousBatchingScheduler(m, eng.params, cfg)
    assert sched.outstanding_tokens_unlocked() == 0
    p = np.arange(1, 11, dtype=np.int32)
    sched.submit(p, SamplingParams(max_new_tokens=6))
    assert sched.outstanding_tokens_unlocked() == 10 + 6
    sched.run_until_idle()
    assert sched.outstanding_tokens_unlocked() == 0


# ---------------------------------------------------------------- frontend
def test_ds_router_help_smoke():
    """tier-1 CLI smoke: bin/ds_router --help exits 0."""
    out = subprocess.run([sys.executable, "bin/ds_router", "--help"],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0
    assert "replica-fleet" in out.stdout


@pytest.mark.slow
def test_fleet_http_end_to_end(served):
    """bin/ds_router's server surface over real HTTP: a mixed stream
    across 2 started replicas, token-identical to static generate;
    /healthz aggregates; /metrics merges under replica labels;
    /debug/fleet answers."""
    from deepspeed_tpu.serving.fleet import make_fleet_server
    m, eng = served
    router, reps = _fleet(served, n=2, max_num_seqs=4, num_blocks=64)
    router.start()
    httpd = make_fleet_server(router, port=0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{httpd.server_port}"
    try:
        prompts = _mixed_prompts(6, seed=12)

        def post(p, i):
            body = json.dumps({"input_ids": p.tolist(),
                               "max_new_tokens": 4,
                               "session_id": f"u{i}"}).encode()
            req = urllib.request.Request(
                base + "/generate", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                assert resp.status == 200
                return json.loads(resp.read())

        outs = [None] * len(prompts)
        threads = [threading.Thread(
            target=lambda i=i, p=p: outs.__setitem__(i, post(p, i)))
            for i, p in enumerate(prompts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        replicas_used = set()
        for p, out in zip(prompts, outs):
            np.testing.assert_array_equal(
                np.asarray(out["output_ids"]),
                _static_reference(eng, p, 4))
            replicas_used.update(out["replica_history"])
        assert replicas_used == {0, 1}, replicas_used
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            health = json.loads(r.read())
            assert health["status"] == "ok" and health["accepting"] == 2
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            text = r.read().decode()
            assert 'serving_completed{replica="0"}' in text
            assert 'serving_completed{replica="1"}' in text
            assert "fleet_dispatches" in text
        with urllib.request.urlopen(base + "/debug/fleet",
                                    timeout=10) as r:
            dbg = json.loads(r.read())
            assert dbg["num_replicas"] == 2
    finally:
        httpd.shutdown()
        router.shutdown()
        httpd.server_close()
