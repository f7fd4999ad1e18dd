"""Numerics observatory (ISSUE 15): training-health telemetry, NaN
provenance, MoE router health, and determinism fingerprints.

Acceptance (tier-1):

- the in-graph stats are banked LAZILY: a training loop adds zero
  ``jax.device_get`` calls and zero bank resolutions on the hot path
  (the overflow-banking contract, asserted directly);
- an injected ``train.nonfinite`` fault at a known leaf group is
  attributed to exactly that group in ``/debug/numerics`` over live
  HTTP, in the flight recorder, and in the post-mortem bundle's
  ``numerics.json``, and the trace validates with ``anomaly/num_*``
  instants carrying the step corr id;
- restore-from-checkpoint reproduces the save-time fingerprint
  (audited at load), a deliberately perturbed restore is flagged, and
  a save→resume run reproduces the uninterrupted run's fingerprint
  stream bitwise (subprocess, cache-less per the documented jaxlib
  restore-then-train hazard);
- einsum and grouped MoE dispatch publish bitwise-identical router
  health through the opt-in registry tap.
"""
import json
import os
import subprocess
import sys
import urllib.request

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.runtime.config import NumericsConfig, TelemetryConfig
from deepspeed_tpu.telemetry import (MetricsRegistry, get_registry,
                                     numerics_payload, peek_numerics,
                                     reset_numerics, reset_tracer)
from deepspeed_tpu.telemetry.numerics import (NumericsState, group_stats,
                                              leaf_groups,
                                              numerics_enabled,
                                              resolve_fingerprint_interval,
                                              state_fingerprint)
from tests.util import base_config, child_env, random_batch, tiny_gpt2

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _numerics_isolation():
    reset_numerics()
    yield
    reset_numerics()


def _batch(seed=0):
    # leading gas=1; inner batch 8 divides the virtual 8-device mesh
    return {"input_ids": random_batch(seed=seed)["input_ids"][None]}


def _engine(tmp_path=None, **cfg_overrides):
    cfg = base_config(**cfg_overrides)
    if tmp_path is not None:
        cfg.setdefault("resilience", {})["postmortem_dir"] = str(tmp_path)
    eng, *_ = deepspeed_tpu.initialize(model=tiny_gpt2(), config=cfg)
    return eng


# ---------------------------------------------------------------- units
def test_leaf_groups_names_and_index():
    tree = {"blocks": {"attn_w": np.zeros((2, 3)),
                       "mlp_w": np.zeros((4,))},
            "wte": np.zeros((5,))}
    names, index = leaf_groups(tree, depth=2)
    assert names == ["blocks/attn_w", "blocks/mlp_w", "wte"]
    assert index == [0, 1, 2]
    names1, index1 = leaf_groups(tree, depth=1)
    assert names1 == ["blocks", "wte"]
    assert index1 == [0, 0, 1]


def test_group_stats_norms_and_nonfinite_bitmap():
    import jax.numpy as jnp
    grads = {"a": jnp.asarray([3.0, 4.0]),
             "b": jnp.asarray([[jnp.nan, 1.0], [jnp.inf, 2.0]])}
    names, index = leaf_groups(grads, depth=1)
    norms, counts = group_stats(grads, index, len(names))
    norms, counts = np.asarray(norms), np.asarray(counts)
    assert norms[0] == pytest.approx(5.0)
    assert not np.isfinite(norms[1])           # NaN/Inf poison the norm
    assert counts.tolist() == [0, 2]           # provenance bitmap
    # structure mismatch degrades to None, never a wrong attribution
    assert group_stats(grads, [0], 1) is None


def test_state_fingerprint_sensitivity():
    params = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
              "b": np.ones((4,), np.float32)}
    rng = np.asarray([1, 2], np.uint32)
    d0 = state_fingerprint(params, rng, step=5)
    assert d0 == state_fingerprint(params, rng, step=5)   # deterministic
    p2 = {"w": params["w"].copy(), "b": params["b"]}
    p2["w"][1, 2] += 1e-6                    # any sampled element flips it
    assert state_fingerprint(p2, rng, step=5) != d0
    assert state_fingerprint(params, np.asarray([1, 3], np.uint32),
                             step=5) != d0   # rng chain is digested
    assert state_fingerprint(params, rng, step=6) != d0   # step too
    assert state_fingerprint(params, rng, step=5, loss=1.0) != d0


def test_numerics_config_roundtrip_and_env_wins(monkeypatch):
    t = TelemetryConfig(numerics={"fingerprint_interval": 8,
                                  "group_depth": 3, "history": 64})
    assert t.numerics.enabled and t.numerics.fingerprint_interval == 8
    assert t.numerics.group_depth == 3 and t.numerics.history == 64
    # bool shorthand matches telemetry.memory's spelling
    assert TelemetryConfig(numerics=False).numerics.enabled is False
    with pytest.raises(ValueError):
        NumericsConfig(fingerprint_interval=-1)
    with pytest.raises(ValueError):
        NumericsConfig(group_depth=0)
    with pytest.raises(ValueError):
        NumericsConfig(history=4)
    monkeypatch.setenv("DS_NUMERICS", "0")
    assert numerics_enabled(True) is False
    monkeypatch.setenv("DS_NUMERICS", "1")
    assert numerics_enabled(False) is True
    monkeypatch.delenv("DS_NUMERICS")
    assert numerics_enabled(None) is True
    monkeypatch.setenv("DS_FINGERPRINT_INTERVAL", "16")
    assert resolve_fingerprint_interval(4) == 16
    monkeypatch.delenv("DS_FINGERPRINT_INTERVAL")
    assert resolve_fingerprint_interval(4) == 4


def test_overflow_handled_provenance_no_postmortem():
    fired = []
    st = NumericsState(["g0", "g1"], registry=MetricsRegistry(),
                       on_nonfinite=fired.append)
    st.bank(1, grad_norm=np.float32(0.0), overflow=np.bool_(True),
            loss=np.float32(2.0), loss_scale=np.float32(1024.0),
            group_norms=np.asarray([0.0, np.inf], np.float32),
            nonfinite=np.asarray([0, 3], np.int32),
            update_ratio=np.float32(0.0))
    st.resolve()
    # handled (overflow) records ride their own rolling tail — they
    # must never consume the first-N unexpected-incident ring
    assert st.nonfinite_records() == []
    handled = st.handled_nonfinite_records()
    assert len(handled) == 1 and handled[0]["handled"] is True
    assert handled[0]["first_group"] == "g1"
    assert st.nonfinite_overflow_steps == 1 and st.nonfinite_steps == 0
    assert fired == []        # loss-scaler skips never trigger a bundle
    # unexpected flavor: counted separately, callback fires
    st.bank(2, grad_norm=np.float32(np.nan), overflow=np.bool_(False),
            nonfinite=np.asarray([2, 0], np.int32),
            group_norms=np.asarray([np.nan, 1.0], np.float32))
    st.resolve()
    assert st.nonfinite_steps == 1
    assert fired and fired[0]["first_group"] == "g0"
    assert st.nonfinite_records()[0]["first_group"] == "g0"
    # non-finite floats never reach the JSON-bound surfaces (spec-
    # invalid NaN tokens would break jq/strict parsers mid-incident)
    snap = st.snapshot()
    json.dumps(snap, allow_nan=False)
    bad = next(e for e in snap["history"] if e["step"] == 2)
    assert bad["nonfinite"] is True
    assert bad["grad_norm"] is None
    assert bad["group_norms"][0] is None
    assert st.registry.get_counter("num/nonfinite_steps",
                                   handled="unexpected") == 1
    assert st.registry.get_counter("num/nonfinite_steps",
                                   handled="overflow") == 1


def test_numerics_payload_unarmed_and_filters():
    assert numerics_payload()["armed"] is False
    from deepspeed_tpu.telemetry.numerics import configure_numerics
    st = configure_numerics(["a/x", "a/y", "b"])
    for step in range(1, 6):
        st.bank(step, grad_norm=np.float32(step), loss=np.float32(1.0),
                group_norms=np.asarray([1.0, 2.0, 3.0], np.float32),
                nonfinite=np.zeros((3,), np.int32),
                update_ratio=np.float32(0.01))
    payload = numerics_payload({"n": "2", "group": "a/"})
    assert payload["armed"] is True
    assert payload["groups"] == ["a/x", "a/y"]
    assert len(payload["history"]) == 2
    assert payload["history"][-1]["group_norms"] == [1.0, 2.0]


# ------------------------------------------------- lazy banking contract
def test_bank_is_lazy_and_resolves_in_one_fetch():
    eng = _engine()
    # warm the compiled step + the one-time cost/memory reports before
    # instrumenting: the acceptance is about the steady-state hot path
    for i in range(2):
        eng.train_batch(batch=_batch(seed=i))
    st = eng.numerics
    st.resolve()
    base_resolves = st.resolves
    calls = {"n": 0}
    real = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real(x)

    jax.device_get = counting
    try:
        for i in range(8):
            eng.train_batch(batch=_batch(seed=10 + i))
        hot_path_fetches = calls["n"]
        assert st.pending_count() == 8       # banked, not fetched
        assert st.resolves == base_resolves  # nothing resolved mid-loop
        assert hot_path_fetches == 0         # zero added host syncs
        entries = st.resolve()
        assert calls["n"] == 1               # the WHOLE backlog: one fetch
    finally:
        jax.device_get = real
    assert [e["step"] for e in entries] == list(range(3, 11))
    last = entries[-1]
    assert np.isfinite(last["grad_norm"]) and np.isfinite(last["loss"])
    assert last["update_ratio"] > 0
    assert len(last["group_norms"]) == len(eng._num_groups)
    reg = eng.telemetry_registry
    assert reg.get_gauge("num/grad_norm") == pytest.approx(
        last["grad_norm"])
    assert reg.get_gauge("num/update_ratio") == pytest.approx(
        last["update_ratio"])
    assert reg.get_gauge("num/group_grad_norm",
                         group=eng._num_groups[0]) is not None


def test_numerics_disabled_restores_bare_metrics(monkeypatch):
    monkeypatch.setenv("DS_NUMERICS", "0")
    eng = _engine()
    assert eng.numerics is None and not eng._num_on
    eng.train_batch(batch=_batch())
    assert "grad_norm" in eng.last_metrics
    assert "num_group_norms" not in eng.last_metrics
    assert peek_numerics() is None


# --------------------------------------------- chaos acceptance (HTTP)
def test_chaos_nonfinite_http_trace_and_bundle(tmp_path, monkeypatch):
    """ISSUE 15 acceptance: a ``train.nonfinite`` NaN at a known leaf
    group under DS_TRACE is attributed to that group over live HTTP
    (/debug/numerics), in the flight recorder, and in the bundle's
    numerics.json — while the training loop itself banked lazily (no
    resolves, no extra host syncs) and the trace validates with
    ``anomaly/num_*`` instants carrying the step corr."""
    from deepspeed_tpu.resilience.postmortem import reset_rate_limit
    reset_rate_limit()
    trace_path = str(tmp_path / "numerics_trace.json")
    monkeypatch.setenv("DS_TRACE", trace_path)
    reset_tracer()
    inject_group = 5
    eng = _engine(
        tmp_path=tmp_path / "pm",
        telemetry={"metrics_port": 0},
        resilience={"faults": f"train.nonfinite:deny={inject_group}@4",
                    "postmortem_dir": str(tmp_path / "pm")})
    try:
        for i in range(10):
            eng.train_batch(batch=_batch(seed=i))
        st = eng.numerics
        expect = eng._num_groups[inject_group]
        # lazy banking preserved: the injected step changed nothing on
        # the hot path — detection happens at resolution, not per step
        assert st.resolves == 0
        assert st.pending_count() == 10
        port = eng.metrics_server.port
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/numerics?n=16",
                timeout=10) as r:
            payload = json.loads(r.read())
        assert payload["armed"] is True
        recs = payload["nonfinite"]["records"]
        assert recs and recs[0]["first_group"] == expect
        assert recs[0]["step"] == 5          # invocation 4 == step 5
        assert list(recs[0]["groups"]) == [expect]
        # flight recorder carries the same attribution
        events = eng.flightrec.events(kind_prefix="num/nonfinite")
        assert any(e.get("first_group") == expect
                   and e.get("corr") == "train-step-5" for e in events)
        # the num/* gauges ride the same /metrics exposition
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            prom = r.read().decode()
        assert "num_grad_norm" in prom
        assert "num_group_grad_norm{" in prom
        assert 'num_nonfinite_steps{handled="unexpected"}' in prom
        # the resolve (triggered by the debug read) wrote the bundle
        pm = tmp_path / "pm"
        bundles = [d for d in os.listdir(pm)
                   if d.startswith("postmortem-")]
        assert bundles, "nonfinite detection wrote no bundle"
        with open(pm / bundles[0] / "numerics.json") as f:
            bundle_payload = json.load(f)
        names = [r["first_group"]
                 for r in bundle_payload["nonfinite"]["records"]]
        assert expect in names
    finally:
        eng.metrics_server.stop()
    # flush + validate the trace: anomaly/num_* instants must carry the
    # step corr and detector fields
    eng.tracer.flush()
    reset_tracer()
    from scripts.trace_validate import load_events, validate_anomalies
    events = load_events(trace_path)
    anomalies = [e for e in events
                 if str(e.get("name", "")).startswith("anomaly/num_")]
    assert anomalies, "no anomaly/num_* instants in the trace"
    assert validate_anomalies(events, require_present=True) == []
    nf = [e for e in anomalies if e["name"] == "anomaly/num_nonfinite"]
    assert nf and nf[0]["args"]["corr"] == "train-step-5"
    assert nf[0]["args"]["first_group"] == expect


def test_sanitize_branch_names_group_and_writes_terminal_bundle(
        tmp_path):
    from deepspeed_tpu.resilience.postmortem import reset_rate_limit
    reset_rate_limit()
    eng = _engine(
        tmp_path=tmp_path,
        debug={"sanitize_gradients": True},
        resilience={"faults": "train.nonfinite:deny=3@1",
                    "postmortem_dir": str(tmp_path)})
    eng.train_batch(batch=_batch(seed=0))
    expect = eng._num_groups[3]
    with pytest.raises(FloatingPointError, match=expect.replace("/", "/")):
        eng.train_batch(batch=_batch(seed=1))
    bundles = [d for d in os.listdir(tmp_path)
               if d.startswith("postmortem-")]
    assert bundles, "terminal raise wrote no bundle"


# --------------------------------------------------- fingerprint audit
def test_restore_fingerprint_audit_ok_then_perturbed_flags(tmp_path):
    from deepspeed_tpu.runtime.checkpoint_engine.engine import (
        NpzCheckpointEngine, STATE_DIR)
    save_dir = str(tmp_path / "ckpt")
    eng = _engine()
    eng.checkpoint_engine = NpzCheckpointEngine()
    for i in range(2):
        eng.train_batch(batch=_batch(seed=i))
    assert eng.save_checkpoint(save_dir, tag="t0")
    saved_digest = None
    with open(os.path.join(save_dir, "t0", "ds_metadata.json")) as f:
        saved_digest = json.load(f)["numerics_fingerprint"]["digest"]
    assert saved_digest
    # clean restore: recomputed fingerprint matches the manifest stamp
    # (no training after restore — the documented jaxlib hazard; the
    # continued-stream acceptance runs cache-less in a subprocess)
    e2 = _engine()
    e2.checkpoint_engine = NpzCheckpointEngine()
    path, _ = e2.load_checkpoint(save_dir)
    assert path is not None
    audit = e2.numerics.restore_audits[-1]
    assert audit["ok"] is True and audit["actual"] == saved_digest
    # perturb one param element on disk: structural (manifest)
    # verification passes, the fingerprint audit flags it
    state_path = os.path.join(save_dir, "t0", STATE_DIR + ".npz")
    data = dict(np.load(state_path))
    key = next(k for k in data
               if k.startswith("params/") and data[k].size > 4
               and np.issubdtype(data[k].dtype, np.floating))
    data[key] = data[key].copy()
    data[key].flat[0] += 1.0
    np.savez(state_path.removesuffix(".npz"), **data)
    before = get_registry().get_counter("num/fingerprint_mismatch")
    e3 = _engine(resilience={"verify_checkpoint": "off"})
    e3.checkpoint_engine = NpzCheckpointEngine()
    path, _ = e3.load_checkpoint(save_dir, tag="t0")
    assert path is not None
    audit = e3.numerics.restore_audits[-1]
    assert audit["ok"] is False
    assert audit["expected"] == saved_digest
    assert get_registry().get_counter("num/fingerprint_mismatch") \
        == (before or 0.0) + 1
    # the audit also lands as a num/fingerprint flight event
    evs = e3.flightrec.events(kind_prefix="num/fingerprint")
    assert any(e.get("source") == "restore" and e.get("ok") is False
               for e in evs)


_RESUME_CHILD = """
import os, sys, json
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_backend_optimization_level=0"
sys.path.insert(0, {root!r})
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import gpt2_model

rng = np.random.default_rng(7)
batches = [{{"input_ids": rng.integers(0, 128, size=(1, 4, 16),
                                       dtype=np.int32)}}
           for _ in range(6)]

def make_engine():
    model = gpt2_model(size="custom", vocab_size=128, max_seq_len=64,
                       num_layers=2, num_heads=4, d_model=32,
                       dtype="float32", attention_impl="xla")
    eng, *_ = deepspeed_tpu.initialize(model=model, config={{
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {{"type": "Adam", "params": {{"lr": 1e-3}}}},
        "steps_per_print": 0,
        "telemetry": {{"numerics": {{"fingerprint_interval": 2}}}}}})
    return eng

def interval_stream(eng):
    return {{e["step"]: e["digest"]
             for e in eng.numerics.fingerprint_stream()
             if e["source"] == "interval"}}

# run A: uninterrupted 6 steps
eA = make_engine()
for b in batches:
    eA.train_batch(batch=b)
stream_a = interval_stream(eA)

# run B: 2 steps -> save -> fresh engine restores -> 4 more steps
save_dir = sys.argv[1]
eB = make_engine()
for b in batches[:2]:
    eB.train_batch(batch=b)
eB.save_checkpoint(save_dir, tag="t")
stream_b = interval_stream(eB)
eC = make_engine()
path, _ = eC.load_checkpoint(save_dir)
assert path is not None, "restore failed"
for b in batches[2:]:
    eC.train_batch(batch=b)
stream_b.update(interval_stream(eC))
audits = eC.numerics.restore_audits
print(json.dumps({{"a": stream_a, "b": stream_b,
                   "audit_ok": bool(audits and audits[-1]["ok"])}}))
"""


def test_fingerprint_resume_reproduces_stream_bitwise(tmp_path):
    """Save -> (process boundary) -> resume reproduces the
    uninterrupted run's fingerprint stream bitwise; the restore audit
    passes.  Runs cache-less in a child: on this container's jaxlib a
    donated train step over restored state under the warm persistent
    cache corrupts the heap (test_resilience's documented pattern)."""
    out = subprocess.run(
        [sys.executable, "-c", _RESUME_CHILD.format(root=REPO),
         str(tmp_path / "ckpt")],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc["audit_ok"] is True
    a = {int(k): v for k, v in doc["a"].items()}
    b = {int(k): v for k, v in doc["b"].items()}
    assert set(a) == {2, 4, 6} and set(b) == {2, 4, 6}
    assert a == b, f"fingerprint streams diverged: {a} vs {b}"
    # and the report tool agrees: identical -> 0, perturbed -> 1
    pa = tmp_path / "a.json"
    pb = tmp_path / "b.json"
    pbad = tmp_path / "bad.json"

    def payload(stream):
        return {"history": [], "fingerprints": [
            {"step": s, "digest": d, "source": "interval"}
            for s, d in sorted(stream.items())]}
    pa.write_text(json.dumps(payload(a)))
    pb.write_text(json.dumps(payload(b)))
    bad = dict(b)
    bad[4] = "0" * 32
    pbad.write_text(json.dumps(payload(bad)))
    from scripts.numerics_report import main as report_main
    assert report_main(["--diff", str(pa), str(pb)]) == 0
    assert report_main(["--diff", str(pa), str(pbad)]) == 1


# ------------------------------------------------------ MoE router health
def test_moe_router_health_parity_einsum_vs_grouped():
    from deepspeed_tpu.moe.layer import (MoEConfig, dispatch_scope,
                                         init_moe_params, moe_layer,
                                         set_moe_metrics_registry)
    cfg = MoEConfig(d_model=16, d_ff=32, num_experts=4, top_k=2,
                    z_loss_coef=1e-3)
    params = init_moe_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    vals = {}
    try:
        for mode in ("einsum", "grouped"):
            reg = MetricsRegistry()
            set_moe_metrics_registry(reg)
            with dispatch_scope(mode):
                out, _ = moe_layer(params, x, cfg, train=False)
            jax.block_until_ready(out)
            vals[mode] = {
                "entropy": reg.get_gauge("moe/router_entropy"),
                "max_frac": reg.get_gauge(
                    "moe/expert_load_max_fraction"),
                "dead": reg.get_counter("moe/dead_experts"),
                "aux": reg.get_gauge("moe/aux_loss"),
                "z": reg.get_gauge("moe/z_loss"),
                "load": [reg.get_gauge("moe/expert_load_fraction",
                                       expert=str(i))
                         for i in range(cfg.num_experts)],
            }
    finally:
        set_moe_metrics_registry(None)
    assert vals["einsum"] == vals["grouped"]
    e = vals["einsum"]
    assert e["entropy"] is not None and 0.0 < e["entropy"] <= np.log(4) + 1e-6
    assert 0.25 <= e["max_frac"] <= 1.0
    assert e["z"] is not None and e["z"] > 0.0      # z_loss armed
    assert sum(e["load"]) == pytest.approx(1.0)


def test_moe_router_health_dead_experts_and_disarmed():
    import jax.numpy as jnp
    from deepspeed_tpu.moe.layer import (MoEConfig, init_moe_params,
                                         moe_layer,
                                         set_moe_metrics_registry)
    cfg = MoEConfig(d_model=8, d_ff=16, num_experts=4, top_k=1)
    params = init_moe_params(cfg, jax.random.PRNGKey(0))
    # bias the router so every token picks expert 0: 3 dead experts
    # (non-negative tokens keep every logit's column-0 dot positive)
    params = dict(params)
    params["router"] = jnp.zeros_like(params["router"]).at[:, 0].set(50.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (1, 8, 8)))
    reg = MetricsRegistry()
    set_moe_metrics_registry(reg)
    try:
        out, _ = moe_layer(params, x, cfg, train=False)
        jax.block_until_ready(out)
    finally:
        set_moe_metrics_registry(None)
    assert reg.get_counter("moe/dead_experts") == 3
    assert reg.get_gauge("moe/expert_load_max_fraction") == 1.0
    assert reg.get_gauge("moe/router_entropy") == pytest.approx(
        0.0, abs=1e-4)
    # disarmed tap publishes nothing (the opt-in contract)
    reg2 = MetricsRegistry()
    out, _ = moe_layer(params, x, cfg, train=False)
    jax.block_until_ready(out)
    assert reg2.get_gauge("moe/router_entropy") is None


# ------------------------------------------------------------- tooling
def test_numerics_report_render_and_errors(tmp_path, capsys):
    from scripts.numerics_report import main as report_main
    payload = {
        "armed": True, "groups": ["a", "b"],
        "history": [{"step": 1, "loss": 2.0, "grad_norm": 1.0,
                     "update_ratio": 0.01, "loss_scale": 1.0,
                     "overflow": False, "group_norms": [0.5, 0.8]}],
        "nonfinite": {"unexpected_steps": 1, "overflow_steps": 0,
                      "records": [{"step": 1, "first_group": "b",
                                   "groups": {"b": 3}, "loss": None}]},
        "fingerprints": [{"step": 1, "digest": "ab", "source":
                          "interval"}],
        "restore_audits": [{"step": 1, "ok": False, "expected": "x",
                            "actual": "y"}],
    }
    p = tmp_path / "numerics.json"
    p.write_text(json.dumps(payload))
    assert report_main([str(p)]) == 0
    out = capsys.readouterr().out
    assert "first group 'b'" in out and "MISMATCH" in out
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    assert report_main([str(bad)]) == 2
    assert report_main([str(tmp_path / "missing.json")]) == 2
    assert report_main(["--diff", str(p)]) == 2   # needs two sources


def test_numerics_bench_smoke_subprocess():
    env = child_env(NUMERICS_SMOKE="1")
    env.pop("DS_NUMERICS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "numerics_bench.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "numerics_overhead_fraction"
    assert rec["detail"]["step_s_numerics_off"] > 0


def test_ckpt_bench_detail_gains_convergence_fields():
    from scripts.bench_compare import lower_is_better
    # convergence detail fields gate like latency ones
    assert lower_is_better("ckpt_bench_sync.final_loss")
    assert lower_is_better("ckpt_bench_sync.mean_grad_norm")
    env = dict(os.environ, CKPT_SMOKE="1", ASYNC="0",
               JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "ckpt_bench.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    detail = json.loads(out.stdout.strip().splitlines()[-1])
    assert np.isfinite(detail["final_loss"])
    assert np.isfinite(detail["mean_grad_norm"])
