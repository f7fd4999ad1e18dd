"""Perf observatory: cost model, roofline, bench ledger (ISSUE 13).

The load-bearing contracts:
- the jaxpr cost walk counts dot FLOPs execution-weighted (scan trip
  counts, pallas grids) and pallas launch SITES (the PR 12 recursion as
  a shared API), structurally on CPU via interpret mode;
- costmodel-derived byte floors at the bench shapes match PERF.md's
  hand-computed ``weights_floor_int8`` / ``weights_floor_moe`` values
  within 2% — computed from shape-only abstract trees, no 741 MB of
  params materialized;
- roofline floors resolve ONLY where a device rate is known
  (DS_HBM_GBPS is the CPU test override; no fictitious floors), and
  ``perf/achieved_vs_floor`` lands on /metrics and /debug/perf;
- the bench ledger round-trips: bench script → BENCH/ledger.jsonl
  BenchRecord → history-aware bench_compare, which exits 1 on a >10%
  synthetic regression and 2 on a cross-device or cross-model diff.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import deepspeed_tpu
from deepspeed_tpu.telemetry import MetricsRegistry
from deepspeed_tpu.telemetry.costmodel import (abstract_quantized_blocks,
                                               analyze_fn,
                                               costmodel_enabled,
                                               count_pallas_launches,
                                               param_stream_bytes,
                                               register_report,
                                               reset_reports)
from deepspeed_tpu.telemetry import costmodel, roofline
from tests.util import base_config, random_batches, tiny_gpt2

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_reports():
    reset_reports()
    yield
    reset_reports()


# ------------------------------------------------------------ jaxpr walk
def test_dot_flops_counted():
    def fn(x, w):
        return x @ w

    r = analyze_fn(fn, jnp.ones((4, 8)), jnp.ones((8, 16)), name="dot")
    assert r.flops == 2 * 4 * 16 * 8
    # boundary-byte fallback: inputs + outputs, dtype-aware
    assert r.hbm_bytes == 4 * (4 * 8 + 8 * 16 + 4 * 16)
    assert r.detail["hbm_bytes_source"] == "program_boundary_upper_bound"


def test_scan_multiplies_flops():
    w = jnp.ones((8, 8))

    def step(c, _):
        return c @ w, ()

    def fn(c):
        out, _ = lax.scan(step, c, None, length=5)
        return out

    r = analyze_fn(fn, jnp.ones((4, 8)), name="scan")
    assert r.flops == 5 * 2 * 4 * 8 * 8


def test_explicit_hbm_bytes_and_registry():
    r = analyze_fn(lambda x: x * 2, jnp.ones((4,)), name="prog",
                   hbm_bytes=12345, detail={"model": "m"})
    assert r.hbm_bytes == 12345
    assert r.detail["hbm_bytes_source"] == "param_stream"
    register_report(r)
    assert costmodel.get_report("prog").hbm_bytes == 12345
    assert "prog" in costmodel.get_reports()


def test_costmodel_env_resolution(monkeypatch):
    monkeypatch.delenv("DS_PERF_COSTMODEL", raising=False)
    assert costmodel_enabled()
    assert not costmodel_enabled(False)
    monkeypatch.setenv("DS_PERF_COSTMODEL", "0")
    assert not costmodel_enabled(True)
    monkeypatch.setenv("DS_PERF_COSTMODEL", "1")
    assert costmodel_enabled(False)


# --------------------------------------- structural launch/byte contracts
def test_qgemm_path_counts_launches(monkeypatch):
    """ds_qgemm (interpret) traces as >= 1 pallas launch site; the
    plain composition traces as zero (satellite: the PR 12 counter as a
    shared API over the quantized GEMM path)."""
    monkeypatch.setenv("DS_QGEMM_INTERPRET", "1")
    from deepspeed_tpu.ops.pallas.qgemm import ds_qgemm
    from deepspeed_tpu.ops.pallas.quantization import block_quantize_int8
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    q, s = block_quantize_int8(w, block=16)
    x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda a: ds_qgemm(a, q, s, out_dtype=jnp.float32))(x)
    assert count_pallas_launches(jaxpr) >= 1
    jaxpr_plain = jax.make_jaxpr(lambda a: a @ w)(x)
    assert count_pallas_launches(jaxpr_plain) == 0


def test_grouped_gemm_slot_kernel_launches_and_bytes(monkeypatch):
    """Decode-regime slot kernels: the traced program carries >= 1
    launch site, and the distinct-expert byte floor over the stacked
    int8 expert tree matches the inline min(B·k, E) accounting."""
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    from deepspeed_tpu.models.model import QuantizedTensor
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    from deepspeed_tpu.ops.pallas.quantization import block_quantize_int8
    rng = np.random.default_rng(1)
    E, K, N, B = 4, 32, 16, 2
    w = jnp.asarray(rng.standard_normal((E, K, N)), jnp.float32)
    q, s = block_quantize_int8(w, block=16)
    eids = jnp.asarray(rng.integers(0, E, (B,)), jnp.int32)
    plan = gg.make_slot_plan(eids, E)
    x = jnp.asarray(rng.standard_normal((B, K)), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda a: gg.ds_ggemm_slots(a, (q, s), plan, interpret=True))(x)
    assert count_pallas_launches(jaxpr) >= 1
    # byte acceptance: stacked [L, E, in, out] expert tree floors at
    # dense + distinct experts — same math serve_bench prints
    qe = QuantizedTensor(jnp.zeros((2, E, K, N), jnp.int8),
                         jnp.zeros((2, E, K, 1), jnp.float32), "float32")
    qd = QuantizedTensor(jnp.zeros((2, K, N), jnp.int8),
                         jnp.zeros((2, K, 1), jnp.float32), "float32")
    tree = {"experts": qe, "dense": qd}
    top_k = 2
    floors = param_stream_bytes(tree, batch=B, top_k=top_k,
                                num_experts=E)
    dense_b = 2 * K * N + 4 * 2 * K
    expert_b = 2 * E * K * N + 4 * 2 * E * K
    distinct = min(B * top_k, E)
    assert floors["dense_int8_bytes"] == dense_b
    assert floors["expert_int8_bytes"] == expert_b
    assert floors["weights_floor_moe"] == \
        dense_b + distinct * (expert_b // E)
    assert floors["weights_floor_int8"] == dense_b + expert_b


# --------------------------------------------- PERF.md floor parity (2%)
def test_floors_match_perf_md_hand_values():
    """Acceptance: costmodel-derived byte floors for gpt2/llama/mixtral
    decode at bench shapes match the hand-computed
    ``weights_floor_int8``/``weights_floor_moe`` values within 2% —
    from shape-only abstract trees (eval_shape), nothing materialized.

    The mixtral anchors are PERF.md's PR 8 table literals (204.6 /
    741.3 MB); the dense-family anchors are the decode_profile /
    serve_bench inline formulas re-derived here over the same shapes.
    """
    from deepspeed_tpu.models.model import QuantizedTensor

    def inline_hand_bytes(qblocks):
        # the scripts' idiom: q bytes + 4-byte scales per quantized leaf
        is_q = lambda x: isinstance(x, QuantizedTensor)
        total = 0
        for leaf in jax.tree_util.tree_leaves(qblocks, is_leaf=is_q):
            if is_q(leaf):
                total += int(leaf.q.size) + 4 * int(leaf.s.size)
        return total

    # mixtral:1b-moe — PERF.md PR 8 table (DEC_MOE=1 decode_profile)
    from deepspeed_tpu.models.mixtral import mixtral_model
    m = mixtral_model("1b-moe")
    cfg = m.config
    q = abstract_quantized_blocks(m)
    f1 = param_stream_bytes(q, batch=1, top_k=cfg.top_k,
                            num_experts=cfg.num_experts)
    f4 = param_stream_bytes(q, batch=4, top_k=cfg.top_k,
                            num_experts=cfg.num_experts)
    assert abs(f1["weights_floor_moe"] - 204.6e6) / 204.6e6 < 0.02
    assert abs(f4["weights_floor_moe"] - 741.3e6) / 741.3e6 < 0.02
    assert abs(f1["weights_floor_int8"] - 741.3e6) / 741.3e6 < 0.02
    # B=1 streams 3.6x fewer expert bytes than all-E (the PR 8 ratio)
    assert 3.5 < f4["weights_floor_moe"] / f1["weights_floor_moe"] < 3.7

    # gpt2-1.3b / llama-7b — library vs the inline script math
    from deepspeed_tpu.models.gpt2 import gpt2_model
    from deepspeed_tpu.models.llama import llama_model
    for model in (gpt2_model("1.3b"), llama_model("7b")):
        qb = abstract_quantized_blocks(model)
        lib = param_stream_bytes(qb)["weights_floor_int8"]
        hand = inline_hand_bytes(qb)
        assert lib == hand                    # same walk, zero drift
        # decode_profile's measured-stream variant counts q bytes only;
        # the stored-form floor differs by exactly the scale overhead
        qonly = sum(int(leaf.q.size) for leaf in jax.tree_util.tree_leaves(
            qb, is_leaf=lambda x: isinstance(x, QuantizedTensor))
            if isinstance(leaf, QuantizedTensor))
        assert abs(lib - qonly) / qonly < 0.02     # 4/256 = 1.6%
    # PERF.md: gpt2-1.3B int8 weight stream "~1.3 GB/step-batch"
    g = param_stream_bytes(abstract_quantized_blocks(gpt2_model("1.3b")))
    assert 1.2e9 < g["weights_floor_int8"] < 1.4e9


# ------------------------------------------------------------- roofline
def test_hbm_table_and_override(monkeypatch):
    monkeypatch.setenv("DS_HBM_GBPS", "819")
    assert roofline.hbm_bytes_per_s() == 819e9

    class FakeDev:
        device_kind = "TPU v5e"
    assert roofline.hbm_bytes_per_s(FakeDev(), env={}) == 819e9
    assert roofline.hbm_bytes_per_s(
        type("D", (), {"device_kind": "cpu"})(), env={}) is None


def test_floor_and_classification():
    from deepspeed_tpu.telemetry.costmodel import CostReport
    r = CostReport(name="p", flops=2e12, hbm_bytes=819e9)
    # bandwidth term: 1 s at 819 GB/s; compute term: 0.01 s at 200 TF
    assert roofline.floor_seconds(r, 200e12, 819e9) == pytest.approx(1.0)
    assert roofline.classify(r, 200e12, 819e9) == "bandwidth_bound"
    r2 = CostReport(name="p2", flops=400e12, hbm_bytes=1e6)
    assert roofline.classify(r2, 200e12, 819e9) == "compute_bound"
    assert roofline.floor_seconds(r, None, None) is None
    assert roofline.classify(r, None, 819e9) is None
    # one known rate is enough for a floor
    assert roofline.floor_seconds(r, None, 819e9) == pytest.approx(1.0)


def test_publish_and_observe_gauges(monkeypatch):
    monkeypatch.setenv("DS_HBM_GBPS", "100")    # 100 GB/s synthetic
    from deepspeed_tpu.telemetry.costmodel import CostReport
    reg = MetricsRegistry()
    r = CostReport(name="serve/window:w1", flops=1000,
                   hbm_bytes=int(100e9 // 1000), pallas_launches=3)
    roofline.publish_report(reg, r)
    assert reg.get_gauge("perf/pallas_launches",
                         program="serve/window:w1") == 3
    # floor = 1 ms at 100 GB/s for 1e8 bytes... here hbm/bw = 1e-3 s
    assert reg.get_gauge("perf/floor_ms",
                         program="serve/window:w1") == pytest.approx(1.0)
    roofline.observe_achieved(reg, "serve/window:w1", 0.004)
    assert reg.get_gauge("perf/achieved_ms",
                         program="serve/window:w1") == pytest.approx(4.0)
    assert reg.get_gauge("perf/achieved_vs_floor",
                         program="serve/window:w1") == pytest.approx(4.0)
    # and the lock-free payload carries the same rows
    from deepspeed_tpu.telemetry.debug import perf_payload
    p = perf_payload()
    row = p["programs"]["serve/window:w1"]
    assert row["achieved_vs_floor"] == pytest.approx(4.0, rel=1e-3)
    assert row["bound"] == "bandwidth_bound" if p["peak_flops"] else True
    assert perf_payload({"program": "nope"})["programs"] == {}


def test_no_floor_on_cpu_without_override(monkeypatch):
    monkeypatch.delenv("DS_HBM_GBPS", raising=False)
    monkeypatch.delenv("DS_PEAK_FLOPS", raising=False)
    if jax.devices()[0].platform != "cpu":
        pytest.skip("CPU-only contract")
    from deepspeed_tpu.telemetry.costmodel import CostReport
    reg = MetricsRegistry()
    r = CostReport(name="p", flops=10, hbm_bytes=10)
    roofline.publish_report(reg, r)
    assert reg.get_gauge("perf/floor_ms", program="p") is None
    roofline.observe_achieved(reg, "p", 0.1)
    assert reg.get_gauge("perf/achieved_ms", program="p") is not None
    assert reg.get_gauge("perf/achieved_vs_floor", program="p") is None


# -------------------------------------------------- scheduler integration
def test_scheduler_registers_programs_and_gauges(monkeypatch):
    monkeypatch.setenv("DS_HBM_GBPS", "100")
    from deepspeed_tpu.runtime.config import ServingConfig
    from deepspeed_tpu.serving import (ContinuousBatchingScheduler,
                                       SamplingParams)
    m = tiny_gpt2()
    eng = deepspeed_tpu.init_inference(model=m,
                                       config={"dtype": "float32"})
    reg = MetricsRegistry()
    cfg = ServingConfig(block_size=8, num_blocks=64, max_num_seqs=2)
    sched = ContinuousBatchingScheduler(m, eng.params, cfg, registry=reg)
    rng = np.random.default_rng(0)
    for _ in range(2):
        sched.submit(rng.integers(1, 120, (6,)).astype(np.int32),
                     SamplingParams(max_new_tokens=3))
    sched.run_until_idle()
    reports = costmodel.get_reports()
    assert any(n.startswith("serve/prefill") for n in reports)
    # decode families are keyed per fused-step count k: a k-step scan
    # streams the weights k times, so each k owns a k-scaled byte model
    decode_names = [n for n in reports if n.startswith("serve/decode:k")]
    assert decode_names, reports
    for name in decode_names:
        k = int(name.rsplit("k", 1)[1])
        dec = reports[name]
        assert dec.flops > 0
        assert dec.hbm_bytes == \
            k * sched._cost_stream["weights_floor_bytes"]
        assert dec.detail["weight_passes"] == k
    observed = [n for n in decode_names
                if reg.get_gauge("perf/achieved_vs_floor",
                                 program=n) is not None]
    assert observed, decode_names
    prom = reg.render_prometheus()
    assert f'perf_achieved_vs_floor{{program="{observed[0]}"}}' in prom
    from deepspeed_tpu.telemetry.debug import perf_payload
    assert observed[0] in perf_payload()["programs"]


def test_scheduler_costmodel_off(monkeypatch):
    monkeypatch.setenv("DS_PERF_COSTMODEL", "0")
    from deepspeed_tpu.runtime.config import ServingConfig
    from deepspeed_tpu.serving import (ContinuousBatchingScheduler,
                                       SamplingParams)
    m = tiny_gpt2()
    eng = deepspeed_tpu.init_inference(model=m,
                                       config={"dtype": "float32"})
    reg = MetricsRegistry()
    sched = ContinuousBatchingScheduler(
        m, eng.params, ServingConfig(block_size=8, num_blocks=64,
                                     max_num_seqs=2), registry=reg)
    sched.submit(np.arange(1, 7, dtype=np.int32),
                 SamplingParams(max_new_tokens=2))
    sched.run_until_idle()
    assert costmodel.get_reports() == {}
    assert "perf_flops" not in reg.render_prometheus()


# ----------------------------------------------------- engine integration
def test_engine_train_step_cost_report():
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_gpt2(),
                                               config=base_config())
    engine.train_batch(iter(random_batches(1, seed=0)))
    # made for whoever asks, not by the step (tests/test_step_cost_report.py)
    assert costmodel.get_report("train/step") is None
    from deepspeed_tpu.telemetry import tracing
    assert tracing.get_program_cost() is costmodel.get_report("train/step")
    rep = costmodel.get_report("train/step")
    assert rep is not None and rep.flops > 0
    assert engine.telemetry_registry.get_gauge(
        "perf/flops", program="train/step") == float(rep.flops)
    # the call's duration is a dispatch time: the step is held to no floor
    engine.train_batch(iter(random_batches(1, seed=1)))
    assert engine.telemetry_registry.get_gauge(
        "perf/achieved_ms", program="train/step") is None


def test_postmortem_bundle_has_perf_json(tmp_path):
    from deepspeed_tpu.resilience.postmortem import (reset_rate_limit,
                                                     write_postmortem)
    from deepspeed_tpu.telemetry.costmodel import CostReport
    register_report(CostReport(name="serve/decode", flops=10,
                               hbm_bytes=10))
    reset_rate_limit()
    path = write_postmortem(str(tmp_path), "perf test")
    assert path is not None
    perf = json.load(open(os.path.join(path, "perf.json")))
    assert "serve/decode" in perf["programs"]
    man = json.load(open(os.path.join(path, "manifest.json")))
    assert man["files"]["perf.json"] is True


# ------------------------------------------------------------ perf_report
def test_perf_report_renders_trace_with_floors(tmp_path, capsys):
    from scripts.perf_report import main
    events = []
    t = 0.0
    for _ in range(3):
        events.append({"name": "serve/step", "ph": "B", "ts": t,
                       "pid": 1, "tid": 1})
        events.append({"name": "serve/window", "ph": "B", "ts": t + 100,
                       "pid": 1, "tid": 1})
        events.append({"name": "serve/window", "ph": "E", "ts": t + 900,
                       "pid": 1, "tid": 1})
        events.append({"name": "serve/step", "ph": "E", "ts": t + 1000,
                       "pid": 1, "tid": 1})
        t += 1500
    trace = str(tmp_path / "trace.json")
    json.dump({"traceEvents": events}, open(trace, "w"))
    perf = str(tmp_path / "perf.json")
    json.dump({"programs": {"serve/window:w1": {
        "floor_ms": 0.2, "bound": "bandwidth_bound",
        "pallas_launches": 3}}}, open(perf, "w"))
    assert main([trace, "--perf", perf, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    spans = out["spans"]
    assert spans["serve/step"]["count"] == 3
    assert spans["serve/window"]["mean_ms"] == pytest.approx(0.8)
    # the w1 program joined its span family's stem
    assert spans["serve/window"]["floor_ms"] == 0.2
    assert spans["serve/window"]["mean_vs_floor"] == pytest.approx(4.0)
    assert main([trace, "--top", "5"]) == 0       # table mode renders
    assert main([str(tmp_path / "missing.json")]) == 2
    # several buckets of one family: the join survives and takes the
    # lowest (most conservative) floor
    json.dump({"programs": {
        "serve/window:w2": {"floor_ms": 0.3, "bound": "bandwidth_bound"},
        "serve/window:w8": {"floor_ms": 0.2, "bound": "bandwidth_bound"},
    }}, open(perf, "w"))
    capsys.readouterr()                   # drain the table-mode output
    assert main([trace, "--perf", perf, "--json"]) == 0
    out2 = json.loads(capsys.readouterr().out)
    assert out2["spans"]["serve/window"]["floor_ms"] == 0.2


# ------------------------------------------------------------ bench ledger
def test_bench_record_schema_and_ledger(tmp_path, monkeypatch):
    from scripts.bench_util import (append_ledger, bench_meta,
                                    ledger_enabled, make_record)
    monkeypatch.setenv("DS_BENCH_DIR", str(tmp_path / "B"))
    monkeypatch.delenv("DS_BENCH_LEDGER", raising=False)
    assert not ledger_enabled()
    monkeypatch.setenv("DS_BENCH_LEDGER", "1")
    assert ledger_enabled()
    meta = bench_meta()
    assert meta["schema"] == "ds-bench/1"
    assert meta["device_kind"] and meta["device_count"] >= 1
    rec = make_record("m_tok_s", 100.0, unit="tok/s",
                      direction="higher_better",
                      detail={"model": "gpt2:tiny"})
    path = append_ledger(rec)
    assert path == str(tmp_path / "B" / "ledger.jsonl")
    got = json.loads(open(path).read().strip())
    assert got["metric"] == "m_tok_s" and got["meta"]["schema"]
    with pytest.raises(ValueError):
        make_record("m", 1.0, direction="sideways")


def _ledger_lines(tmp_path, values, kind="cpu", model="gpt2:tiny"):
    path = str(tmp_path / "ledger.jsonl")
    with open(path, "a") as f:
        for v in values:
            f.write(json.dumps({
                "metric": "m_tok_s", "value": v,
                "direction": "higher_better",
                "detail": {"model": model},
                "meta": {"schema": "ds-bench/1", "git_rev": "abc",
                         "device_kind": kind, "device_count": 1}}) + "\n")
    return path


def test_bench_compare_history_gate(tmp_path):
    from scripts.bench_compare import main
    led = _ledger_lines(tmp_path, [100.0, 102.0, 98.0, 101.0, 99.0])
    ok = str(tmp_path / "ok.json")
    json.dump({"metric": "m_tok_s", "value": 97.0,
               "direction": "higher_better",
               "detail": {"model": "gpt2:tiny"},
               "meta": {"schema": "ds-bench/1", "device_kind": "cpu",
                        "device_count": 1}}, open(ok, "w"))
    assert main(["--history", led, ok, "-q"]) == 0
    # synthetic >10% regression against the rolling median (100)
    bad = str(tmp_path / "bad.json")
    json.dump({"metric": "m_tok_s", "value": 85.0,
               "direction": "higher_better",
               "detail": {"model": "gpt2:tiny"},
               "meta": {"schema": "ds-bench/1", "device_kind": "cpu",
                        "device_count": 1}}, open(bad, "w"))
    assert main(["--history", led, bad, "-q"]) == 1
    # declared direction wins over the _s-suffix-free name inference:
    # lower_better means 85 < 100 is an improvement
    low = str(tmp_path / "low.json")
    json.dump({"metric": "m_latency", "value": 120.0,
               "direction": "lower_better",
               "meta": {"schema": "ds-bench/1", "device_kind": "cpu",
                        "device_count": 1}}, open(low, "w"))
    led2 = str(tmp_path / "ledger2.jsonl")
    with open(led2, "w") as f:
        f.write(json.dumps({
            "metric": "m_latency", "value": 100.0,
            "direction": "lower_better",
            "meta": {"schema": "ds-bench/1", "device_kind": "cpu",
                     "device_count": 1}}) + "\n")
    assert main(["--history", led2, low, "-q"]) == 1   # 20% worse


def test_bench_compare_refuses_cross_device(tmp_path):
    """Acceptance: a CPU-smoke record must not gate an on-chip one —
    exit 2 with a diagnostic, both pairwise and against history."""
    from scripts.bench_compare import main
    cpu = str(tmp_path / "cpu.json")
    tpu = str(tmp_path / "tpu.json")
    json.dump({"metric": "m_tok_s", "value": 100.0,
               "meta": {"schema": "ds-bench/1", "device_kind": "cpu",
                        "device_count": 1}}, open(cpu, "w"))
    json.dump({"metric": "m_tok_s", "value": 5000.0,
               "meta": {"schema": "ds-bench/1",
                        "device_kind": "TPU v5e", "device_count": 1}},
              open(tpu, "w"))
    assert main([cpu, tpu, "-q"]) == 2
    # history holds ONLY cpu records; current is on-chip -> refuse
    led = _ledger_lines(tmp_path, [100.0, 101.0], kind="cpu")
    tpu2 = str(tmp_path / "tpu2.json")
    json.dump({"metric": "m_tok_s", "value": 5000.0,
               "detail": {"model": "gpt2:tiny"},
               "meta": {"schema": "ds-bench/1",
                        "device_kind": "TPU v5e", "device_count": 1}},
              open(tpu2, "w"))
    assert main(["--history", led, tpu2, "-q"]) == 2
    # pre-schema records (no meta) keep comparing
    old_style = str(tmp_path / "old.json")
    json.dump({"metric": "m_tok_s", "value": 100.0}, open(old_style, "w"))
    assert main([old_style, old_style, "-q"]) == 0


def test_history_tolerates_mixed_model_ledger(tmp_path):
    """A ledger legitimately holding several model shapes for one
    metric (smoke + full-size runs on one box) must NOT trip the
    cross-model refusal — the rolling baseline is already filtered to
    the current record's shape."""
    from scripts.bench_compare import main
    led = _ledger_lines(tmp_path, [100.0, 101.0], model="gpt2:tiny")
    _ledger_lines(tmp_path, [10.0, 11.0], model="gpt2:350m")
    cur = str(tmp_path / "cur.json")
    json.dump({"metric": "m_tok_s", "value": 99.0,
               "direction": "higher_better",
               "detail": {"model": "gpt2:tiny"},
               "meta": {"schema": "ds-bench/1", "device_kind": "cpu",
                        "device_count": 1}}, open(cur, "w"))
    # baseline comes from the tiny-model records (median 100.5), not
    # the 350m ones — 99 is within threshold
    assert main(["--history", led, cur, "-q"]) == 0


def test_schema_version_mismatch_refused(tmp_path):
    from scripts.bench_compare import main, meta_conflict
    assert meta_conflict({"schema": "ds-bench/1"},
                         {"schema": "ds-bench/2"}) is not None
    a = str(tmp_path / "a.json")
    b = str(tmp_path / "b.json")
    json.dump({"metric": "m", "value": 1.0,
               "meta": {"schema": "ds-bench/1"}}, open(a, "w"))
    json.dump({"metric": "m", "value": 1.0,
               "meta": {"schema": "ds-bench/2"}}, open(b, "w"))
    assert main([a, b, "-q"]) == 2


def test_achieved_mean_excludes_warmup_sample():
    """The first observation of a program carries compile + the
    analysis trace; the running mean must be over warm executions."""
    costmodel.record_achieved("p", 10.0)         # compile-tainted
    costmodel.record_achieved("p", 0.002)
    costmodel.record_achieved("p", 0.004)
    register_report(costmodel.CostReport(name="p", flops=1, hbm_bytes=1))
    row = roofline.perf_table()["programs"]["p"]
    assert row["achieved_count"] == 3
    assert row["achieved_mean_ms"] == pytest.approx(3.0)   # (2+4)/2
    assert row["achieved_ms"] == pytest.approx(4.0)


def test_bench_compare_refuses_cross_model(tmp_path):
    from scripts.bench_compare import main
    a = str(tmp_path / "a.json")
    b = str(tmp_path / "b.json")
    json.dump({"metric": "m_tok_s", "value": 100.0,
               "detail": {"model": "gpt2:125m"}}, open(a, "w"))
    json.dump({"metric": "m_tok_s", "value": 50.0,
               "detail": {"model": "gpt2:1.3b"}}, open(b, "w"))
    assert main([a, b, "-q"]) == 2


def test_ledger_round_trip_via_bench_script(tmp_path):
    """Satellite: bench script → BENCH/ record → history gate, in
    CPU-smoke mode (ckpt_bench CKPT_SMOKE=1 writes a real BenchRecord;
    a synthetic regressed record then trips the history gate)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", CKPT_SMOKE="1",
               ASYNC="0", DS_BENCH_LEDGER="1",
               DS_BENCH_DIR=str(tmp_path))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "ckpt_bench.py")],
        env=env, capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    led = str(tmp_path / "ledger.jsonl")
    recs = [json.loads(line) for line in open(led) if line.strip()]
    assert recs and recs[-1]["metric"] == "ckpt_bench_sync"
    meta = recs[-1]["meta"]
    assert meta["schema"] == "ds-bench/1" and meta["device_kind"]
    # gate a synthetic 10x step-time regression against the history
    from scripts.bench_compare import main
    bad = dict(recs[-1])
    bad["value"] = recs[-1]["value"] * 10
    cur = str(tmp_path / "cur.json")
    json.dump(bad, open(cur, "w"))
    assert main(["--history", led, cur, "-q",
                 "--metrics", "ckpt_bench_sync"]) == 1
    good = dict(recs[-1])
    cur2 = str(tmp_path / "cur2.json")
    json.dump(good, open(cur2, "w"))
    assert main(["--history", led, cur2, "-q",
                 "--metrics", "ckpt_bench_sync"]) == 0
