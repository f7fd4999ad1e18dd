"""What the MiniCPM-SALA cell brings to the benchmark: its required
operations against a count by hand (the keys KEPT, averaged over the
traffic's spans), its three metric files on hand-made events through the
readers that were there, and its metrics being its own cell's alone."""
import importlib
import json

import numpy as np
import pytest

from harness import datagen, flops, trace as tr
from harness.manifest import Manifest
from required_ops import minicpm_sala as ops
from test_moe_readers import row
from test_step_readers import context, program  # noqa: F401 (a fixture)
from test_trace_reduction import KERNEL, hlo

CELL = "minicpm-sala.packed-s16384-longdocs"
METRICS = ["sparse.select_ms_per_step", "sparse.attend_roofline",
           "lightning.scan_roofline"]
BLOCK = "jit(train_step)/ds.fwd_bwd/{}ds.block/{}/op"


def sizes():
    with open(Manifest().path("configs", "minicpm-sala.json")) as f:
        return json.load(f)["model"]


def value(metric, ctx):
    s = Manifest().layer_metric(metric)
    return importlib.import_module(
        "layer_metrics.readers." + s["reader"]).read(ctx, s["params"])


def test_required_operations_by_hand():
    s = sizes()
    assert ops._kinds(s) == (1, 3)
    sparse = 3 * 4096 * 4096 + 2 * 4096 * 256
    lightning = 5 * 4096 * 4096
    assert (ops.sparse_weights(s), ops.lightning_weights(s)) \
        == (sparse, lightning) == (52_428_800, 83_886_080)
    # one span of 1,000 tokens (no traffic file has that S_eff): under
    # dense_len, every query keeps its p + 1 causal keys, 500.5 on average,
    # and has scored the (p - 31) // 16 + 1 windows that ended before it
    assert ops.kept_keys_per_query(s, 1000.0) == pytest.approx(500.5)
    windows = np.maximum(0, (np.arange(1000) - 31) // 16 + 1).mean()
    assert ops.scored_windows_per_query(s, 1000.0) == pytest.approx(windows)
    weights = sparse + 3 * lightning + 4 * 3 * 4096 * 16384 + 4096 * 9181
    want = 6 * weights + 3 * 4 * 32 * 128 * 500.5 \
        + 3 * 3 * 4 * 32 * 128 * 128 + 2 * 32 * 128 * windows
    assert ops.train_flops_per_token(s, 1000.0) == pytest.approx(want)
    assert flops.resolve("minicpm_sala:train_flops_per_token") \
        is ops.train_flops_per_token
    # one span of 16,384: past dense_len, so a query beyond its 64th block
    # keeps 63 whole blocks and its own block up to itself
    p = np.arange(16384)
    kept = np.where(p // 64 + 1 > 64, 63 * 64 + p % 64 + 1, p + 1).mean()
    assert ops.kept_keys_per_query(s, 16384.0) == pytest.approx(kept)
    assert 3500 < kept < 3600
    # ... and a span under dense_len keeps every causal key
    assert ops.kept_keys_per_query(s, 8000.0) == pytest.approx(4000.5)


def test_the_count_is_of_the_cells_own_traffic():
    """Handed the traffic mix's S_eff, the functions find its spans again
    (the sample ``effective_context`` averages over) and count the keys
    kept over them: 3,283 a query where a dense causal layer would see
    S_eff / 2 = 6,262."""
    s = sizes()
    traffic = Manifest().traffic("packed-s16384-longdocs")
    s_eff = datagen.effective_context(traffic)
    assert s_eff == pytest.approx(12524.8, abs=0.1)
    lens = ops._span_lengths(s_eff)
    assert lens.sum() == 4096 * 16384 and len(lens) > 4096
    kept = ops.kept_keys_per_query(s, s_eff)
    assert kept == pytest.approx(3283.0, abs=1.0) and kept < s_eff / 3
    # 7.07 GFLOP a token, 0.16 of it the sparse layer's attention over the
    # keys kept; at the keys the masked-chunks lowering visits (10,240) it
    # would be 0.50
    assert ops.train_flops_per_token(s, s_eff) == pytest.approx(7.065e9,
                                                                rel=1e-3)
    assert 3 * 4 * 32 * 128 * kept == pytest.approx(0.161e9, rel=1e-2)
    # the floors of a step's three passes: the attend stage is bound by the
    # matrix unit (17.9 ms), the scan by memory (7.4 ms)
    f, b = ops.sparse_attend_ops(16384, s, s_eff, ["fwd", "fwd", "bwd"])
    assert 1e3 * f / 197e12 == pytest.approx(17.9, rel=0.01)
    assert 1e3 * b / 819e9 < 2.0
    f, b = ops.lightning_scan_ops(16384, s, s_eff, ["fwd", "fwd", "bwd"])
    assert 1e3 * b / 819e9 == pytest.approx(7.37, rel=0.01)
    assert 1e3 * f / 197e12 == pytest.approx(2.09, rel=0.01)


def test_a_moved_sample_is_an_error_not_one_span(monkeypatch):
    """``_span_lengths`` repeats ``effective_context``'s sampling: where
    that moves (here: another S_eff for the same file) the count does not
    fall back to one span of S_eff tokens, it says so; a mix no file of
    the benchmark holds (a rehearsal's) still falls back."""
    traffic = Manifest().traffic("packed-s16384-longdocs")
    real = datagen.effective_context
    moved = real(traffic) + 0.5
    monkeypatch.setattr(
        datagen, "effective_context",
        lambda t: moved if t["seq_len"] == 16384 else real(t))
    ops._span_lengths.cache_clear()
    try:
        with pytest.raises(RuntimeError, match="sample here as there"):
            ops._span_lengths(moved)
        assert ops._span_lengths(37.25).tolist() == [37]
    finally:
        ops._span_lengths.cache_clear()


def synthetic():
    ops_ = [(0, 100, hlo("fusion.1", "fusion")),         # sparse_attn/qkv
            (100, 300, hlo("fusion.2", "fusion")),       # select
            (300, 900, hlo("fusion.3", "fusion")),       # attend
            (900, 1000, hlo("fusion.4", "fusion")),      # lightning/in_proj
            (1000, 1200, hlo("ds_ssd_fwd.1", "custom-call", KERNEL)),
            (1200, 1300, hlo("fusion.5", "fusion")),     # select, recompute
            (1300, 2300, hlo("fusion.6", "fusion")),     # attend, backward
            (2300, 2700, hlo("ds_ssd_bwd.1", "custom-call", KERNEL)),
            (2700, 2800, hlo("fusion.7", "fusion"))]     # mlp
    dev = tr.DeviceTrace("/device:TPU:0", {
        tr.OPS: ops_, tr.MODULES: [(0, 2800, "jit_train_step(1)")]})
    at = lambda part, outer="": row(BLOCK.format(outer, part))
    back = "transpose(jvp())/"
    table = {"fusion.1": at("sparse_attn/qkv"),
             "fusion.2": at("sparse_attn/select"),
             "fusion.3": at("sparse_attn/attend"),
             "fusion.4": at("lightning/in_proj"),
             "ds_ssd_fwd.1": row(BLOCK.format("", "lightning/scan"),
                                 "ds_ssd_fwd"),
             "fusion.5": at("sparse_attn/select", "rematted_computation/"),
             "fusion.6": at("sparse_attn/attend", back),
             "ds_ssd_bwd.1": row(BLOCK.format(back, "lightning/scan"),
                                 "ds_ssd_bwd"),
             "fusion.7": at("mlp")}
    return tr.Trace([dev], {}), table


def test_known_answers_on_hand_made_events(program):  # noqa: F811
    trace, table = synthetic()
    program(table)
    ctx = context(trace, steps=2)
    ctx["model"] = sizes()
    ctx["peaks"] = {**ctx["peaks"], "hbm_bytes_per_s": 819e9}
    ms = lambda ns: ns * 1e-6 / 2
    assert value("sparse.select_ms_per_step", ctx) == pytest.approx(
        ms(200 + 100))
    tokens, s_eff = ctx["tokens_per_step_per_chip"], ctx["s_eff"]
    passes = ["fwd", "fwd", "bwd"]

    def share(fn, ns):
        need_flops, need_bytes = fn(tokens, ctx["model"], s_eff, passes)
        floor = 1e3 * max(need_flops / ctx["peaks"]["bf16_flops_per_s"],
                          need_bytes / ctx["peaks"]["hbm_bytes_per_s"])
        return 100 * floor / ms(ns)
    assert value("sparse.attend_roofline", ctx) == pytest.approx(
        share(ops.sparse_attend_ops, 600 + 1000))
    assert value("lightning.scan_roofline", ctx) == pytest.approx(
        share(ops.lightning_scan_ops, 200 + 400))


@pytest.mark.parametrize("metric", METRICS)
def test_no_device_plane_reads_nothing(metric):
    """What the parent commit's traced runs need of a metric new here: a
    trace without a device plane gives None and does not raise."""
    ctx = context(tr.Trace([], {}), steps=2)
    ctx["model"] = sizes()
    ctx["peaks"] = {**ctx["peaks"], "hbm_bytes_per_s": 819e9}
    assert value(metric, ctx) is None


def test_the_metrics_are_the_new_cells_alone():
    manifest = Manifest()
    for m in manifest.data["per_layer"]:
        if m["name"] in METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "tokens_per_s_per_chip"
    assert set(METRICS) <= {
        m["name"] for m in manifest.metrics("per_layer", CELL)}
    assert len(manifest.data["per_layer"]) == 128      # the contract's most
    assert manifest.data["workloads"][-1]["name"] == CELL
    assert manifest.workload(CELL)["chips"] == 1
    config = manifest.config("minicpm-sala")
    assert config["reference"] == "minicpm_sala"
    assert config["flops"]["train"] == "minicpm_sala:train_flops_per_token"
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    traffic = manifest.traffic("packed-s16384-longdocs")
    assert traffic["micro_batch_per_chip"] \
        * traffic["gradient_accumulation_steps"] * traffic["seq_len"] == 16384
    assert traffic["driver"] == "train_steps_counted"
