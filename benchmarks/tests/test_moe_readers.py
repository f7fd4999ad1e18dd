"""The readers the OLMoE cell brings (step_op_time, grouped_rows): known
answers on hand-made events and on a hand-made account, and nothing —
not an error — from a program that has neither."""
import importlib

import pytest

from harness import trace as tr
from harness.manifest import Manifest
from layer_metrics.readers import grouped_rows, step_op_time, step_phase
from test_step_readers import context, program  # noqa: F401 (a fixture)
from test_trace_reduction import KERNEL, hlo

CELL = "olmoe-1b-7b.packed-s4096-gas8"
MOE_METRICS = ["moe.ggemm_ms_per_step", "moe.ggemm_fwd_roofline",
               "moe.ggemm_bwd_roofline", "moe.dispatch_ms_per_step",
               "moe.padded_row_share_pct"]
SCOPE = "jit(train_step)/ds.fwd_bwd/{}while/body/ds.block/mlp/{}/op"


def value(metric, ctx):
    s = Manifest().layer_metric(metric)
    return importlib.import_module(
        "layer_metrics.readers." + s["reader"]).read(ctx, s["params"])


def row(scope, kernel=None):
    return {"scope": scope, "phase": "forward", "kernel": kernel,
            "collective": None, "wire_bytes": None}


def synthetic():
    """One run of the step's module, 2 'steps': a scatter under dispatch
    0..100, the three grouped kernels 100..400 / 400..600 / 600..700, the
    flash kernel 700..800, a gather under combine (backward) 800..950, a
    router op 950..1000; then a dispatch-scoped name in ANOTHER module."""
    ops = [(0, 100, hlo("scatter.1", "scatter")),
           (100, 400, hlo("ds_ggemm_fwd.1", "custom-call", KERNEL)),
           (400, 600, hlo("ds_ggemm_dx.1", "custom-call", KERNEL)),
           (600, 700, hlo("ds_ggemm_dw.1", "custom-call", KERNEL)),
           (700, 800, hlo("ds_flash_fwd.1", "custom-call", KERNEL)),
           (800, 950, hlo("fusion.7", "fusion")),
           (950, 1000, hlo("fusion.8", "fusion")),
           (1100, 1200, hlo("scatter.1", "scatter"))]
    modules = [(0, 1000, "jit_train_step(1)"), (1100, 1200, "jit_other(2)")]
    dev = tr.DeviceTrace("/device:TPU:0", {tr.OPS: ops, tr.MODULES: modules})
    table = {
        "scatter.1": row(SCOPE.format("", "dispatch")),
        "ds_ggemm_fwd.1": row(SCOPE.format("", "experts"), "ds_ggemm_fwd"),
        "ds_ggemm_dx.1": row(SCOPE.format("", "experts"), "ds_ggemm_dx"),
        "ds_ggemm_dw.1": row(SCOPE.format("", "experts"), "ds_ggemm_dw"),
        "ds_flash_fwd.1": row("jit(train_step)/ds.block/attn/x",
                              "ds_flash_fwd"),
        "fusion.7": row(SCOPE.format("transpose(jvp())/", "combine")),
        "fusion.8": row(SCOPE.format("", "router"))}
    return tr.Trace([dev], {}), table


def test_known_answers_on_hand_made_events(program):  # noqa: F811
    trace, table = synthetic()
    program(table)
    ctx = context(trace, steps=2)
    ctx["model"] = {"num_layers": 2, "d_model": 256, "d_ff": 128, "top_k": 2}
    ms = lambda ns: ns * 1e-6 / 2
    assert value("moe.ggemm_ms_per_step", ctx) == pytest.approx(ms(600))
    assert value("moe.dispatch_ms_per_step", ctx) == pytest.approx(ms(250))
    # 512 tokens x 2 layers x top_k 2 x D 256 x F 128: 6 a forward pass
    # and 4 a recompute, which multiplies no output matrix
    need = 512 * 2 * 2 * 256 * 128
    assert value("moe.ggemm_fwd_roofline", ctx) == pytest.approx(
        100 * (10 * need / 197e12 * 1e3) / ms(300))
    assert value("moe.ggemm_bwd_roofline", ctx) == pytest.approx(
        100 * (12 * need / 197e12 * 1e3) / ms(300))


def test_nothing_of_the_map_ran_is_a_broken_join(program):  # noqa: F811
    trace, table = synthetic()
    program({name: row("jit(train_step)/ds.block/attn/x")
             for name in table})
    with pytest.raises(step_phase.BrokenJoin):
        value("moe.dispatch_ms_per_step", context(trace, steps=2))


def test_no_device_plane_reads_nothing():
    ctx = context(tr.Trace([], {}), steps=2)
    assert step_op_time.read(ctx, {"program": "train/step", "module": "x",
                                   "scope": "/dispatch/"}) is None


def test_padded_row_share_from_the_programs_account(monkeypatch):
    from deepspeed_tpu.telemetry import tracing
    params = Manifest().layer_metric("moe.padded_row_share_pct")["params"]
    monkeypatch.setattr(tracing, "grouped_gemm_rows", lambda name: None)
    assert grouped_rows.read({}, params) is None       # no grouped dispatch
    monkeypatch.setattr(tracing, "grouped_gemm_rows", lambda name: {
        "routed_rows_per_call": 32768, "padded_rows_per_call": 40960})
    assert grouped_rows.read({}, params) == pytest.approx(20.0)
    # a program from before the account: the metric is left out
    monkeypatch.delattr(tracing, "grouped_gemm_rows")
    assert grouped_rows.read({}, params) is None


def test_the_metrics_are_the_new_cells_alone():
    manifest = Manifest()
    for m in manifest.data["per_layer"]:
        if m["name"] in MOE_METRICS:
            assert m["workloads"] == [CELL] and m["layer"] == "moe"
    assert set(MOE_METRICS) <= {
        m["name"] for m in manifest.metrics("per_layer", CELL)}
