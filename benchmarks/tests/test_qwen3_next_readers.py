"""What the Qwen3-Next cell brings to the benchmark: its required
operations against a count by hand, the new reader (step_scope_roofline)
and the held experts' metrics on hand-made events and a hand-made account,
its metrics being its own cell's alone, and the driver that holds a run
to the model's step counts."""
import importlib
import json

import numpy as np
import pytest

from harness import flops, trace as tr
from harness.manifest import Manifest
from layer_metrics.readers import step_scope_roofline
from required_ops import qwen3_next as ops
from test_moe_readers import row
from test_step_readers import context, program  # noqa: F401 (a fixture)
from test_trace_reduction import KERNEL, hlo

CELL = "qwen3-next-80b-a3b.packed-s8192-gas2"
METRICS = ["linattn.layer_ms_per_step", "linattn.scan_ms_per_step",
           "linattn.scan_roofline", "linattn.conv_ms_per_step",
           "attention.full_layer_flash_fwd_roofline",
           "attention.full_layer_flash_bwd_roofline",
           "moe.held_ggemm_ms_per_step", "moe.held_ggemm_fwd_roofline",
           "moe.held_ggemm_bwd_roofline", "moe.shared_expert_ms_per_step",
           "moe.held_dispatch_ms_per_step", "moe.held_padded_row_share_pct"]
BLOCK = "jit(train_step)/ds.fwd_bwd/{}while/body/ds.block/{}/op"


def sizes():
    with open(Manifest().path("configs", "qwen3-next-80b-a3b.json")) as f:
        return json.load(f)["model"]


def value(metric, ctx):
    s = Manifest().layer_metric(metric)
    return importlib.import_module(
        "layer_metrics.readers." + s["reader"]).read(ctx, s["params"])


def test_required_operations_by_hand():
    s = sizes()
    linear = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 4096 * 2048
    full = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    experts = 2048 * 512 + 3 * 2048 * 512 + 2048 \
        + 10 * 32 / 512 * 3 * 2048 * 512
    want = 6 * (3 * linear + full + 4 * experts + 2048 * 18992) \
        + 3 * 3 * 32 * 6 * 128 * 128 + 6 * 4096 * 1000
    assert ops.train_flops_per_token(s, 1000) == pytest.approx(want)
    assert flops.resolve("qwen3_next:train_flops_per_token") \
        is ops.train_flops_per_token
    # one attention layer of width 4096, where causal_attention_flops
    # counts num_layers * d_model = 4 * 2048: twice as much
    assert ops.full_layer_attention_flops(100, s, 1000, ["fwd"]) \
        == pytest.approx(0.5 * 4 * 100 * 4096 * 1000)
    assert flops.causal_attention_flops(100, s, 1000, ["fwd"]) \
        == pytest.approx(2 * ops.full_layer_attention_flops(
            100, s, 1000, ["fwd"]))
    # 0.625 held experts a token, three matrices, four layers
    assert ops.held_ffn_flops(100, s, 0, ["fwd", "bwd"]) \
        == pytest.approx(18 * 100 * 4 * 0.625 * 2048 * 512)
    need, moved = ops.delta_rule_ops(100, s, 0, ["fwd", "fwd", "bwd"])
    assert need == pytest.approx(300 * 32 * 6 * 128 * 128 * 4)
    inputs = 2 * (2 * 2048 + 4096) + 8 * 32
    assert moved == pytest.approx(300 * (4 * inputs + 3 * 2 * 4096))


def synthetic():
    ops_ = [(0, 400, hlo("fusion.1", "fusion")),         # delta rule, fwd
            (400, 500, hlo("fusion.2", "fusion")),       # conv
            (500, 600, hlo("fusion.3", "fusion")),       # in_proj
            (600, 900, hlo("fusion.4", "fusion")),       # delta rule, bwd
            (900, 1000, hlo("ds_flash_fwd.1", "custom-call", KERNEL)),
            (1000, 1100, hlo("fusion.5", "fusion")),     # shared expert
            (1100, 1200, hlo("ds_ggemm_fwd.1", "custom-call", KERNEL)),
            (1200, 1250, hlo("sort.1", "sort")),         # the held plan
            (1250, 1400, hlo("fusion.6", "fusion"))]     # sum into tokens
    dev = tr.DeviceTrace("/device:TPU:0", {
        tr.OPS: ops_, tr.MODULES: [(0, 1400, "jit_train_step(1)")]})
    lin = lambda part, outer="": row(
        BLOCK.format(outer, "linear_attn/" + part))
    table = {"fusion.1": lin("delta_rule/while/body"),
             "fusion.2": lin("conv"), "fusion.3": lin("in_proj"),
             "fusion.4": lin("delta_rule", "transpose(jvp())/"),
             "ds_flash_fwd.1": row(BLOCK.format("", "attn"),
                                   "ds_flash_fwd"),
             "fusion.5": row(BLOCK.format("", "mlp/shared_expert")),
             "ds_ggemm_fwd.1": row(BLOCK.format("", "mlp/experts"),
                                   "ds_ggemm_fwd"),
             "sort.1": row(BLOCK.format("", "mlp/dispatch")),
             "fusion.6": row(BLOCK.format("", "mlp/combine"))}
    return tr.Trace([dev], {}), table


def test_known_answers_on_hand_made_events(program):  # noqa: F811
    trace, table = synthetic()
    program(table)
    ctx = context(trace, steps=2)
    ctx["model"] = sizes()
    ctx["peaks"] = {**ctx["peaks"], "hbm_bytes_per_s": 819e9}
    ms = lambda ns: ns * 1e-6 / 2
    assert value("linattn.layer_ms_per_step", ctx) == pytest.approx(ms(900))
    assert value("linattn.scan_ms_per_step", ctx) == pytest.approx(ms(700))
    assert value("linattn.conv_ms_per_step", ctx) == pytest.approx(ms(100))
    assert value("moe.shared_expert_ms_per_step", ctx) \
        == pytest.approx(ms(100))
    assert value("moe.held_ggemm_ms_per_step", ctx) == pytest.approx(ms(100))
    assert value("moe.held_dispatch_ms_per_step", ctx) \
        == pytest.approx(ms(200))
    tokens, s_eff = ctx["tokens_per_step_per_chip"], ctx["s_eff"]
    need, moved = ops.delta_rule_ops(tokens, ctx["model"], s_eff,
                                     ["fwd", "fwd", "bwd"])
    peaks = ctx["peaks"]
    floor = max(need / peaks["bf16_flops_per_s"],
                moved / peaks["hbm_bytes_per_s"])
    assert moved / peaks["hbm_bytes_per_s"] > need / peaks[
        "bf16_flops_per_s"]                  # memory is the floor here
    assert value("linattn.scan_roofline", ctx) == pytest.approx(
        100 * floor * 1e3 / ms(700))
    assert value("attention.full_layer_flash_fwd_roofline", ctx) \
        == pytest.approx(100 * ops.full_layer_attention_flops(
            tokens, ctx["model"], s_eff, ["fwd", "fwd"])
            / peaks["bf16_flops_per_s"] * 1e3 / ms(100))


def test_no_device_plane_reads_nothing():
    ctx = context(tr.Trace([], {}), steps=2)
    params = Manifest().layer_metric("linattn.scan_roofline")["params"]
    assert step_scope_roofline.read(ctx, params) is None


def test_padded_share_from_the_programs_account(monkeypatch):
    from deepspeed_tpu.telemetry import tracing
    # the cell's own shapes: 10,240 expected held rows in a plan of
    # held_rows_bound 20,480 + 32 tiles of 128
    monkeypatch.setattr(tracing, "grouped_gemm_rows", lambda name: {
        "routed_rows_per_call": 10240, "padded_rows_per_call": 24576,
        "held_rows_bound": 20480, "experts_held": 32,
        "experts_routed": 512})
    assert value("moe.held_padded_row_share_pct", {}) \
        == pytest.approx(100 * 14336 / 24576)
    monkeypatch.setattr(tracing, "grouped_gemm_rows", lambda name: None)
    assert value("moe.held_padded_row_share_pct", {}) is None


@pytest.mark.parametrize("counts, token, correct", [
    ({"moe/rows_over_bound": 0}, (1e-3, 2e-3), True),
    ({"moe/rows_over_bound": 3}, (1e-3, 2e-3), False),
    ({"moe/rows_over_bound": 0}, (3e-3, 2e-3), False),
    (None, (None, None), True)])
def test_what_the_counted_driver_holds_a_run_to(monkeypatch, capsys, counts,
                                                token, correct):
    """train_steps_counted is train_steps' run, then the engine's counts
    and the token-by-token distance from the reference: a count that is
    not zero, or a distance over the limit, fails the run; a program from
    before either is left as train_steps found it."""
    from drivers import train_steps, train_steps_counted

    class Engine:
        global_steps = 20
        mesh = type("Mesh", (), {"devices": np.zeros((1,))})
    if counts is not None:
        Engine.step_counts = lambda self: counts
    build = lambda *a, **k: (Engine(), "mesh")
    monkeypatch.setattr(train_steps, "build_engine", build)
    monkeypatch.setattr(
        train_steps, "run_cell",
        lambda *a, **k: (train_steps.build_engine(),
                         {"correct": True, "metrics": {}})[1])
    monkeypatch.setattr(train_steps_counted, "token_check",
                        lambda *a: token)
    result = train_steps_counted.run_cell("cell", {}, {}, {}, 1)
    assert result["correct"] is correct
    assert train_steps.build_engine is build            # put back
    out, err = capsys.readouterr()
    lines = [json.loads(l) for l in out.strip().splitlines()[-2:]]
    assert lines == [
        {"line": "step_counts", "counts": counts, "optimizer_steps": 20},
        {"line": "token_check", "token_nll_rms": token[0],
         "limit": token[1], "at_step": 20}]
    assert ("CHECK FAILED" in err) is not correct


def test_the_metrics_are_the_new_cells_alone():
    manifest = Manifest()
    for m in manifest.data["per_layer"]:
        if m["name"] in METRICS:
            assert m["workloads"] == [CELL]
    assert set(METRICS) <= {
        m["name"] for m in manifest.metrics("per_layer", CELL)}
    # after the four cells that were there (later cells go after it)
    names = [w["name"] for w in manifest.data["workloads"]]
    assert names.index(CELL) == 4
