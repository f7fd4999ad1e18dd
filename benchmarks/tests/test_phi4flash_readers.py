"""What the Phi-4-mini-flash cell brings to the benchmark: its required
operations against a count by hand, its eleven metric files on hand-made
events through the readers that were there (a broken join raises), and its
metrics being its own cell's alone."""
import importlib
import json

import pytest

from harness import datagen, flops, trace as tr
from harness.manifest import Manifest
from layer_metrics.readers import step_phase, window_roofline
from required_ops import phi4flash as ops
from test_moe_readers import row
from test_step_readers import context, program  # noqa: F401 (a fixture)
from test_trace_reduction import KERNEL, hlo

CELL = "phi-4-mini-flash-reasoning.packed-s16384-traces"
METRICS = ["mamba1.layer_ms_per_step", "mamba1.scan_ms_per_step",
           "mamba1.conv_ms_per_step", "mamba1.scan_roofline",
           "gmu.layer_ms_per_step", "diffattn.layer_ms_per_step",
           "diffattn.combine_ms_per_step",
           "attention.diff_window_flash_fwd_roofline",
           "attention.diff_window_flash_bwd_roofline",
           "attention.diff_full_flash_fwd_roofline",
           "attention.diff_full_flash_bwd_roofline"]
BLOCK = "jit(train_step)/ds.fwd_bwd/{}ds.block/{}/op"


def sizes():
    with open(Manifest().path("configs",
                              "phi-4-mini-flash-reasoning.json")) as f:
        return json.load(f)["model"]


def traffic():
    return Manifest().traffic("packed-s16384-traces")


def value(metric, ctx):
    s = Manifest().layer_metric(metric)
    return importlib.import_module(
        "layer_metrics.readers." + s["reader"]).read(ctx, s["params"])


def test_required_operations_by_hand():
    s = sizes()
    mlp = 3 * 2560 * 10240
    mamba = 2560 * 10240 + 4 * 5120 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    own = 2560 * (2560 + 1280 + 1280) + 2560 * 2560
    cross = 2 * 2560 * 2560
    gmu = 2 * 2560 * 5120
    weights = 8 * mlp + 3 * mamba + 3 * own + cross + gmu + 2560 * 25008
    assert ops.multiplying_weights(s) == weights
    # the vectors (norms, biases, lambdas, A_log, D) are what is left
    assert 0 < s["n_params"] - weights < 0.001 * weights
    width = 1.5 * 40 * 64
    assert ops.train_flops_per_token(s, 1000) == pytest.approx(
        6 * weights + 3 * 3 * 6 * 5120 * 16 + 6 * 2 * width * 1000
        + 6 * 2 * width * ops.window_keys_times_two(1000, 512))
    assert flops.resolve("phi4flash:train_flops_per_token") \
        is ops.train_flops_per_token
    # two layers call the causal kernels, two the windowed ones: two maps
    # each of 20 heads, 2 * (64 + 128) operations a key
    assert ops.diff_full_attention_flops(100, s, 1000, ["fwd"]) \
        == pytest.approx(100 * 2 * 2 * 20 * 2 * (64 + 128) * 500)
    assert ops.diff_window_attention_flops(100, s, 600, ["fwd", "bwd"]) \
        == pytest.approx(3 * 100 * 2 * 2 * 20 * 2 * (64 + 128) * 300)
    # causal_attention_flops counts num_layers * d_model = 8 * 2560 where
    # the causal kernels' layers are 2 * 3840: 2.67 times as much
    assert flops.causal_attention_flops(100, s, 1000, ["fwd"]) \
        == pytest.approx(8 * 2560 / (2 * width)
                         * ops.diff_full_attention_flops(100, s, 1000,
                                                         ["fwd"]))
    need, moved = ops.selective_scan_ops(100, s, 0, ["fwd", "fwd", "bwd"])
    assert need == pytest.approx(300 * 6 * 5120 * 16 * 4)
    inputs = 2 * (2 * 5120 + 32)
    assert moved == pytest.approx(300 * (4 * inputs + 3 * 2 * 5120))
    # the published 32 layers count 9 : 8 : 1 : 7 : 7
    assert ops.layer_counts({**s, "num_layers": 32}) == {
        "mamba": 9, "swa": 8, "full": 1, "gmu": 7, "cross": 7}
    assert ops.multiplying_weights(
        {**s, "num_layers": 32, "vocab_size": 200064}) \
        == pytest.approx(3.851e9, rel=1e-3)
    # the file's S_eff is the generator's
    assert f"is {datagen.effective_context(traffic()):.0f}" \
        in traffic()["what"]


def synthetic():
    ops_ = [(0, 400, hlo("ds_sscan_fwd.1", "custom-call", KERNEL)),
            (400, 500, hlo("ds_conv_fwd.1", "custom-call", KERNEL)),
            (500, 600, hlo("fusion.3", "fusion")),       # in_proj
            (600, 900, hlo("ds_sscan_bwd.1", "custom-call", KERNEL)),
            (900, 950, hlo("fusion.4", "fusion")),       # scan: the rows
            (950, 1000, hlo("fusion.5", "fusion")),      # gmu
            (1000, 1200, hlo("ds_flash_win_fwd.1", "custom-call", KERNEL)),
            (1200, 1600, hlo("ds_flash_fwd.1", "custom-call", KERNEL)),
            (1600, 1700, hlo("fusion.6", "fusion")),     # combine
            (1700, 1800, hlo("fusion.7", "fusion")),     # qkv
            (1800, 2100, hlo("ds_flash_win_bwd_dq.1", "custom-call",
                             KERNEL)),
            (2100, 2200, hlo("ds_flash_win_bwd_dkv.1", "custom-call",
                             KERNEL)),
            (2200, 2800, hlo("ds_flash_bwd_dq.1", "custom-call", KERNEL)),
            (2800, 2900, hlo("fusion.8", "fusion"))]     # mlp
    dev = tr.DeviceTrace("/device:TPU:0", {
        tr.OPS: ops_, tr.MODULES: [(0, 2900, "jit_train_step(1)")]})
    back = "transpose(jvp())/"
    at = lambda part, kernel=None, outer="": row(
        BLOCK.format(outer, part), kernel)
    table = {
        "ds_sscan_fwd.1": at("mamba/scan", "ds_sscan_fwd"),
        "ds_conv_fwd.1": at("mamba/conv", "ds_conv_fwd"),
        "fusion.3": at("mamba/in_proj"),
        "ds_sscan_bwd.1": at("mamba/scan", "ds_sscan_bwd", back),
        "fusion.4": at("mamba/scan", None, back),
        "fusion.5": at("gmu"),
        "ds_flash_win_fwd.1": at("diff_attn/flash", "ds_flash_win_fwd"),
        "ds_flash_fwd.1": at("diff_attn/flash", "ds_flash_fwd"),
        "fusion.6": at("diff_attn/combine"),
        "fusion.7": at("diff_attn/qkv"),
        "ds_flash_win_bwd_dq.1": at("diff_attn/flash",
                                    "ds_flash_win_bwd_dq", back),
        "ds_flash_win_bwd_dkv.1": at("diff_attn/flash",
                                     "ds_flash_win_bwd_dkv", back),
        "ds_flash_bwd_dq.1": at("diff_attn/flash", "ds_flash_bwd_dq", back),
        "fusion.8": at("mlp")}
    return tr.Trace([dev], {}), table


def _context(trace):
    ctx = context(trace, steps=2)
    ctx["model"], ctx["traffic"] = sizes(), traffic()
    ctx["peaks"] = {**ctx["peaks"], "hbm_bytes_per_s": 819e9}
    return ctx


def test_known_answers_on_hand_made_events(program):  # noqa: F811
    trace, table = synthetic()
    program(table)
    ctx = _context(trace)
    ms = lambda ns: ns * 1e-6 / 2
    assert value("mamba1.layer_ms_per_step", ctx) == pytest.approx(ms(950))
    assert value("mamba1.scan_ms_per_step", ctx) == pytest.approx(ms(750))
    assert value("mamba1.conv_ms_per_step", ctx) == pytest.approx(ms(100))
    assert value("gmu.layer_ms_per_step", ctx) == pytest.approx(ms(50))
    assert value("diffattn.layer_ms_per_step", ctx) \
        == pytest.approx(ms(200 + 400 + 100 + 100 + 300 + 100 + 600))
    assert value("diffattn.combine_ms_per_step", ctx) \
        == pytest.approx(ms(100))
    tokens, s_eff = ctx["tokens_per_step_per_chip"], ctx["s_eff"]
    peaks = ctx["peaks"]
    need, moved = ops.selective_scan_ops(tokens, ctx["model"], s_eff,
                                         ["fwd", "fwd", "bwd"])
    assert moved / peaks["hbm_bytes_per_s"] > need / peaks[
        "bf16_flops_per_s"]                  # memory is the floor
    assert value("mamba1.scan_roofline", ctx) == pytest.approx(
        100 * moved / peaks["hbm_bytes_per_s"] * 1e3 / ms(750))
    share = lambda fn, passes, ns, keys=s_eff: 100 * fn(
        tokens, ctx["model"], keys, passes) / peaks["bf16_flops_per_s"] \
        * 1e3 / ms(ns)
    # the windowed kernels against the sample's keys inside the window,
    # the causal ones against S_eff
    keys = window_roofline.keys_times_two(ctx["traffic"], 512)
    assert 512 < keys < 1024
    assert value("attention.diff_window_flash_fwd_roofline", ctx) \
        == pytest.approx(share(ops.diff_window_attention_flops,
                               ["fwd", "fwd"], 200, keys))
    assert value("attention.diff_window_flash_bwd_roofline", ctx) \
        == pytest.approx(share(ops.diff_window_attention_flops,
                               ["bwd"], 400, keys))
    assert value("attention.diff_full_flash_fwd_roofline", ctx) \
        == pytest.approx(share(ops.diff_full_attention_flops,
                               ["fwd", "fwd"], 400))
    assert value("attention.diff_full_flash_bwd_roofline", ctx) \
        == pytest.approx(share(ops.diff_full_attention_flops,
                               ["bwd"], 600))


@pytest.mark.parametrize("metric", [
    "attention.diff_full_flash_fwd_roofline", "mamba1.scan_ms_per_step"])
def test_a_broken_join_raises(metric, program):  # noqa: F811
    """A traced step whose instructions the program's map does not know
    (the map of another program): not a silent zero."""
    trace, table = synthetic()
    program({name + "_of_another_program": r for name, r in table.items()})
    with pytest.raises(step_phase.BrokenJoin):
        value(metric, _context(trace))


def test_a_program_without_the_windowed_kernels_reads_nothing(program):  # noqa: F811,E501
    trace, table = synthetic()
    program({name: r for name, r in table.items()
             if "win" not in (r["kernel"] or "")})
    ctx = _context(trace)
    assert value("attention.diff_window_flash_fwd_roofline", ctx) is None
    assert value("attention.diff_window_flash_bwd_roofline", ctx) is None


@pytest.mark.parametrize("metric", METRICS)
def test_no_device_plane_reads_nothing(metric):
    """What a CPU rehearsal needs of a metric new here: a trace without a
    device plane gives None and does not raise."""
    assert value(metric, _context(tr.Trace([], {}))) is None


def test_the_metrics_are_the_new_cells_alone():
    manifest = Manifest()
    for m in manifest.data["per_layer"]:
        if m["name"] in METRICS:
            assert m["workloads"] == [CELL]
    assert set(METRICS) <= {
        m["name"] for m in manifest.metrics("per_layer", CELL)}
    assert manifest.workload(CELL)["chips"] == 1
    config = manifest.config("phi-4-mini-flash-reasoning")
    assert config["reference"] == "phi4flash"
    assert config["flops"]["train"] == "phi4flash:train_flops_per_token"
    assert config["deployment"]["chips"] == 1
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert manifest.traffic("packed-s16384-traces")[
        "gradient_accumulation_steps"] == 1
