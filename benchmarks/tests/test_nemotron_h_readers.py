"""What the Nemotron-H cell brings to the benchmark: its required
operations against a count by hand, its eleven metric files on hand-made
events through the readers that were there, and its metrics being its own
cell's alone."""
import importlib
import json

import pytest

from harness import flops, trace as tr
from harness.manifest import Manifest
from required_ops import nemotron_h as ops
from test_moe_readers import row
from test_step_readers import context, program  # noqa: F401 (a fixture)
from test_trace_reduction import KERNEL, hlo

CELL = "nemotron-3-nano-30b-a3b.packed-s8192-gas2"
METRICS = ["ssm.layer_ms_per_step", "ssm.scan_ms_per_step",
           "ssm.conv_ms_per_step", "ssm.scan_roofline",
           "attention.attn_layer_flash_fwd_roofline",
           "attention.attn_layer_flash_bwd_roofline",
           "moe.relu2_ggemm_ms_per_step", "moe.relu2_ggemm_fwd_roofline",
           "moe.relu2_ggemm_bwd_roofline", "moe.relu2_dispatch_ms_per_step",
           "moe.relu2_shared_expert_ms_per_step"]
BLOCK = "jit(train_step)/ds.fwd_bwd/{}while/body/ds.block/{}/op"


def sizes():
    with open(Manifest().path("configs",
                              "nemotron-3-nano-30b-a3b.json")) as f:
        return json.load(f)["model"]


def value(metric, ctx):
    s = Manifest().layer_metric(metric)
    return importlib.import_module(
        "layer_metrics.readers." + s["reader"]).read(ctx, s["params"])


def test_required_operations_by_hand():
    s = sizes()
    ssm = 2688 * 10304 + 4 * 6144 + 4096 * 2688
    attn = 2688 * (4096 + 512) + 4096 * 2688
    experts = 2688 * 128 + 2 * 2688 * 3712 + 6 * 8 / 128 * 2 * 2688 * 1856
    want = 6 * (4 * ssm + attn + 4 * experts + 2688 * 16384) \
        + 3 * 4 * 6 * 64 * 64 * 128 + 6 * 4096 * 1000
    assert ops.train_flops_per_token(s, 1000) == pytest.approx(want)
    assert flops.resolve("nemotron_h:train_flops_per_token") \
        is ops.train_flops_per_token
    # one attention layer of width 4096, where causal_attention_flops
    # counts num_layers * d_model = 9 * 2688: 5.9 times as much
    assert ops.attention_layer_flops(100, s, 1000, ["fwd"]) \
        == pytest.approx(0.5 * 4 * 100 * 4096 * 1000)
    assert flops.causal_attention_flops(100, s, 1000, ["fwd"]) \
        == pytest.approx(9 * 2688 / 4096 * ops.attention_layer_flops(
            100, s, 1000, ["fwd"]))
    # 0.375 held experts a token, two matrices, four expert layers
    assert ops.held_relu2_ffn_flops(100, s, 0, ["fwd", "bwd"]) \
        == pytest.approx(12 * 100 * 4 * 0.375 * 2688 * 1856)
    need, moved = ops.ssd_ops(100, s, 0, ["fwd", "fwd", "bwd"])
    assert need == pytest.approx(400 * 64 * 6 * 64 * 128 * 4)
    inputs = 2 * (4096 + 2 * 1024) + 4 * 64
    assert moved == pytest.approx(400 * (4 * inputs + 3 * 2 * 4096))
    # the published pattern counts 23 : 23 : 6
    whole = {**s, "num_layers": 52, "hybrid_override_pattern":
             "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    assert ops._layers(whole) == (23, 23, 6)


def synthetic():
    ops_ = [(0, 400, hlo("fusion.1", "fusion")),         # scan, fwd
            (400, 500, hlo("fusion.2", "fusion")),       # conv
            (500, 600, hlo("fusion.3", "fusion")),       # in_proj
            (600, 900, hlo("fusion.4", "fusion")),       # scan, bwd
            (900, 1000, hlo("ds_flash_fwd.1", "custom-call", KERNEL)),
            (1000, 1100, hlo("fusion.5", "fusion")),     # shared expert
            (1100, 1200, hlo("ds_ggemm_fwd.1", "custom-call", KERNEL)),
            (1200, 1250, hlo("sort.1", "sort")),         # the held plan
            (1250, 1400, hlo("fusion.6", "fusion")),     # sum into tokens
            (1400, 1600, hlo("ds_ggemm_dw.1", "custom-call", KERNEL)),
            (1600, 1900, hlo("ds_flash_bwd_dq.1", "custom-call", KERNEL))]
    dev = tr.DeviceTrace("/device:TPU:0", {
        tr.OPS: ops_, tr.MODULES: [(0, 1900, "jit_train_step(1)")]})
    ssm = lambda part, outer="": row(BLOCK.format(outer, "ssm/" + part))
    back = "transpose(jvp())/"
    table = {"fusion.1": ssm("scan/while/body"),
             "fusion.2": ssm("conv"), "fusion.3": ssm("in_proj"),
             "fusion.4": ssm("scan", back),
             "ds_flash_fwd.1": row(BLOCK.format("", "attn"),
                                   "ds_flash_fwd"),
             "fusion.5": row(BLOCK.format("", "mlp/shared_expert")),
             "ds_ggemm_fwd.1": row(BLOCK.format("", "mlp/experts"),
                                   "ds_ggemm_fwd"),
             "sort.1": row(BLOCK.format("", "mlp/dispatch")),
             "fusion.6": row(BLOCK.format("", "mlp/combine")),
             "ds_ggemm_dw.1": row(BLOCK.format(back, "mlp/experts"),
                                  "ds_ggemm_dw"),
             "ds_flash_bwd_dq.1": row(BLOCK.format(back, "attn"),
                                      "ds_flash_bwd_dq")}
    return tr.Trace([dev], {}), table


def test_known_answers_on_hand_made_events(program):  # noqa: F811
    trace, table = synthetic()
    program(table)
    ctx = context(trace, steps=2)
    ctx["model"] = sizes()
    ctx["peaks"] = {**ctx["peaks"], "hbm_bytes_per_s": 819e9}
    ms = lambda ns: ns * 1e-6 / 2
    assert value("ssm.layer_ms_per_step", ctx) == pytest.approx(ms(900))
    assert value("ssm.scan_ms_per_step", ctx) == pytest.approx(ms(700))
    assert value("ssm.conv_ms_per_step", ctx) == pytest.approx(ms(100))
    assert value("moe.relu2_shared_expert_ms_per_step", ctx) \
        == pytest.approx(ms(100))
    assert value("moe.relu2_ggemm_ms_per_step", ctx) \
        == pytest.approx(ms(300))
    assert value("moe.relu2_dispatch_ms_per_step", ctx) \
        == pytest.approx(ms(200))
    tokens, s_eff = ctx["tokens_per_step_per_chip"], ctx["s_eff"]
    peaks = ctx["peaks"]
    need, moved = ops.ssd_ops(tokens, ctx["model"], s_eff,
                              ["fwd", "fwd", "bwd"])
    assert moved / peaks["hbm_bytes_per_s"] > need / peaks[
        "bf16_flops_per_s"]                  # memory is the floor here
    assert value("ssm.scan_roofline", ctx) == pytest.approx(
        100 * moved / peaks["hbm_bytes_per_s"] * 1e3 / ms(700))
    share = lambda fn, passes, ns: 100 * fn(
        tokens, ctx["model"], s_eff, passes) / peaks["bf16_flops_per_s"] \
        * 1e3 / ms(ns)
    assert value("attention.attn_layer_flash_fwd_roofline", ctx) \
        == pytest.approx(share(ops.attention_layer_flops,
                               ["fwd", "fwd"], 100))
    assert value("attention.attn_layer_flash_bwd_roofline", ctx) \
        == pytest.approx(share(ops.attention_layer_flops, ["bwd"], 300))
    assert value("moe.relu2_ggemm_fwd_roofline", ctx) \
        == pytest.approx(share(ops.held_relu2_ffn_flops,
                               ["fwd", "fwd"], 100))
    assert value("moe.relu2_ggemm_bwd_roofline", ctx) \
        == pytest.approx(share(ops.held_relu2_ffn_flops, ["bwd"], 200))


@pytest.mark.parametrize("metric", METRICS)
def test_no_device_plane_reads_nothing(metric):
    """What the parent commit's traced runs need of a metric new here: a
    trace without a device plane gives None and does not raise."""
    ctx = context(tr.Trace([], {}), steps=2)
    ctx["model"] = sizes()
    assert value(metric, ctx) is None


def test_the_metrics_are_the_new_cells_alone():
    manifest = Manifest()
    for m in manifest.data["per_layer"]:
        if m["name"] in METRICS:
            assert m["workloads"] == [CELL]
    assert set(METRICS) <= {
        m["name"] for m in manifest.metrics("per_layer", CELL)}
    # after the five cells that were there (later cells go after it)
    names = [w["name"] for w in manifest.data["workloads"]]
    assert names.index(CELL) == 5
