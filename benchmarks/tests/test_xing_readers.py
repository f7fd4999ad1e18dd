"""What the Xing4.0 cell brings to the benchmark: its required operations
against a count by hand, its thirteen metric files on hand-made events
through the readers that were there, and its metrics being its own cell's
alone."""
import importlib
import json

import pytest

from harness import flops, trace as tr
from harness.manifest import Manifest
from required_ops import joyai as joyai_ops
from required_ops import xing as ops
from test_moe_readers import row
from test_step_readers import context, program  # noqa: F401 (a fixture)
from test_trace_reduction import KERNEL, hlo

CELL = "xing4.0-29b-a4b.packed-s4096-pretrain"
METRICS = ["hc.layer_ms_per_step", "hc.coeff_ms_per_step",
           "hc.stream_roofline", "mla.xing_layer_ms_per_step",
           "mla.xing_latent_proj_ms_per_step",
           "attention.xing_flash_fwd_roofline",
           "attention.xing_flash_bwd_roofline", "mtp.xing_module_ms_per_step",
           "moe.w1024h_ggemm_ms_per_step", "moe.w1024h_ggemm_fwd_roofline",
           "moe.w1024h_ggemm_bwd_roofline", "moe.w1024h_dispatch_ms_per_step",
           "moe.w1024h_shared_expert_ms_per_step"]
BLOCK = "jit(train_step)/ds.fwd_bwd/{}while/body/ds.block/{}/op"
MTP = "jit(train_step)/ds.fwd_bwd/{}ds.mtp/{}/op"


def sizes():
    with open(Manifest().path("configs", "xing4.0-29b-a4b.json")) as f:
        return json.load(f)["model"]


def value(metric, ctx):
    s = Manifest().layer_metric(metric)
    return importlib.import_module(
        "layer_metrics.readers." + s["reader"]).read(ctx, s["params"])


def test_required_operations_by_hand():
    s = sizes()
    attn = 3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 \
        + 4096 * 3584
    assert ops.attention_weights(s) == joyai_ops.attention_weights(s) \
        == attn == 28_409_856
    stream = 4 * 3584 * 24 + 24 * 3584
    assert ops.stream_multiply_adds(s) == stream == 430_080
    assert ops.sublayer_calls(s) == 12
    experts = 3584 * 64 + 3 * 3584 * 1024 + 4 * 8 / 64 * 3 * 3584 * 1024
    weights = 6 * attn + 3 * 3584 * 9216 + 5 * experts + 2 * 3584 * 3584 \
        + 2 * 3584 * 16384 + 12 * stream
    want = 6 * weights + 3 * 6 * 32 * (192 + 128) * 1000
    assert ops.train_flops_per_token(s, 1000) == pytest.approx(want)
    assert flops.resolve("xing:train_flops_per_token") \
        is ops.train_flops_per_token
    # the stream is 1% of the weights a token multiplies
    assert 0.005 < 12 * stream / weights < 0.02
    # without the stream it is JoyAI's count at these sizes
    assert ops.train_flops_per_token(s, 1679) - 6 * 12 * stream \
        == pytest.approx(joyai_ops.train_flops_per_token(s, 1679))
    # the published model: two leading layers, every expert held
    whole = {**s, "num_layers": 40, "num_dense_layers": 2,
             "experts_held": None, "vocab_size": 131072}
    assert ops._blocks(whole) == (41, 2, 39)
    assert ops._held_share(whole) == 4
    assert ops.sublayer_calls(whole) == 82
    # the floor: (3 n + 2) C elements forward, (5 n + 3) C backward, 2 B each
    need_flops, need_bytes = ops.hc_stream_ops(100, s, 0, ["fwd"])
    assert need_bytes == 100 * 12 * 14 * 3584 * 2 == 100 * 12 * 100_352
    assert need_flops == 100 * 12 * 2 * 24 * 3584
    _, both = ops.hc_stream_ops(100, s, 0, ["fwd", "fwd", "bwd"])
    assert both == 100 * 12 * (14 + 14 + 23) * 3584 * 2
    # 39.5 GB a forward pass of a 32,768-token step: 48 ms at 819 GB/s
    assert ops.hc_stream_ops(32768, s, 0, ["fwd"])[1] \
        == pytest.approx(39.46e9, rel=1e-3)


def synthetic():
    ops_ = [(0, 100, hlo("fusion.1", "fusion")),         # attn/hc/coeff
            (100, 300, hlo("fusion.2", "fusion")),       # attn/hc/read
            (300, 350, hlo("fusion.3", "fusion")),       # q_latent
            (350, 750, hlo("ds_flash_fwd.1", "custom-call", KERNEL)),
            (750, 850, hlo("fusion.4", "fusion")),       # attn/hc/write
            (850, 950, hlo("fusion.5", "fusion")),       # shared expert
            (950, 1050, hlo("ds_ggemm_fwd.1", "custom-call", KERNEL)),
            (1050, 1100, hlo("sort.1", "sort")),         # the held plan
            (1100, 1250, hlo("fusion.6", "fusion")),     # mlp/hc/write
            (1250, 1450, hlo("ds_ggemm_dw.1", "custom-call", KERNEL)),
            (1450, 1750, hlo("ds_flash_bwd_dq.1", "custom-call", KERNEL)),
            (1750, 1800, hlo("fusion.7", "fusion")),     # the module: W_eh
            (1800, 2000, hlo("fusion.8", "fusion")),     # ... its hc/write
            (2000, 2100, hlo("fusion.9", "fusion")),     # the exit sum
            (2100, 2200, hlo("fusion.10", "fusion"))]    # mlp/hc/coeff, bwd
    dev = tr.DeviceTrace("/device:TPU:0", {
        tr.OPS: ops_, tr.MODULES: [(0, 2200, "jit_train_step(1)")]})
    at = lambda part, outer="": row(BLOCK.format(outer, part))
    back = "transpose(jvp())/"
    table = {"fusion.1": at("attn/hc/coeff"), "fusion.2": at("attn/hc/read"),
             "fusion.3": at("attn/q_latent"),
             "ds_flash_fwd.1": row(BLOCK.format("", "attn/scores"),
                                   "ds_flash_fwd"),
             "fusion.4": at("attn/hc/write"),
             "fusion.5": at("mlp/shared_expert"),
             "ds_ggemm_fwd.1": row(BLOCK.format("", "mlp/experts"),
                                   "ds_ggemm_fwd"),
             "sort.1": at("mlp/dispatch"),
             "fusion.6": at("mlp/hc/write"),
             "ds_ggemm_dw.1": row(BLOCK.format(back, "mlp/experts"),
                                  "ds_ggemm_dw"),
             "ds_flash_bwd_dq.1": row(BLOCK.format(back, "attn/scores"),
                                      "ds_flash_bwd_dq"),
             "fusion.7": row(MTP.format("", "dot_general")),
             "fusion.8": row(MTP.format(
                 "", "checkpoint/ds.block/attn/hc/write")),
             "fusion.9": row("jit(train_step)/ds.fwd_bwd/ds.block/hc/op"),
             "fusion.10": at("mlp/hc/coeff", back)}
    return tr.Trace([dev], {}), table


def test_known_answers_on_hand_made_events(program):  # noqa: F811
    trace, table = synthetic()
    program(table)
    ctx = context(trace, steps=2)
    ctx["model"] = sizes()
    ctx["peaks"] = {**ctx["peaks"], "hbm_bytes_per_s": 819e9}
    ms = lambda ns: ns * 1e-6 / 2
    # every hc scope: coeff, read, write, the module's and the exit sum
    assert value("hc.layer_ms_per_step", ctx) == pytest.approx(
        ms(100 + 200 + 100 + 150 + 200 + 100 + 100))
    assert value("hc.coeff_ms_per_step", ctx) == pytest.approx(ms(200))
    # attention without the stream's share of its scope
    assert value("mla.xing_layer_ms_per_step", ctx) == pytest.approx(
        ms(50 + 400 + 300))
    assert value("mla.xing_latent_proj_ms_per_step", ctx) \
        == pytest.approx(ms(50))
    assert value("mtp.xing_module_ms_per_step", ctx) == pytest.approx(ms(250))
    assert value("moe.w1024h_shared_expert_ms_per_step", ctx) \
        == pytest.approx(ms(100))
    assert value("moe.w1024h_ggemm_ms_per_step", ctx) == pytest.approx(ms(300))
    assert value("moe.w1024h_dispatch_ms_per_step", ctx) \
        == pytest.approx(ms(50))
    tokens, s_eff = ctx["tokens_per_step_per_chip"], ctx["s_eff"]
    share = lambda fn, passes, ns: 100 * fn(
        tokens, ctx["model"], s_eff, passes) \
        / ctx["peaks"]["bf16_flops_per_s"] * 1e3 / ms(ns)
    assert value("attention.xing_flash_fwd_roofline", ctx) \
        == pytest.approx(share(joyai_ops.mla_attention_flops,
                               ["fwd", "fwd"], 400))
    assert value("attention.xing_flash_bwd_roofline", ctx) \
        == pytest.approx(share(joyai_ops.mla_attention_flops, ["bwd"], 300))
    assert value("moe.w1024h_ggemm_fwd_roofline", ctx) \
        == pytest.approx(share(joyai_ops.held_swiglu_ffn_flops,
                               ["fwd", "fwd"], 100))
    assert value("moe.w1024h_ggemm_bwd_roofline", ctx) \
        == pytest.approx(share(joyai_ops.held_swiglu_ffn_flops,
                               ["bwd"], 200))
    # read and write (650 ns), the larger of the two floors: the bytes'
    need_flops, need_bytes = ops.hc_stream_ops(
        tokens, ctx["model"], s_eff, ["fwd", "fwd", "bwd"])
    floor_ms = 1e3 * need_bytes / ctx["peaks"]["hbm_bytes_per_s"]
    assert floor_ms > 1e3 * need_flops / ctx["peaks"]["bf16_flops_per_s"]
    assert value("hc.stream_roofline", ctx) == pytest.approx(
        100 * floor_ms / ms(200 + 100 + 150 + 200))


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
    names = [w["name"] for w in manifest.data["workloads"]]
    assert names.index(CELL) == 10 and manifest.workload(CELL)["chips"] == 1
    config = manifest.config("xing4.0-29b-a4b")
    assert config["reference"] == "xing"
    assert config["flops"]["train"] == "xing:train_flops_per_token"
    traffic = manifest.traffic("packed-s4096-pretrain")
    assert traffic["micro_batch_per_chip"] \
        * traffic["gradient_accumulation_steps"] * traffic["seq_len"] == 32768
    assert traffic["driver"] == "train_steps_counted"
