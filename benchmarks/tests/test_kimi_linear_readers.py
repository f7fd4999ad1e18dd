"""What the Kimi-Linear cell brings to the benchmark: its required
operations against a count by hand, its nine metric files on hand-made
events through the readers that were there, and its metrics being its own
cell's alone."""
import importlib
import json

import pytest

from harness import flops, trace as tr
from harness.manifest import Manifest
from required_ops import kimi_linear as ops
from test_moe_readers import row
from test_step_readers import context, program  # noqa: F401 (a fixture)
from test_trace_reduction import KERNEL, hlo

CELL = "kimi-linear-48b-a3b.packed-s16384-traces"
METRICS = ["kda.layer_ms_per_step", "kda.scan_ms_per_step",
           "kda.conv_ms_per_step", "kda.low_rank_gate_ms_per_step",
           "kda.scan_roofline", "mla.nope_layer_ms_per_step",
           "attention.nope_flash_fwd_roofline",
           "attention.nope_flash_bwd_roofline", "moe.kimi_ggemm_ms_per_step"]
BLOCK = "jit(train_step)/ds.fwd_bwd/{}while/body/ds.block/{}/op"


def sizes():
    with open(Manifest().path("configs", "kimi-linear-48b-a3b.json")) as f:
        return json.load(f)["model"]


def value(metric, ctx):
    s = Manifest().layer_metric(metric)
    return importlib.import_module(
        "layer_metrics.readers." + s["reader"]).read(ctx, s["params"])


def test_required_operations_by_hand():
    s = sizes()
    kda = 2304 * 12288 + 4 * 12288 + 2 * (2304 * 128 + 128 * 4096) \
        + 2304 * 32 + 4096 * 2304
    assert ops.kda_weights(s) == kda == 39_510_016
    mla = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
    assert ops.mla_weights(s) == mla == 29_114_368
    assert ops._kinds(s) == (6, 2) and ops._held_share(s) == 0.25
    experts = 2304 * 256 + 3 * 2304 * 1024 + 0.25 * 3 * 2304 * 1024
    weights = 6 * kda + 2 * mla + 3 * 2304 * 9216 + 7 * experts \
        + 2304 * 20480
    rule = 7 * 32 * 128 * 128
    want = 6 * weights + 3 * 6 * rule + 3 * 2 * 32 * (192 + 128) * 1000
    assert ops.train_flops_per_token(s, 1000) == pytest.approx(want)
    assert flops.resolve("kimi_linear:train_flops_per_token") \
        is ops.train_flops_per_token
    # 3.37 GFLOP a token at the traffic's S_eff, 0.47 of it the two
    # latent-attention layers' products, 0.066 the rule's recurrence
    assert ops.train_flops_per_token(s, 7691) == pytest.approx(3.372e9,
                                                               rel=1e-3)
    assert 3 * 2 * 32 * 320 * 7691 == pytest.approx(0.4725e9, rel=1e-3)
    # the published model: 20 KDA and 7 latent-attention layers
    whole = {**s, "num_layers": 27, "layer_kinds": "KKKM" * 6 + "KKM",
             "experts_held": None, "vocab_size": 163840}
    assert ops._kinds(whole) == (20, 7) and ops._held_share(whole) == 8
    # the rule's floors: q, k, v in bf16, g in float32 AT dk WIDE, beta,
    # o out — 42 KB a token-layer forward — and 7 dk dv a head of FLOPs
    need_flops, need_bytes = ops.kda_ops(100, s, 0, ["fwd"])
    assert need_bytes == 100 * 6 * (3 * 8192 + 16384 + 128 + 8192)
    assert need_flops == 100 * 6 * rule
    flops3, bytes3 = ops.kda_ops(16384, s, 0, ["fwd", "fwd", "bwd"])
    # a step's three passes: 22.6 ms of memory, 7.3 ms of matrix unit
    assert 1e3 * bytes3 / 819e9 == pytest.approx(22.6, rel=0.01)
    assert 1e3 * flops3 / 197e12 == pytest.approx(7.33, rel=0.01)
    # the two latent-attention layers' flash calls, forward and recompute
    assert ops.mla_attention_flops(16384, s, 7691, ["fwd", "fwd"]) \
        == pytest.approx(2 * 16384 * 2 * 32 * 320 * 7691)


def synthetic():
    ops_ = [(0, 100, hlo("fusion.1", "fusion")),         # linear_attn/in_proj
            (100, 150, hlo("fusion.2", "fusion")),       # low_rank_gate
            (150, 250, hlo("ds_conv_fwd.1", "custom-call", KERNEL)),
            (250, 650, hlo("fusion.3", "fusion")),       # delta_rule
            (650, 700, hlo("fusion.4", "fusion")),       # gate_norm
            (700, 800, hlo("fusion.5", "fusion")),       # attn/in_proj
            (800, 1200, hlo("ds_flash_fwd.1", "custom-call", KERNEL)),
            (1200, 1300, hlo("ds_ggemm_fwd.1", "custom-call", KERNEL)),
            (1300, 1500, hlo("ds_ggemm_dw.1", "custom-call", KERNEL)),
            (1500, 1800, hlo("ds_flash_bwd_dq.1", "custom-call", KERNEL)),
            (1800, 2400, hlo("fusion.6", "fusion")),     # delta_rule, bwd
            (2400, 2450, hlo("fusion.7", "fusion"))]     # the lead's MLP
    dev = tr.DeviceTrace("/device:TPU:0", {
        tr.OPS: ops_, tr.MODULES: [(0, 2450, "jit_train_step(1)")]})
    at = lambda part, outer="": row(BLOCK.format(outer, part))
    back = "transpose(jvp())/"
    table = {"fusion.1": at("linear_attn/in_proj"),
             "fusion.2": at("linear_attn/low_rank_gate"),
             "ds_conv_fwd.1": row(BLOCK.format("", "linear_attn/conv"),
                                  "ds_conv_fwd"),
             "fusion.3": at("linear_attn/delta_rule"),
             "fusion.4": at("linear_attn/gate_norm"),
             "fusion.5": at("attn/in_proj"),
             "ds_flash_fwd.1": row(BLOCK.format("", "attn/scores"),
                                   "ds_flash_fwd"),
             "ds_ggemm_fwd.1": row(BLOCK.format("", "mlp/experts"),
                                   "ds_ggemm_fwd"),
             "ds_ggemm_dw.1": row(BLOCK.format(back, "mlp/experts"),
                                  "ds_ggemm_dw"),
             "ds_flash_bwd_dq.1": row(BLOCK.format(back, "attn/scores"),
                                      "ds_flash_bwd_dq"),
             "fusion.6": at("linear_attn/delta_rule", back),
             "fusion.7": row("jit(train_step)/ds.fwd_bwd/ds.block/"
                             "ds.lead_mlp/mlp/op")}
    return tr.Trace([dev], {}), table


def test_known_answers_on_hand_made_events(program):  # noqa: F811
    trace, table = synthetic()
    program(table)
    ctx = context(trace, steps=2)
    ctx["model"] = sizes()
    ctx["peaks"] = {**ctx["peaks"], "hbm_bytes_per_s": 819e9}
    ms = lambda ns: ns * 1e-6 / 2
    assert value("kda.layer_ms_per_step", ctx) == pytest.approx(
        ms(100 + 50 + 100 + 400 + 50 + 600))
    assert value("kda.scan_ms_per_step", ctx) == pytest.approx(ms(1000))
    assert value("kda.conv_ms_per_step", ctx) == pytest.approx(ms(100))
    assert value("kda.low_rank_gate_ms_per_step", ctx) \
        == pytest.approx(ms(50))
    # the latent-attention layers' scope, which linear_attn does not match
    assert value("mla.nope_layer_ms_per_step", ctx) == pytest.approx(
        ms(100 + 400 + 300))
    assert value("moe.kimi_ggemm_ms_per_step", ctx) == pytest.approx(ms(300))
    tokens, s_eff = ctx["tokens_per_step_per_chip"], ctx["s_eff"]
    share = lambda fn, passes, ns: 100 * fn(
        tokens, ctx["model"], s_eff, passes) \
        / ctx["peaks"]["bf16_flops_per_s"] * 1e3 / ms(ns)
    assert value("attention.nope_flash_fwd_roofline", ctx) \
        == pytest.approx(share(ops.mla_attention_flops, ["fwd", "fwd"], 400))
    assert value("attention.nope_flash_bwd_roofline", ctx) \
        == pytest.approx(share(ops.mla_attention_flops, ["bwd"], 300))
    # the rule's scope (1,000 ns), the larger of the two floors: the bytes'
    need_flops, need_bytes = ops.kda_ops(
        tokens, ctx["model"], s_eff, ["fwd", "fwd", "bwd"])
    floor_ms = 1e3 * need_bytes / ctx["peaks"]["hbm_bytes_per_s"]
    assert floor_ms > 1e3 * need_flops / ctx["peaks"]["bf16_flops_per_s"]
    assert value("kda.scan_roofline", ctx) == pytest.approx(
        100 * floor_ms / ms(1000))


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
    assert len(manifest.data["per_layer"]) <= 128      # the contract's most
    assert manifest.workload(CELL)["chips"] == 1
    config = manifest.config("kimi-linear-48b-a3b")
    assert config["reference"] == "kimi_linear"
    assert config["flops"]["train"] == "kimi_linear:train_flops_per_token"
    # the traffic is Phi-4-mini-flash's, to the byte: no file of its own
    traffic = manifest.traffic("packed-s16384-traces")
    assert traffic["micro_batch_per_chip"] \
        * traffic["gradient_accumulation_steps"] * traffic["seq_len"] == 16384
    assert traffic["driver"] == "train_steps_counted"
