"""What the JoyAI-LLM-Flash cell brings to the benchmark: its required
operations against a count by hand, its ten metric files on hand-made
events through the readers that were there, and its metrics being its own
cell's alone."""
import importlib
import json

import pytest

from harness import flops, trace as tr
from harness.manifest import Manifest
from required_ops import joyai as ops
from test_moe_readers import row
from test_step_readers import context, program  # noqa: F401 (a fixture)
from test_trace_reduction import KERNEL, hlo

CELL = "joyai-llm-flash.packed-s8192-gas2"
METRICS = ["mla.layer_ms_per_step", "mla.latent_proj_ms_per_step",
           "attention.mla_flash_fwd_roofline",
           "attention.mla_flash_bwd_roofline", "mtp.module_ms_per_step",
           "moe.w768_ggemm_ms_per_step", "moe.w768_ggemm_fwd_roofline",
           "moe.w768_ggemm_bwd_roofline", "moe.w768_dispatch_ms_per_step",
           "moe.w768_shared_expert_ms_per_step"]
BLOCK = "jit(train_step)/ds.fwd_bwd/{}while/body/ds.block/{}/op"
MTP = "jit(train_step)/ds.fwd_bwd/{}ds.mtp/{}/op"


def sizes():
    with open(Manifest().path("configs", "joyai-llm-flash.json")) as f:
        return json.load(f)["model"]


def value(metric, ctx):
    s = Manifest().layer_metric(metric)
    return importlib.import_module(
        "layer_metrics.readers." + s["reader"]).read(ctx, s["params"])


def test_required_operations_by_hand():
    s = sizes()
    attn = 2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 \
        + 4096 * 2048
    assert ops.attention_weights(s) == attn == 26_345_472
    experts = 2048 * 256 + 3 * 2048 * 768 + 8 * 16 / 256 * 3 * 2048 * 768
    weights = 6 * attn + 3 * 2048 * 7168 + 5 * experts + 2 * 2048 * 2048 \
        + 2 * 2048 * 16160
    want = 6 * weights + 3 * 6 * 32 * (192 + 128) * 1000
    assert ops.train_flops_per_token(s, 1000) == pytest.approx(want)
    assert ops.train_flops_per_token(s, 2152) == pytest.approx(2.2849e9,
                                                               rel=1e-4)
    assert flops.resolve("joyai:train_flops_per_token") \
        is ops.train_flops_per_token
    # six blocks at q k^T width 6144 and P v width 4096, where
    # causal_attention_flops counts num_layers * d_model = 5 * 2048 for
    # both products: a third of it
    assert ops.mla_attention_flops(100, s, 1000, ["fwd"]) \
        == pytest.approx(0.5 * 2 * 100 * 6 * (6144 + 4096) * 1000)
    assert ops.mla_attention_flops(100, s, 1000, ["fwd", "bwd"]) \
        == pytest.approx(3 * ops.mla_attention_flops(100, s, 1000, ["fwd"]))
    assert ops.mla_attention_flops(100, s, 1000, ["fwd"]) \
        == pytest.approx(3 * flops.causal_attention_flops(
            100, s, 1000, ["fwd"]))
    # 0.5 held experts a token, three matrices, five expert blocks
    assert ops.held_swiglu_ffn_flops(100, s, 0, ["fwd", "bwd"]) \
        == pytest.approx(18 * 100 * 5 * 0.5 * 2048 * 768)
    # the published model: 40 layers and the module, every expert held
    whole = {**s, "num_layers": 40, "experts_held": None,
             "vocab_size": 129280}
    assert ops._blocks(whole) == (41, 40)
    assert ops._held_share(whole) == 8
    assert ops._blocks({**whole, "num_mtp_layers": 0}) == (40, 39)


def synthetic():
    ops_ = [(0, 100, hlo("fusion.1", "fusion")),         # q_latent
            (100, 300, hlo("fusion.2", "fusion")),       # kv_latent
            (300, 350, hlo("fusion.3", "fusion")),       # rope
            (350, 750, hlo("ds_flash_fwd.1", "custom-call", KERNEL)),
            (750, 850, hlo("fusion.4", "fusion")),       # out_proj
            (850, 950, hlo("fusion.5", "fusion")),       # shared expert
            (950, 1050, hlo("ds_ggemm_fwd.1", "custom-call", KERNEL)),
            (1050, 1100, hlo("sort.1", "sort")),         # the held plan
            (1100, 1250, hlo("fusion.6", "fusion")),     # sum into tokens
            (1250, 1450, hlo("ds_ggemm_dw.1", "custom-call", KERNEL)),
            (1450, 1750, hlo("ds_flash_bwd_dq.1", "custom-call", KERNEL)),
            (1750, 1800, hlo("fusion.7", "fusion")),     # the module: W_eh
            (1800, 2000, hlo("ds_flash_fwd.2", "custom-call", KERNEL)),
            (2000, 2100, hlo("fusion.8", "fusion")),     # ... its head
            (2100, 2200, hlo("fusion.9", "fusion"))]     # the dense MLP
    dev = tr.DeviceTrace("/device:TPU:0", {
        tr.OPS: ops_, tr.MODULES: [(0, 2200, "jit_train_step(1)")]})
    attn = lambda part, outer="": row(BLOCK.format(outer, "attn/" + part))
    back = "transpose(jvp())/"
    table = {"fusion.1": attn("q_latent"), "fusion.2": attn("kv_latent"),
             "fusion.3": attn("rope"),
             "ds_flash_fwd.1": row(BLOCK.format("", "attn/scores"),
                                   "ds_flash_fwd"),
             "fusion.4": attn("out_proj"),
             "fusion.5": row(BLOCK.format("", "mlp/shared_expert")),
             "ds_ggemm_fwd.1": row(BLOCK.format("", "mlp/experts"),
                                   "ds_ggemm_fwd"),
             "sort.1": row(BLOCK.format("", "mlp/dispatch")),
             "fusion.6": row(BLOCK.format("", "mlp/combine")),
             "ds_ggemm_dw.1": row(BLOCK.format(back, "mlp/experts"),
                                  "ds_ggemm_dw"),
             "ds_flash_bwd_dq.1": row(BLOCK.format(back, "attn/scores"),
                                      "ds_flash_bwd_dq"),
             "fusion.7": row(MTP.format("", "dot_general")),
             "ds_flash_fwd.2": row(
                 MTP.format("", "checkpoint/ds.block/attn/scores"),
                 "ds_flash_fwd"),
             "fusion.8": row(MTP.format("", "checkpoint/ds.head_loss")),
             "fusion.9": row(BLOCK.format("", "mlp"))}
    return tr.Trace([dev], {}), table


def test_known_answers_on_hand_made_events(program):  # noqa: F811
    trace, table = synthetic()
    program(table)
    ctx = context(trace, steps=2)
    ctx["model"] = sizes()
    ms = lambda ns: ns * 1e-6 / 2
    # everything under attn, the module's block's too
    assert value("mla.layer_ms_per_step", ctx) == pytest.approx(
        ms(100 + 200 + 50 + 400 + 100 + 300 + 200))
    assert value("mla.latent_proj_ms_per_step", ctx) \
        == pytest.approx(ms(350))
    assert value("mtp.module_ms_per_step", ctx) == pytest.approx(ms(350))
    assert value("moe.w768_shared_expert_ms_per_step", ctx) \
        == pytest.approx(ms(100))
    assert value("moe.w768_ggemm_ms_per_step", ctx) \
        == pytest.approx(ms(300))
    assert value("moe.w768_dispatch_ms_per_step", ctx) \
        == pytest.approx(ms(200))
    tokens, s_eff = ctx["tokens_per_step_per_chip"], ctx["s_eff"]
    share = lambda fn, passes, ns: 100 * fn(
        tokens, ctx["model"], s_eff, passes) \
        / ctx["peaks"]["bf16_flops_per_s"] * 1e3 / ms(ns)
    assert value("attention.mla_flash_fwd_roofline", ctx) \
        == pytest.approx(share(ops.mla_attention_flops,
                               ["fwd", "fwd"], 600))
    assert value("attention.mla_flash_bwd_roofline", ctx) \
        == pytest.approx(share(ops.mla_attention_flops, ["bwd"], 300))
    assert value("moe.w768_ggemm_fwd_roofline", ctx) \
        == pytest.approx(share(ops.held_swiglu_ffn_flops,
                               ["fwd", "fwd"], 100))
    assert value("moe.w768_ggemm_bwd_roofline", ctx) \
        == pytest.approx(share(ops.held_swiglu_ffn_flops, ["bwd"], 200))


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
    # after the six cells that were there (later cells go after it)
    names = [w["name"] for w in manifest.data["workloads"]]
    assert names.index(CELL) == 6
    # the reference the configuration names is the file tier-1 imports
    config = manifest.config("joyai-llm-flash")
    assert config["reference"] == "joyai"
    assert config["flops"]["train"] == "joyai:train_flops_per_token"
