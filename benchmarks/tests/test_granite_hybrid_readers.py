"""What the Granite 4.0-H cell brings to the benchmark: its required
operations against a count by hand, its eleven metric files on hand-made
events through the readers that were there, the manifest's lint with the
new configuration and cell, and why the eleven files are not entries yet."""
import importlib
import json

import pytest

from harness import flops, trace as tr
from harness.manifest import Manifest, lint
from required_ops import granite_hybrid as ops
from test_moe_readers import row
from test_step_readers import context, program  # noqa: F401 (a fixture)
from test_trace_reduction import KERNEL, hlo

CELL = "granite-4.0-h-small.packed-s4096-gas1"
METRICS = ["granite.ssm_layer_ms_per_step", "granite.scan_ms_per_step",
           "granite.scan_roofline", "granite.conv_ms_per_step",
           "attention.granite_flash_fwd_roofline",
           "attention.granite_flash_bwd_roofline",
           "moe.granite_route_ms_per_step", "moe.granite_ggemm_ms_per_step",
           "moe.granite_ggemm_fwd_roofline",
           "moe.granite_ggemm_bwd_roofline",
           "moe.granite_shared_expert_ms_per_step"]
BLOCK = "jit(train_step)/ds.fwd_bwd/{}while/body/ds.block/{}/op"


def sizes():
    with open(Manifest().path("configs", "granite-4.0-h-small.json")) as f:
        return json.load(f)["model"]


def value(metric, ctx):
    s = Manifest().layer_metric(metric)
    return importlib.import_module(
        "layer_metrics.readers." + s["reader"]).read(ctx, s["params"])


def test_required_operations_by_hand():
    s = sizes()
    ssm = 4096 * (1024 + 1024 + 256 + 16) + 4 * 1280 + 1024 * 4096
    attn = 4096 * (512 + 2 * 128) + 512 * 4096
    experts = 4096 * 72 + 3 * 4096 * 1536 + 10 * 9 / 72 * 3 * 4096 * 768
    assert ops.mixer_weights(s) == (ssm, attn)
    assert ops.expert_sublayer_weights(s) == pytest.approx(experts)
    # ISSUE 66's count: 128.5 M mixers, 309.7 M expert sublayers, 51.4 M
    assert (9 * ssm + attn, 10 * experts, 4096 * 12544) == pytest.approx(
        (128_562_176, 309_657_600, 51_380_224))
    want = 6 * (9 * ssm + attn + 10 * experts + 4096 * 12544) \
        + 3 * 9 * 6 * 16 * 64 * 128 + 6 * 512 * 1000
    assert ops.train_flops_per_token(s, 1000) == pytest.approx(want)
    assert ops.train_flops_per_token(s, 1679) == pytest.approx(2.964e9,
                                                               rel=1e-3)
    assert flops.resolve("granite_hybrid:train_flops_per_token") \
        is ops.train_flops_per_token
    # one attention layer at the 4 heads held, where
    # causal_attention_flops counts num_layers * d_model = 10 * 4096
    assert ops.attention_layer_flops(100, s, 1000, ["fwd"]) \
        == pytest.approx(0.5 * 4 * 100 * 512 * 1000)
    assert flops.causal_attention_flops(100, s, 1000, ["fwd"]) \
        == pytest.approx(80 * ops.attention_layer_flops(
            100, s, 1000, ["fwd"]))
    # 1.25 held experts a token, three matrices, ten expert sublayers
    assert ops.held_ffn_flops(100, s, 0, ["fwd", "bwd"]) \
        == pytest.approx(18 * 100 * 10 * 1.25 * 4096 * 768)
    assert ops.held_ffn_flops(100, s, 0, ["gate_up"]) \
        == pytest.approx(4 * 100 * 10 * 1.25 * 4096 * 768)
    need, moved = ops.ssd_ops(100, s, 0, ["fwd", "fwd", "bwd"])
    assert need == pytest.approx(900 * 16 * 6 * 64 * 128 * 4)
    inputs = 2 * (1024 + 2 * 128) + 4 * 16
    assert moved == pytest.approx(900 * (4 * inputs + 3 * 2 * 1024))
    # uncut, by the same rules: every head and expert, 36 : 4 layers
    whole = {**s, "num_layers": 40, "layer_kinds": "MMMMMAMMMM" * 4,
             "mamba_heads_held": None, "attn_heads_held": None,
             "kv_heads_held": None, "experts_held": None,
             "vocab_size": 100352}
    assert ops._layers(whole) == (36, 4)
    assert ops.mixer_weights(whole) == (
        4096 * (2 * 8192 + 256 + 128) + 4 * 8448 + 8192 * 4096,
        4096 * (4096 + 2 * 1024) + 4096 * 4096)
    # the card's "A9B": 8.8 B weights multiply a token
    assert ops.train_flops_per_token(whole, 0) / 6 == pytest.approx(
        8.8e9, rel=0.02)


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
            (1600, 1900, hlo("ds_flash_bwd_dq.1", "custom-call", KERNEL)),
            (1900, 1930, hlo("fusion.7", "fusion"))]     # router
    dev = tr.DeviceTrace("/device:TPU:0", {
        tr.OPS: ops_, tr.MODULES: [(0, 1930, "jit_train_step(1)")]})
    ssm = lambda part, outer="": row(BLOCK.format(outer, "ssm/" + part))
    back = "transpose(jvp())/"
    table = {"fusion.1": ssm("scan/ds_ssd_fwd"),
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
                                      "ds_flash_bwd_dq"),
             "fusion.7": row(BLOCK.format("", "mlp/router"))}
    return tr.Trace([dev], {}), table


def test_known_answers_on_hand_made_events(program):  # noqa: F811
    trace, table = synthetic()
    program(table)
    ctx = context(trace, steps=2)
    ctx["model"] = sizes()
    ctx["peaks"] = {**ctx["peaks"], "hbm_bytes_per_s": 819e9}
    ms = lambda ns: ns * 1e-6 / 2
    assert value("granite.ssm_layer_ms_per_step", ctx) \
        == pytest.approx(ms(900))
    assert value("granite.scan_ms_per_step", ctx) == pytest.approx(ms(700))
    assert value("granite.conv_ms_per_step", ctx) == pytest.approx(ms(100))
    assert value("moe.granite_shared_expert_ms_per_step", ctx) \
        == pytest.approx(ms(100))
    assert value("moe.granite_ggemm_ms_per_step", ctx) \
        == pytest.approx(ms(300))
    # router + dispatch + combine: a token's way there and back
    assert value("moe.granite_route_ms_per_step", ctx) \
        == pytest.approx(ms(50 + 150 + 30))
    tokens, s_eff = ctx["tokens_per_step_per_chip"], ctx["s_eff"]
    peaks = ctx["peaks"]
    need, moved = ops.ssd_ops(tokens, ctx["model"], s_eff,
                              ["fwd", "fwd", "bwd"])
    floor = max(moved / peaks["hbm_bytes_per_s"],
                need / peaks["bf16_flops_per_s"])
    assert value("granite.scan_roofline", ctx) == pytest.approx(
        100 * floor * 1e3 / ms(700))
    share = lambda fn, passes, ns: 100 * fn(
        tokens, ctx["model"], s_eff, passes) / peaks["bf16_flops_per_s"] \
        * 1e3 / ms(ns)
    assert value("attention.granite_flash_fwd_roofline", ctx) \
        == pytest.approx(share(ops.attention_layer_flops,
                               ["fwd", "fwd"], 100))
    assert value("attention.granite_flash_bwd_roofline", ctx) \
        == pytest.approx(share(ops.attention_layer_flops, ["bwd"], 300))
    assert value("moe.granite_ggemm_fwd_roofline", ctx) \
        == pytest.approx(share(ops.held_ffn_flops, ["fwd", "fwd"], 100))
    assert value("moe.granite_ggemm_bwd_roofline", ctx) \
        == pytest.approx(share(ops.held_ffn_flops, ["bwd"], 200))


@pytest.mark.parametrize("metric", METRICS)
def test_each_file_names_a_reader_and_a_count_that_are_there(metric):
    """... and a trace without a device plane (the parent commit's traced
    runs of a metric new here) gives None and does not raise."""
    spec = Manifest().layer_metric(metric)
    reader = importlib.import_module("layer_metrics.readers."
                                     + spec["reader"])
    assert callable(reader.read)
    for key in ("flops", "ops"):
        if key in spec["params"]:
            assert spec["params"][key].startswith("granite_hybrid:")
            assert callable(flops.resolve(spec["params"][key]))
    ctx = context(tr.Trace([], {}), steps=2)
    ctx["model"] = sizes()
    ctx["peaks"] = {**ctx["peaks"], "hbm_bytes_per_s": 819e9}
    assert value(metric, ctx) is None


def test_the_manifest_lints_with_the_new_cell_and_has_no_room_for_more():
    """One configuration, one cell appended last, files only.  The eleven
    metric files are NOT entries of ``per_layer``: the manifest held the
    contract's most, 128, before this cell came (PERF.md section 7), so
    they wait as files, read here and by a traced run handed them by name,
    and the cell's traced line carries the fourteen metrics without a
    ``workloads`` list."""
    manifest = Manifest()
    assert lint(manifest) == []
    assert manifest.data["workloads"][-1]["name"] == CELL
    assert manifest.workload(CELL)["chips"] == 1
    entered = [m["name"] for m in manifest.data["per_layer"]]
    assert len(entered) == 128 and not set(METRICS) & set(entered)
    unscoped = [m["name"] for m in manifest.data["per_layer"]
                if "workloads" not in m]
    assert len(unscoped) == 14
    assert unscoped == [m["name"]
                        for m in manifest.metrics("per_layer", CELL)]
    config = manifest.config("granite-4.0-h-small")
    assert config["reference"] == "granite_hybrid"
    assert config["flops"]["train"] == "granite_hybrid:train_flops_per_token"
    assert config["reduced"] == [
        "num_hidden_layers", "num_local_experts", "vocab_size",
        "mamba_n_heads", "num_attention_heads", "num_key_value_heads"]
    assert manifest.data["configs"][-1]["reduced"] == config["reduced"]
    traffic = manifest.traffic("packed-s4096-gas1")
    assert traffic["micro_batch_per_chip"] \
        * traffic["gradient_accumulation_steps"] * traffic["seq_len"] == 4096
    assert traffic["driver"] == "train_steps_counted"
    older = manifest.traffic("packed-s4096-gas8")
    assert traffic["documents"] == older["documents"]
    assert traffic["tokens"]["zipf_exponent"] \
        == older["tokens"]["zipf_exponent"]


def test_every_number_of_the_catalogs_row_is_in_the_file():
    """The source's keys under their own names; the six cut ones are the
    ``reduced`` list and ``published`` has them as the source does."""
    with open(Manifest().path("configs", "granite-4.0-h-small.json")) as f:
        config = json.load(f)
    assert (config["hidden_size"], config["intermediate_size"],
            config["shared_intermediate_size"], config["mamba_d_head"],
            config["mamba_d_state"], config["mamba_d_conv"],
            config["num_experts_per_tok"], config["mamba_n_groups"],
            config["mamba_chunk_size"], config["mamba_expand"]) \
        == (4096, 768, 1536, 64, 128, 4, 10, 1, 256, 2)
    assert (config["embedding_multiplier"], config["residual_multiplier"],
            config["attention_multiplier"], config["logits_scaling"]) \
        == (12, 0.22, 0.0078125, 16)
    assert len(config["layer_types"]) == 40 \
        and [i for i, t in enumerate(config["layer_types"])
             if t == "attention"] == [5, 15, 25, 35]
    published = {k: v for k, v in config["published"].items()
                 if k not in ("what", "n_params")}
    assert published == {
        "num_hidden_layers": 40, "num_local_experts": 72,
        "vocab_size": 100352, "mamba_n_heads": 128,
        "num_attention_heads": 32, "num_key_value_heads": 8}
    assert {k: config[k] for k in published} == {
        "num_hidden_layers": 10, "num_local_experts": 9,
        "vocab_size": 12544, "mamba_n_heads": 16,
        "num_attention_heads": 4, "num_key_value_heads": 1}
    assert config["model"]["chunk_size"] == 128     # assumed: see the file
