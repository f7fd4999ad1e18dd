"""What the Mellum 2 cell brings to the benchmark: its required operations
and the exchange's wire bytes against a count by hand, its thirteen metric
files on hand-made events through the readers (one of them new:
scoped_collective), and its metrics being its own cell's alone."""
import importlib
import json

import pytest

from harness import flops, trace as tr
from harness.manifest import Manifest
from layer_metrics.readers import window_roofline
from required_ops import mellum2 as ops
from test_step_readers import context, program  # noqa: F401 (a fixture)
from test_trace_reduction import KERNEL, hlo

CELL = "mellum2-12b-a2.5b-ep4.packed-s8192-gas4-ep"
METRICS = ["moe.exchange_ms_per_step", "moe.exchange_exposed_ms_per_step",
           "moe.exchange_gbps_per_chip",
           "comm.ep_dense_reduce_exposed_ms_per_step",
           "moe.ep_ggemm_ms_per_step", "moe.ep_ggemm_fwd_roofline",
           "moe.ep_ggemm_bwd_roofline", "moe.ep_dispatch_ms_per_step",
           "moe.ep_padded_row_share_pct",
           "attention.w1024_flash_fwd_roofline",
           "attention.w1024_flash_bwd_roofline",
           "attention.mellum_full_flash_fwd_roofline",
           "attention.mellum_full_flash_bwd_roofline"]
BLOCK = "jit(train_step)/ds.fwd_bwd/{}while/body/ds.block/{}/op"


def sizes():
    with open(Manifest().path("configs", "mellum2-12b-a2.5b-ep4.json")) as f:
        return json.load(f)["model"]


def traffic():
    with open(Manifest().path("traffic", "packed-s8192-gas4-ep.json")) as f:
        return json.load(f)


def value(metric, ctx):
    s = Manifest().layer_metric(metric)
    return importlib.import_module(
        "layer_metrics.readers." + s["reader"]).read(ctx, s["params"])


def row(scope, kernel=None, collective=None, wire_bytes=None):
    return {"scope": scope, "phase": "forward", "kernel": kernel,
            "collective": collective, "wire_bytes": wire_bytes}


def test_required_operations_by_hand():
    s = sizes()
    assert ops.layer_kinds(s) == (1, 3)
    assert ops.layer_kinds({**s, "num_layers": 28}) == (7, 21)
    attention = 2 * 2304 * 4096 + 2 * 2304 * 512
    assert ops.attention_weights(s) == attention == 21_233_664
    layer = attention + 2304 * 64 + 8 * 3 * 2304 * 896
    weights = 4 * layer + 2304 * 98304
    # the head is 44% of the weights that multiply a token here, 10% at 28
    assert 2304 * 98304 / weights == pytest.approx(0.443, abs=2e-3)
    assert 2304 * 98304 / (28 * layer + 2304 * 98304) \
        == pytest.approx(0.102, abs=2e-3)
    keys = (1024 * 1025 / 2 + 1024 * 976) / 2000
    assert ops.window_keys_times_two(2000, 1024) == pytest.approx(2 * keys)
    assert ops.window_keys_times_two(300, 1024) == 301
    want = 6 * weights + 6 * 1 * 4096 * 2000 + 6 * 3 * 4096 * 2 * keys
    assert ops.train_flops_per_token(s, 2000) == pytest.approx(want)
    assert flops.resolve("mellum2:train_flops_per_token") \
        is ops.train_flops_per_token
    assert ops.full_layer_attention_flops(100, s, 1000, ["fwd"]) \
        == pytest.approx(0.5 * 4 * 100 * 1 * 4096 * 1000)
    assert ops.window_layer_attention_flops(100, s, 786, ["fwd", "bwd"]) \
        == pytest.approx(0.5 * 12 * 100 * 3 * 4096 * 786)
    # a chip's own tokens' rows: 8 experts a token, three matrices, 4 layers
    assert ops.swiglu_ffn_flops(100, s, 0, ["fwd", "bwd"]) \
        == pytest.approx(18 * 100 * 4 * 8 * 2304 * 896)
    # ISSUE 47's count: 49,152 rows x 2304 x 2 B = 226 MB an all-to-all,
    # six a layer and micro-batch, 4 layers, 4 micro-batches: 21.7 GB
    assert ops.exchange_wire_bytes(8192, s, 4, passes=1) / 4 \
        == 49152 * 2304 * 2 == 226_492_416
    assert ops.exchange_wire_bytes(32768, s, 4) \
        == pytest.approx(21.74e9, rel=1e-3)


def synthetic():
    a2a = lambda n: hlo(f"all_to_all.{n}", "all-to-all")
    ops_ = [(0, 100, hlo("fusion.1", "fusion")),         # dispatch: plan
            (100, 300, a2a(1)),                          # send, exposed
            (300, 400, hlo("fusion.2", "fusion")),       # dispatch: held
            (400, 700, hlo("ds_ggemm_fwd.1", "custom-call", KERNEL)),
            (700, 800, hlo("ds_rowsum.1", "custom-call", KERNEL)),
            (800, 1000, a2a(2)),                         # return ...
            (1000, 1100, hlo("fusion.3", "fusion")),     # combine
            (1100, 1300, hlo("ds_ggemm_dx.1", "custom-call", KERNEL)),
            (1300, 1500, hlo("ds_ggemm_dw.1", "custom-call", KERNEL)),
            (1500, 1700, hlo("ds_flash_win_fwd.1", "custom-call", KERNEL)),
            (1700, 2100, hlo("ds_flash_fwd.1", "custom-call", KERNEL)),
            (2100, 2400, hlo("ds_flash_win_bwd_dq.1", "custom-call",
                             KERNEL)),
            (2400, 2500, hlo("ds_flash_win_bwd_dkv.1", "custom-call",
                             KERNEL)),
            (2500, 3100, hlo("ds_flash_bwd_dq.1", "custom-call", KERNEL)),
            (3100, 3200, hlo("reduce-scatter.1", "reduce-scatter")),
            (3200, 3300, hlo("all_to_all.9", "all-to-all"))]  # not ours
    # the return overlaps a fusion on another line for half of its time
    dev = tr.DeviceTrace("/device:TPU:0", {
        tr.OPS: ops_, tr.MODULES: [(0, 3300, "jit_train_step(1)")]})
    mlp = lambda part: BLOCK.format("", "mlp/shard_map/" + part)
    table = {
        "fusion.1": row(mlp("dispatch")),
        "all_to_all.1": row(mlp("exchange/exchange_send"), None, "all-to-all", 3000),
        "fusion.2": row(mlp("dispatch")),
        "ds_ggemm_fwd.1": row(mlp("experts"), "ds_ggemm_fwd"),
        "ds_rowsum.1": row(mlp("combine"), "ds_rowsum"),
        "all_to_all.2": row(mlp("exchange/exchange_return"), None, "all-to-all",
                            5000),
        "fusion.3": row(mlp("combine")),
        "ds_ggemm_dx.1": row(mlp("experts"), "ds_ggemm_dx"),
        "ds_ggemm_dw.1": row(mlp("experts"), "ds_ggemm_dw"),
        "ds_flash_win_fwd.1": row(BLOCK.format("", "ds.attn_sliding/attn"),
                                  "ds_flash_win_fwd"),
        "ds_flash_fwd.1": row(BLOCK.format("", "ds.attn_full/attn"),
                              "ds_flash_fwd"),
        "ds_flash_win_bwd_dq.1": row("x", "ds_flash_win_bwd_dq"),
        "ds_flash_win_bwd_dkv.1": row("x", "ds_flash_win_bwd_dkv"),
        "ds_flash_bwd_dq.1": row("x", "ds_flash_bwd_dq"),
        "reduce-scatter.1": row("jit(train_step)/ds.optimizer/x", None,
                                "reduce-scatter", 100),
        "all_to_all.9": row("jit(train_step)/ds.embed/x", None,
                            "all-to-all", 7)}
    return tr.Trace([dev], {}), table


def test_known_answers_on_hand_made_events(program, monkeypatch):  # noqa: F811
    trace, table = synthetic()
    program(table)
    ctx = context(trace, steps=2)
    ctx["model"], ctx["traffic"] = sizes(), traffic()
    ms = lambda ns: ns * 1e-6 / 2
    # the two all-to-alls under exchange/, not the one under ds.embed
    assert value("moe.exchange_ms_per_step", ctx) == pytest.approx(ms(400))
    assert value("moe.exchange_exposed_ms_per_step", ctx) \
        == pytest.approx(ms(400))
    assert value("moe.exchange_gbps_per_chip", ctx) \
        == pytest.approx(8000 / 400)
    assert value("comm.ep_dense_reduce_exposed_ms_per_step", ctx) \
        == pytest.approx(ms(100))
    assert value("moe.ep_ggemm_ms_per_step", ctx) == pytest.approx(ms(700))
    # dispatch and combine (the rowsum kernel among them), not the exchange
    assert value("moe.ep_dispatch_ms_per_step", ctx) \
        == pytest.approx(ms(100 + 100 + 100 + 100))
    tokens, s_eff = ctx["tokens_per_step_per_chip"], ctx["s_eff"]
    share = lambda fn, passes, ns, keys=s_eff: 100 * fn(
        tokens, ctx["model"], keys, passes) \
        / ctx["peaks"]["bf16_flops_per_s"] * 1e3 / ms(ns)
    keys = window_roofline.keys_times_two(ctx["traffic"], 1024)
    assert value("attention.w1024_flash_fwd_roofline", ctx) \
        == pytest.approx(share(ops.window_layer_attention_flops,
                               ["fwd", "fwd"], 200, keys))
    assert value("attention.w1024_flash_bwd_roofline", ctx) \
        == pytest.approx(share(ops.window_layer_attention_flops,
                               ["bwd"], 400, keys))
    assert value("attention.mellum_full_flash_fwd_roofline", ctx) \
        == pytest.approx(share(ops.full_layer_attention_flops,
                               ["fwd", "fwd"], 400))
    assert value("attention.mellum_full_flash_bwd_roofline", ctx) \
        == pytest.approx(share(ops.full_layer_attention_flops,
                               ["bwd"], 600))
    assert value("moe.ep_ggemm_fwd_roofline", ctx) \
        == pytest.approx(share(ops.swiglu_ffn_flops, ["fwd", "gate_up"], 300))
    # five forward products a layer-pass where two whole passes are six
    assert share(ops.swiglu_ffn_flops, ["fwd", "gate_up"], 300) * 6 \
        == pytest.approx(share(ops.swiglu_ffn_flops, ["fwd", "fwd"], 300) * 5)
    assert value("moe.ep_ggemm_bwd_roofline", ctx) \
        == pytest.approx(share(ops.swiglu_ffn_flops, ["bwd"], 400))
    from deepspeed_tpu.telemetry import tracing
    monkeypatch.setattr(tracing, "grouped_gemm_rows", lambda name: {
        "routed_rows_per_call": 65536, "padded_rows_per_call": 133120})
    assert value("moe.ep_padded_row_share_pct", ctx) \
        == pytest.approx(100 * (1 - 65536 / 133120))


def test_a_program_without_an_exchange_reads_nothing(program):  # noqa: F811
    """A program whose map has no all-to-all under an exchange scope (any
    commit before this one, on any cell): the three exchange metrics are
    left out and nothing raises."""
    trace, table = synthetic()
    program({name: r for name, r in table.items()
             if "exchange" not in (r["scope"] or "")})
    ctx = context(trace, steps=2)
    for metric in METRICS[:3]:
        assert value(metric, ctx) is None


@pytest.mark.parametrize("metric", METRICS)
def test_no_device_plane_reads_nothing(metric, monkeypatch):
    """What a CPU rehearsal needs of a metric new here: a trace without a
    device plane gives None and does not raise."""
    from deepspeed_tpu.telemetry import tracing
    monkeypatch.setattr(tracing, "grouped_gemm_rows", lambda name: None)
    ctx = context(tr.Trace([], {}), steps=2)
    ctx["model"], ctx["traffic"] = sizes(), traffic()
    assert value(metric, ctx) is None


def test_the_metrics_are_the_new_cells_alone():
    manifest = Manifest()
    for m in manifest.data["per_layer"]:
        if m["name"] in METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "tokens_per_s_per_chip"
    assert set(METRICS) <= {
        m["name"] for m in manifest.metrics("per_layer", CELL)}
    cell, config, mix = manifest.cell(CELL)
    assert cell["chips"] == 4
    assert config["reference"] == "mellum2"
    assert config["flops"]["train"] == "mellum2:train_flops_per_token"
    assert config["deployment"]["mesh"] == {"axes": ["expert"], "shape": [4]}
    assert config["deployment"]["engine_config"]["mesh"] \
        == {"expert_parallel_size": 4}
    assert mix["driver"] == "train_steps_ep"
    # packed-s8192-gas4's mix, differing only in the driver (and the words)
    other = manifest.traffic("packed-s8192-gas4")
    assert {k: v for k, v in mix.items()
            if k not in ("driver", "what", "name")} \
        == {k: v for k, v in other.items()
            if k not in ("driver", "what", "name")}
