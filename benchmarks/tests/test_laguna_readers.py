"""What the Laguna-S-2.1 cell brings to the benchmark: its required
operations against a count by hand, its twelve metric files on hand-made
events through the readers (two of them new), and its metrics being its
own cell's alone."""
import importlib
import json

import pytest

from harness import datagen, flops, trace as tr
from harness.manifest import Manifest
from layer_metrics.readers import window_roofline
from required_ops import laguna as ops
from test_moe_readers import row
from test_step_readers import context, program  # noqa: F401 (a fixture)
from test_trace_reduction import KERNEL, hlo

CELL = "laguna-s-2.1.packed-s8192-gas4"
METRICS = ["attention.window_layer_ms_per_step",
           "attention.full_layer_ms_per_step",
           "attention.window_flash_fwd_roofline",
           "attention.window_flash_bwd_roofline",
           "attention.laguna_full_flash_fwd_roofline",
           "attention.laguna_full_flash_bwd_roofline",
           "attention.window_keys_visited_per_query",
           "moe.laguna_ggemm_ms_per_step", "moe.laguna_ggemm_fwd_roofline",
           "moe.laguna_ggemm_bwd_roofline", "moe.laguna_dispatch_ms_per_step",
           "moe.laguna_shared_expert_ms_per_step"]
BLOCK = "jit(train_step)/ds.fwd_bwd/{}while/body/ds.block/{}/op"


def sizes():
    with open(Manifest().path("configs", "laguna-s-2.1.json")) as f:
        return json.load(f)["model"]


def traffic():
    with open(Manifest().path("traffic", "packed-s8192-gas4.json")) as f:
        return json.load(f)


def value(metric, ctx):
    s = Manifest().layer_metric(metric)
    return importlib.import_module(
        "layer_metrics.readers." + s["reader"]).read(ctx, s["params"])


def test_required_operations_by_hand():
    s = sizes()
    assert ops.layer_kinds(s) == (2, 3)
    assert ops.layer_kinds({**s, "num_layers": 48}) == (12, 36)
    full = 2 * 3072 * 6144 + 2 * 3072 * 1024 + 3072 * 48
    sliding = 2 * 3072 * 9216 + 2 * 3072 * 1024 + 3072 * 72
    assert ops.attention_weights(s, 48) == full == 44_187_648
    assert ops.attention_weights(s, 72) == sliding == 63_135_744
    experts = 3072 * 256 + 3 * 3072 * 1024 \
        + 10 * 8 / 256 * 3 * 3072 * 1024
    weights = 2 * full + 3 * sliding + 3 * 3072 * 12288 + 4 * experts \
        + 3072 * 12544
    # one span of 1000 positions under a window of 512: 512 * 513 / 2 keys
    # for the first 512 queries, 512 each for the other 488
    keys = (512 * 513 / 2 + 512 * 488) / 1000
    assert ops.window_keys_times_two(1000, 512) == pytest.approx(2 * keys)
    assert ops.window_keys_times_two(300, 512) == 301
    want = 6 * weights + 6 * 2 * 6144 * 1000 + 6 * 3 * 9216 * 2 * keys
    assert ops.train_flops_per_token(s, 1000) == pytest.approx(want)
    assert ops.train_flops_per_token(s, 2152) == pytest.approx(3.2019e9,
                                                               rel=1e-4)
    assert flops.resolve("laguna:train_flops_per_token") \
        is ops.train_flops_per_token
    # two full layers at 6144 where causal_attention_flops counts
    # num_layers * d_model = 5 * 3072: 0.8 of it
    assert ops.full_layer_attention_flops(100, s, 1000, ["fwd"]) \
        == pytest.approx(0.5 * 4 * 100 * 2 * 6144 * 1000)
    assert ops.full_layer_attention_flops(100, s, 1000, ["fwd"]) \
        == pytest.approx(0.8 * flops.causal_attention_flops(
            100, s, 1000, ["fwd"]))
    # three sliding layers at 9216 over the keys handed over
    assert ops.window_layer_attention_flops(100, s, 786, ["fwd", "bwd"]) \
        == pytest.approx(0.5 * 12 * 100 * 3 * 9216 * 786)
    # 0.3125 held experts a token, three matrices, four expert layers
    assert ops._held_share(s) == 0.3125
    assert ops.held_swiglu_ffn_flops(100, s, 0, ["fwd", "bwd"]) \
        == pytest.approx(18 * 100 * 4 * 0.3125 * 3072 * 1024)


def test_the_required_keys_are_the_samples():
    """Inside the document AND the window, over effective_context's own
    sample: 393 keys a query at S 8192 where the causal mask alone leaves
    1,076; the closed form for one span of S_eff, which mfu_pct's function
    has to use, is an upper bound (Jensen)."""
    t = traffic()
    windowed = window_roofline.keys_times_two(t, 512)
    assert windowed / 2 == pytest.approx(392.6, abs=0.1)
    # a window no document reaches: the causal count (S_eff + 1: a query
    # attends itself, which sum(len^2) / sum(len) leaves out)
    s_eff = datagen.effective_context(t)
    assert window_roofline.keys_times_two(t, 10 ** 9) \
        == pytest.approx(s_eff + 1)
    closed = ops.window_keys_times_two(s_eff, 512)
    assert closed / 2 == pytest.approx(451.2, abs=0.1)
    assert 1.14 < closed / windowed < 1.16
    # unpacked: one span of the sequence
    assert window_roofline.keys_times_two(
        {**t, "segment_ids": False}, 512) == pytest.approx(
            ops.window_keys_times_two(8192, 512))


def synthetic():
    ops_ = [(0, 100, hlo("fusion.1", "fusion")),         # sliding: q, k, v
            (100, 150, hlo("fusion.2", "fusion")),       # ... rope
            (150, 350, hlo("ds_flash_win_fwd.1", "custom-call", KERNEL)),
            (350, 400, hlo("fusion.3", "fusion")),       # ... head gate
            (400, 500, hlo("fusion.4", "fusion")),       # ... out_proj
            (500, 900, hlo("ds_flash_fwd.1", "custom-call", KERNEL)),
            (900, 1000, hlo("fusion.5", "fusion")),      # full: out_proj
            (1000, 1100, hlo("fusion.6", "fusion")),     # shared expert
            (1100, 1200, hlo("ds_ggemm_fwd.1", "custom-call", KERNEL)),
            (1200, 1250, hlo("sort.1", "sort")),         # the held plan
            (1250, 1400, hlo("fusion.7", "fusion")),     # sum into tokens
            (1400, 1600, hlo("ds_ggemm_dw.1", "custom-call", KERNEL)),
            (1600, 1900, hlo("ds_flash_win_bwd_dq.1", "custom-call", KERNEL)),
            (1900, 2000, hlo("ds_flash_win_bwd_dkv.1", "custom-call",
                             KERNEL)),
            (2000, 2600, hlo("ds_flash_bwd_dq.1", "custom-call", KERNEL)),
            (2600, 2700, hlo("fusion.8", "fusion"))]     # the dense MLP
    dev = tr.DeviceTrace("/device:TPU:0", {
        tr.OPS: ops_, tr.MODULES: [(0, 2700, "jit_train_step(1)")]})
    sliding = lambda part, outer="": BLOCK.format(
        outer, "ds.attn_sliding/attn/" + part)
    full = lambda part, outer="": BLOCK.format(
        outer, "ds.attn_full/attn/" + part)
    back = "transpose(jvp())/"
    table = {"fusion.1": row(sliding("dot_general")),
             "fusion.2": row(sliding("rope")),
             "ds_flash_win_fwd.1": row(sliding("scores"),
                                       "ds_flash_win_fwd"),
             "fusion.3": row(sliding("ds.head_gate")),
             "fusion.4": row(sliding("out_proj")),
             "ds_flash_fwd.1": row(full("scores"), "ds_flash_fwd"),
             "fusion.5": row(full("out_proj")),
             "fusion.6": row(BLOCK.format("", "mlp/shared_expert")),
             "ds_ggemm_fwd.1": row(BLOCK.format("", "mlp/experts"),
                                   "ds_ggemm_fwd"),
             "sort.1": row(BLOCK.format("", "mlp/dispatch")),
             "fusion.7": row(BLOCK.format("", "mlp/combine")),
             "ds_ggemm_dw.1": row(BLOCK.format(back, "mlp/experts"),
                                  "ds_ggemm_dw"),
             "ds_flash_win_bwd_dq.1": row(sliding("scores", back),
                                          "ds_flash_win_bwd_dq"),
             "ds_flash_win_bwd_dkv.1": row(sliding("scores", back),
                                           "ds_flash_win_bwd_dkv"),
             "ds_flash_bwd_dq.1": row(full("scores", back),
                                      "ds_flash_bwd_dq"),
             "fusion.8": row(BLOCK.format("", "ds.lead_mlp/mlp"))}
    return tr.Trace([dev], {}), table


def test_known_answers_on_hand_made_events(program):  # noqa: F811
    trace, table = synthetic()
    program(table)
    ctx = context(trace, steps=2)
    ctx["model"], ctx["traffic"] = sizes(), traffic()
    ms = lambda ns: ns * 1e-6 / 2
    assert value("attention.window_layer_ms_per_step", ctx) \
        == pytest.approx(ms(100 + 50 + 200 + 50 + 100 + 300 + 100))
    assert value("attention.full_layer_ms_per_step", ctx) \
        == pytest.approx(ms(400 + 100 + 600))
    assert value("moe.laguna_shared_expert_ms_per_step", ctx) \
        == pytest.approx(ms(100))
    assert value("moe.laguna_ggemm_ms_per_step", ctx) \
        == pytest.approx(ms(300))
    assert value("moe.laguna_dispatch_ms_per_step", ctx) \
        == pytest.approx(ms(200))
    tokens, s_eff = ctx["tokens_per_step_per_chip"], ctx["s_eff"]
    share = lambda fn, passes, ns, keys=s_eff: 100 * fn(
        tokens, ctx["model"], keys, passes) \
        / ctx["peaks"]["bf16_flops_per_s"] * 1e3 / ms(ns)
    # the windowed kernels against the sample's keys, not the context's
    # S_eff; the causal ones against S_eff
    keys = window_roofline.keys_times_two(ctx["traffic"], 512)
    assert value("attention.window_flash_fwd_roofline", ctx) \
        == pytest.approx(share(ops.window_layer_attention_flops,
                               ["fwd", "fwd"], 200, keys))
    assert value("attention.window_flash_bwd_roofline", ctx) \
        == pytest.approx(share(ops.window_layer_attention_flops,
                               ["bwd"], 400, keys))
    assert value("attention.laguna_full_flash_fwd_roofline", ctx) \
        == pytest.approx(share(ops.full_layer_attention_flops,
                               ["fwd", "fwd"], 400))
    assert value("attention.laguna_full_flash_bwd_roofline", ctx) \
        == pytest.approx(share(ops.full_layer_attention_flops,
                               ["bwd"], 600))
    assert value("moe.laguna_ggemm_fwd_roofline", ctx) \
        == pytest.approx(share(ops.held_swiglu_ffn_flops,
                               ["fwd", "fwd"], 100))
    assert value("moe.laguna_ggemm_bwd_roofline", ctx) \
        == pytest.approx(share(ops.held_swiglu_ffn_flops, ["bwd"], 200))


def test_a_program_without_windowed_kernels_reads_nothing(program):  # noqa: F811,E501
    """The parent commit's traced runs (this PR's benchmark files laid over
    it): its map has no ds_flash_win_* kernel and its account no windowed
    row, so the three readers that are new return None and do not raise."""
    trace, table = synthetic()
    program({name: r for name, r in table.items()
             if "win" not in (r["kernel"] or "")})
    ctx = context(trace, steps=2)
    ctx["model"], ctx["traffic"] = sizes(), traffic()
    assert value("attention.window_flash_fwd_roofline", ctx) is None
    assert value("attention.window_flash_bwd_roofline", ctx) is None
    assert value("attention.window_keys_visited_per_query", ctx) is None


def test_the_account_gives_the_keys_visited(monkeypatch):
    from deepspeed_tpu.telemetry import tracing
    rows = [{"heads": 48, "blocks": [512, 512]},
            {"heads": 72, "blocks": [256, 256], "window": 512,
             "k_tiles_per_q_block": 3}]
    monkeypatch.setattr(tracing, "flash_calls", lambda name: rows)
    assert value("attention.window_keys_visited_per_query", {}) == 768.0
    monkeypatch.setattr(tracing, "flash_calls", lambda name: rows[:1])
    assert value("attention.window_keys_visited_per_query", {}) is None
    monkeypatch.setattr(tracing, "flash_calls", lambda name: None)
    assert value("attention.window_keys_visited_per_query", {}) is None


@pytest.mark.parametrize("metric", METRICS)
def test_no_device_plane_reads_nothing(metric, monkeypatch):
    """What a CPU rehearsal needs of a metric new here: a trace without a
    device plane gives None and does not raise."""
    from deepspeed_tpu.telemetry import tracing
    monkeypatch.setattr(tracing, "flash_calls", lambda name: None)
    ctx = context(tr.Trace([], {}), steps=2)
    ctx["model"], ctx["traffic"] = sizes(), traffic()
    assert value(metric, ctx) is None


def test_the_metrics_are_the_new_cells_alone():
    manifest = Manifest()
    for m in manifest.data["per_layer"]:
        if m["name"] in METRICS:
            assert m["workloads"] == [CELL]
    assert set(METRICS) <= {
        m["name"] for m in manifest.metrics("per_layer", CELL)}
    # after the seven cells that were there (later cells go after it)
    names = [w["name"] for w in manifest.data["workloads"]]
    assert names.index(CELL) == 7
    config = manifest.config("laguna-s-2.1")
    assert config["reference"] == "laguna"
    assert config["flops"]["train"] == "laguna:train_flops_per_token"
    assert config["deployment"]["chips"] == 1
    assert manifest.traffic("packed-s8192-gas4")["micro_batch_per_chip"] == 1
