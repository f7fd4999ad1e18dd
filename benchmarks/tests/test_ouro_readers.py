"""What the Ouro cell brings to the benchmark: its required operations
against a count by hand, its six metric files on hand-made events through
the readers that were there and — the one that needs no device — from a
CPU rehearsal handed the files by name, the manifest's lint with the new
configuration and cell, every number of the catalog's row, and why the six
files are not entries yet."""
import importlib
import json

import pytest

from harness import flops, trace as tr
from harness.manifest import Manifest, lint
from required_ops import ouro as ops
from rehearse import TOY_PEAKS, toy
from test_moe_readers import row
from test_step_readers import context, program  # noqa: F401 (a fixture)
from test_trace_reduction import KERNEL, hlo

CELL = "ouro-2.6b.packed-s16384-traces"
METRICS = ["ouro.layer_applications_ms_per_step",
           "ouro.exit_heads_ms_per_step", "ouro.exit_gate_ms_per_step",
           "attention.ouro_flash_fwd_roofline",
           "attention.ouro_flash_bwd_roofline", "ouro.expected_exit_pass"]
STEP = "jit(train_step)/ds.fwd_bwd/{}"


def config_file():
    with open(Manifest().path("configs", "ouro-2.6b.json")) as f:
        return json.load(f)


def sizes():
    return config_file()["model"]


def value(metric, ctx):
    s = Manifest().layer_metric(metric)
    return importlib.import_module(
        "layer_metrics.readers." + s["reader"]).read(ctx, s["params"])


def test_required_operations_by_hand():
    s = sizes()
    layers = s["num_layers"]
    assert ops.layer_weights(s) == 51_380_224
    assert ops.applied_weights(s) == 4 * (
        layers * 51_380_224 + 100_663_296 + 2_048)
    want = 6 * ops.applied_weights(s) + 6 * 4 * layers * 2048 * 1000
    assert ops.train_flops_per_token(s, 1000) == pytest.approx(want)
    assert flops.resolve("ouro:train_flops_per_token") \
        is ops.train_flops_per_token
    if layers == 12:
        # ISSUE 70's count: 17.2 + 4.5 = 21.7 GFLOP a token at S_eff 7,691
        assert ops.applied_weights(s) == 2_868_912_128
        assert 6 * ops.applied_weights(s) == pytest.approx(17.2e9, rel=3e-3)
        assert ops.train_flops_per_token(s, 7691) == pytest.approx(
            21.75e9, rel=2e-3)
    # 6 N counts a layer once and the embedding's lookup as a product
    assert 3.3 < ops.train_flops_per_token(s, 0) / (
        6.0 * (s["n_params"] - 2048 * 49152)) < 4.0
    # every application attends: four times what num_layers calls would
    assert ops.attention_layer_flops(100, s, 1000, ["fwd"]) \
        == pytest.approx(0.5 * 4 * 100 * 4 * layers * 2048 * 1000)
    assert ops.attention_layer_flops(100, s, 1000, ["fwd", "fwd", "bwd"]) \
        == pytest.approx(4 * flops.causal_attention_flops(
            100, s, 1000, ["fwd", "fwd", "bwd"]))
    # uncut: the published 48 layers
    whole = {**s, "num_layers": 48}
    assert ops.applied_weights(whole) == 4 * (
        48 * 51_380_224 + 100_663_296 + 2_048)
    assert 4 * 100_663_296 / ops.applied_weights(whole) \
        == pytest.approx(0.039, abs=1e-3)


def synthetic():
    ops_ = [(0, 400, hlo("fusion.1", "fusion")),         # a layer, forward
            (400, 500, hlo("ds_flash_fwd.1", "custom-call", KERNEL)),
            (500, 600, hlo("fusion.2", "fusion")),       # final norm, pass end
            (600, 900, hlo("fusion.3", "fusion")),       # head chunk loop
            (900, 950, hlo("fusion.4", "fusion")),       # the gate
            (950, 1000, hlo("fusion.5", "fusion")),      # head, backward
            (1000, 1100, hlo("ds_flash_fwd.2", "custom-call", KERNEL)),
            (1100, 1400, hlo("ds_flash_bwd_dq.1", "custom-call", KERNEL)),
            (1400, 1600, hlo("ds_flash_bwd_dkv.1", "custom-call", KERNEL)),
            (1600, 1700, hlo("fusion.6", "fusion")),     # a layer, backward
            (1700, 1730, hlo("fusion.7", "fusion")),     # the gate, backward
            (1730, 1800, hlo("fusion.8", "fusion"))]     # the optimizer
    dev = tr.DeviceTrace("/device:TPU:0", {
        tr.OPS: ops_, tr.MODULES: [(0, 1800, "jit_train_step(1)")]})
    block = lambda outer, part, kernel=None: row(STEP.format(
        outer + "while/body/checkpoint/ds.block/" + part), kernel)
    back = "transpose(jvp())/"
    table = {"fusion.1": block("", "mlp/dot_general"),
             "ds_flash_fwd.1": block("", "attn/scores", "ds_flash_fwd"),
             "fusion.2": block("", "cond/branch_1_fun/mul"),
             "fusion.3": row(STEP.format("ds.head_loss/while/body/dot")),
             "fusion.4": row(STEP.format("ds.exit_gate/dot_general")),
             "fusion.5": row(STEP.format(back + "ds.head_loss/mul")),
             "ds_flash_fwd.2": block(back, "attn/scores", "ds_flash_fwd"),
             "ds_flash_bwd_dq.1": block(back, "attn/scores",
                                        "ds_flash_bwd_dq"),
             "ds_flash_bwd_dkv.1": block(back, "attn/scores",
                                         "ds_flash_bwd_dkv"),
             "fusion.6": block(back, "mlp/dot_general"),
             "fusion.7": row(STEP.format(back + "ds.exit_gate/mul")),
             "fusion.8": row("jit(train_step)/ds.optimizer/add")}
    return tr.Trace([dev], {}), table


def test_known_answers_on_hand_made_events(program):  # noqa: F811
    trace, table = synthetic()
    program(table)
    ctx = context(trace, steps=2)
    ctx["model"] = sizes()
    ms = lambda ns: ns * 1e-6 / 2
    assert value("ouro.layer_applications_ms_per_step", ctx) \
        == pytest.approx(ms(400 + 100 + 100 + 100 + 300 + 200 + 100))
    assert value("ouro.exit_heads_ms_per_step", ctx) \
        == pytest.approx(ms(300 + 50))
    assert value("ouro.exit_gate_ms_per_step", ctx) \
        == pytest.approx(ms(50 + 30))
    tokens, s_eff, peaks = ctx["tokens_per_step_per_chip"], ctx["s_eff"], \
        ctx["peaks"]
    share = lambda passes, ns: 100 * ops.attention_layer_flops(
        tokens, ctx["model"], s_eff, passes) / peaks["bf16_flops_per_s"] \
        * 1e3 / ms(ns)
    assert value("attention.ouro_flash_fwd_roofline", ctx) \
        == pytest.approx(share(["fwd", "fwd"], 200))
    assert value("attention.ouro_flash_bwd_roofline", ctx) \
        == pytest.approx(share(["bwd"], 500))


def test_the_expected_exit_pass_over_a_hand_made_account(monkeypatch):
    from deepspeed_tpu.telemetry import tracing
    even = {"ouro/exit_mass_1": 500, "ouro/exit_mass_2": 250,
            "ouro/exit_mass_3": 125, "ouro/exit_mass_4": 125,
            "ouro/scored_tokens": 1000, "ouro/exit_pass_tokens": 1875}
    later = {**even, "ouro/exit_pass_tokens": 3000}
    monkeypatch.setattr(tracing, "step_load", lambda program: {
        "steps": 3, "totals": {}, "last": [even, even, later]})
    assert value("ouro.expected_exit_pass", {"steps": 2}) \
        == pytest.approx((1875 + 3000) / 2000)
    assert value("ouro.expected_exit_pass", {"steps": 1}) == 3.0
    monkeypatch.setattr(tracing, "step_load", lambda program: None)
    assert value("ouro.expected_exit_pass", {"steps": 2}) is None


@pytest.mark.parametrize("metric", METRICS)
def test_each_file_names_a_reader_and_a_count_that_are_there(metric):
    """... and a trace without a device plane, from a program that has no
    such step (the parent commit's traced runs of a metric new here),
    gives None and does not raise."""
    spec = Manifest().layer_metric(metric)
    reader = importlib.import_module("layer_metrics.readers."
                                     + spec["reader"])
    assert callable(reader.read)
    if "flops" in spec["params"]:
        assert spec["params"]["flops"] == "ouro:attention_layer_flops"
        assert callable(flops.resolve(spec["params"]["flops"]))
    ctx = context(tr.Trace([], {}), steps=2)
    ctx["model"] = sizes()
    assert value(metric, ctx) is None


def test_a_cpu_rehearsal_handed_the_files_reads_what_needs_no_device(
        tmp_path):
    """The cell at toy size through the driver with the six files handed
    by name, as an entry would hand them: the device readers find no TPU
    plane and are left out; where the tokens leave comes from the step's
    own outputs."""
    from drivers import train_steps_counted
    import jax
    manifest = Manifest()
    cell, config, traffic = toy(manifest, CELL)
    result = train_steps_counted.run_cell(
        CELL, config, traffic,
        {name: manifest.layer_metric(name) for name in METRICS}, 1, 0.3,
        True, jax.devices()[:1], TOY_PEAKS, t_origin=0.0,
        work_dir=str(tmp_path / "work"))
    assert result["correct"] is True
    assert set(result["metrics"]) == {"ouro.expected_exit_pass"}
    # every gate starts near a half: 1 x .5 + 2 x .25 + (3 + 4) x .125
    assert result["metrics"]["ouro.expected_exit_pass"] \
        == pytest.approx(1.875, abs=0.2)


def test_the_manifest_lints_with_the_new_cell_and_has_no_room_for_more():
    """One configuration, one cell appended last, files only.  The six
    metric files are NOT entries of ``per_layer``: the manifest holds the
    contract's most, 128 (PERF.md section 7), so they wait as files, read
    here and by a traced run handed them by name, and the cell's traced
    line carries the fourteen metrics without a ``workloads`` list."""
    manifest = Manifest()
    assert lint(manifest) == []
    assert manifest.data["workloads"][-1]["name"] == CELL
    assert manifest.data["configs"][-1]["name"] == "ouro-2.6b"
    assert manifest.workload(CELL)["chips"] == 1
    entered = [m["name"] for m in manifest.data["per_layer"]]
    assert len(entered) == 128 and not set(METRICS) & set(entered)
    unscoped = [m["name"] for m in manifest.data["per_layer"]
                if "workloads" not in m]
    assert len(unscoped) == 14
    assert unscoped == [m["name"]
                        for m in manifest.metrics("per_layer", CELL)]
    config = manifest.config("ouro-2.6b")
    assert config["reference"] == "ouro"
    assert config["flops"]["train"] == "ouro:train_flops_per_token"
    assert config["reduced"] == ["num_hidden_layers"] \
        == manifest.data["configs"][-1]["reduced"]
    assert config["checks"]["require_kernels"] == [
        "ds_flash_fwd", "ds_flash_bwd_dkv", "ds_flash_bwd_dq"]
    # the traffic is the Phi-4 and Kimi-Linear cells', to the byte: no new
    # traffic file
    assert manifest.workload(CELL)["traffic"] == "packed-s16384-traces" \
        == manifest.workload(
            "phi-4-mini-flash-reasoning.packed-s16384-traces")["traffic"]


def test_every_number_of_the_catalogs_row_is_in_the_file():
    """The source's keys under their own names; the one cut is the
    ``reduced`` list and ``published`` has it as the source does; every
    ``assumed`` key that is an equation has its control in
    tests/test_ouro.py."""
    config = config_file()
    assert (config["hidden_size"], config["intermediate_size"],
            config["head_dim"], config["num_attention_heads"],
            config["num_key_value_heads"], config["vocab_size"],
            config["total_ut_steps"], config["rope_theta"],
            config["rms_norm_eps"], config["max_position_embeddings"]) \
        == (2048, 5632, 128, 16, 16, 49152, 4, 1000000, 1e-06, 65536)
    assert config["tie_word_embeddings"] is False
    assert config["early_exit_threshold"] == 1
    assert config["model_type"] == "ouro"
    assert config["layer_types"] == ["full_attention"] * 48
    assert config["published"]["num_hidden_layers"] == 48
    assert config["published"]["n_params"] == 2_667_974_657
    layers = config["num_hidden_layers"]
    assert layers == config["model"]["num_layers"] \
        == config["builder"]["kwargs"]["num_layers"]
    assert (layers, config["model"]["n_params"]) in (
        (12, 817_991_681), (8, 612_438_017))
    assert {"sandwich_norms", "final_norm_every_pass", "exit_gate",
            "loss", "exit_entropy_beta"} <= set(config["assumed"])
