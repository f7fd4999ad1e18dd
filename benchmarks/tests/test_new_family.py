"""A configuration of another model family is new files and two entries:
the driver knows no family.  The fixture (data/new_family/) is the
program's Mixtral at its ``tiny`` size with dropless dispatch — a family
the driver had never seen — with its plain reference, its own function of
required operations, a traffic file; nothing of it is a supported
configuration.  Also: kernels required by name, and the functions of
``harness/flops.py`` against counts made by hand."""
import copy
import json
import os
import shutil

import pytest

from drivers import train_steps
from harness import flops
from harness.manifest import Manifest, ROOT, lint
from rehearse import rehearse, toy

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "new_family")
CELL = "mixtral-tiny.dense-s64"
# Mixtral tiny by hand: D 32, 4 heads and 2 kv heads of 8, 4 experts of
# width 64, 2 per token, vocabulary 256.  Weights a token multiplies, per
# layer: q, k, v 32*(4 + 2*2)*8 = 2048, out 32*32 = 1024, router 32*4 =
# 128, two experts 2*3*32*64 = 12288 -> 15488; the head 32*256 = 8192.
LAYER, HEAD = 15488, 8192
TINY = {"num_layers": 2, "d_model": 32, "num_heads": 4, "num_kv_heads": 2,
        "head_dim": 8, "d_ff": 64, "num_experts": 4, "top_k": 2,
        "vocab_size": 256}
# OLMoE-1B-7B cut to 2 of 16 layers (ISSUE 27): 16 heads of 128 (MHA), 64
# experts of width 1024, 8 per token, vocabulary 50304
OLMOE_2L = {"num_layers": 2, "d_model": 2048, "num_heads": 16,
            "num_kv_heads": 16, "head_dim": 128, "d_ff": 1024,
            "num_experts": 64, "top_k": 8, "vocab_size": 50304,
            "n_params": 1045178368}


def run_line(capsys):
    return next(json.loads(line)
                for line in capsys.readouterr().out.split("\n")
                if line.startswith('{"line": "run"'))


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A copy of the benchmark with the fixture's files added and its two
    entries in BENCHMARK.json: no file that was there is touched."""
    root = str(tmp_path / "checkout")
    bench = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for folder, _, files in os.walk(FIXTURE):
        for name in files:
            if name.endswith(".pyc"):
                continue
            to = os.path.join(bench, os.path.relpath(folder, FIXTURE), name)
            assert not os.path.exists(to), f"{to} was there"
            os.makedirs(os.path.dirname(to), exist_ok=True)
            shutil.copy(os.path.join(folder, name), to)
    data = copy.deepcopy(Manifest().data)
    data["configs"].append({
        "name": "mixtral-tiny", "source": "a test's fixture", "reduced": [],
        "file": "benchmarks/configs/mixtral-tiny.json", "why": "a family "
        "the driver has never seen"})
    data["workloads"].append({
        "name": CELL, "config": "mixtral-tiny", "traffic": "dense-s64",
        "chips": 1, "why": "S 64, micro 2: routed experts, dropless"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    # the family's reference and required operations are found beside the
    # harness's own, as in a checkout that holds them
    monkeypatch.syspath_prepend(bench)
    return root


def test_a_new_family_is_files_only(checkout, tmp_path, capsys):
    manifest = Manifest(checkout)
    assert lint(manifest) == []
    # the rehearsal's sizes are the configuration's own, not a small GPT-2
    _, config, _ = toy(manifest, CELL)
    assert config["model"]["num_layers"] == 1
    assert config["builder"]["kwargs"]["num_layers"] == 1
    assert "d_mlp" not in config["model"]
    result = rehearse(CELL, root=checkout, seconds=0.3, tmp=str(tmp_path))
    assert result["correct"] is True and result["attempted"] > 0
    run = run_line(capsys)
    assert abs(run["loss_vs_reference"]) < 2e-3
    # one layer, S_eff = 64 unpacked: weights + causal attention 6*L*H*hd*S
    assert run["flops_per_token"] == 6 * (LAYER + HEAD) + 6 * 1 * 32 * 64
    assert run["flops_per_token"] < 0.6 * 6 * run["n_params"]


def test_required_operations_against_hand_counts(checkout):
    want = 6 * (2 * LAYER + HEAD) + 6 * 2 * 32 * 64         # 259,584
    assert flops.moe_train_flops_per_token(TINY, 64) == want
    assert flops.resolve("mixtral:train_flops_per_token")(TINY, 64) == want
    assert flops.resolve("train_flops_per_token") \
        is flops.train_flops_per_token
    # the dense function, as bench.py has it: 6 N + 6 L D S_eff
    assert flops.train_flops_per_token(
        {"n_params": 1000, "num_layers": 3, "d_model": 10}, 7) \
        == 6 * 1000 + 6 * 3 * 10 * 7
    # ISSUE 27's cell: 237,502,464 weights multiply a token, of 1.045 B
    weights = 2 * (4 * 2048 ** 2 + 2048 * 64 + 8 * 3 * 2048 * 1024) \
        + 2048 * 50304
    assert weights == 237502464
    got = flops.moe_train_flops_per_token(OLMOE_2L, 4096)
    assert got == 6 * weights + 6 * 2 * 2048 * 4096
    assert 4.0 < flops.train_flops_per_token(OLMOE_2L, 4096) / got < 4.5
    # the experts alone, 8,192 tokens: forward 6 k D F per token per layer
    per_token_layer = 6 * 8 * 2048 * 1024
    assert flops.grouped_ffn_flops(8192, OLMOE_2L, 4096, ["fwd"]) \
        == 8192 * 2 * per_token_layer
    assert flops.grouped_ffn_flops(8192, OLMOE_2L, 4096,
                                   ["fwd", "fwd", "bwd"]) \
        == 8192 * 2 * per_token_layer * 4
    assert flops.causal_attention_flops(
        512, {"num_layers": 2, "d_model": 256}, 256, ["fwd", "bwd"]) \
        == 0.5 * 12 * 512 * 2 * 256 * 256


def test_missing_kernels_are_named():
    table = {"fusion.1": {"kernel": None}, "custom-call.2": {"kernel": "a"},
             "custom-call.3": {"kernel": "b"}, "copy.4": {}}
    assert train_steps.missing_kernels(table, ["a", "b"]) == []
    assert train_steps.missing_kernels(table, ["b", "c", "d"]) == ["c", "d"]
    assert train_steps.missing_kernels(None, ["a"]) == ["a"]


def test_require_kernels_holds_a_run_to_the_programs_map(
        tmp_path, monkeypatch, capsys):
    """On the CPU no instruction is a kernel: a required name fails
    ``correct`` and is printed; with the program's map saying the kernels
    are there, the same run passes."""
    cell = Manifest().data["workloads"][0]["name"]
    wanted = {"require_kernels": ["ds_flash_fwd", "ds_ggemm"]}
    result = rehearse(cell, seconds=0.3, tmp=str(tmp_path), checks=wanted)
    assert result["correct"] is False
    problems = " | ".join(run_line(capsys)["problems"])
    assert "ds_flash_fwd" in problems and "ds_ggemm" in problems

    from deepspeed_tpu.telemetry import tracing
    monkeypatch.setattr(tracing, "get_program_map", lambda name: {
        "custom-call.1": {"kernel": "ds_flash_fwd"},
        "custom-call.2": {"kernel": "ds_ggemm"},
        "custom-call.3": {"kernel": "ds_flash_bwd_dq"}})
    result = rehearse(cell, seconds=0.3, tmp=str(tmp_path), checks=wanted)
    assert result["correct"] is True
