"""BENCHMARK.json against the contract's limits, and the requirement that
a later PR adds a cell with files and one entry, and no edit."""
import copy
import json
import os
import shutil

from harness.manifest import Manifest, ROOT, lint
from rehearse import rehearse


def test_manifest_is_within_the_contract():
    assert lint(Manifest()) == []


def test_lint_catches_what_the_driver_refuses():
    manifest = Manifest()
    manifest.data = copy.deepcopy(manifest.data)
    manifest.data["workloads"][0]["name"] = "has space"
    manifest.data["end_to_end"][0]["unit"] = "tokens per second"
    manifest.data["workloads"][1]["chips"] = 4        # 2 of 3 on four chips
    complaints = " | ".join(lint(manifest))
    assert "bad name" in complaints and "bad unit" in complaints
    assert "over 25%" in complaints


def test_a_new_cell_is_files_only(tmp_path):
    """Copy one configuration and one traffic file to new names, give the
    pair a check of its own, add the entries to BENCHMARK.json, and the
    harness resolves and rehearses the new cell: no code under benchmarks/
    knows a name."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    data = copy.deepcopy(Manifest().data)
    old = data["workloads"][0]
    old_config = next(c for c in data["configs"]
                      if c["name"] == old["config"])
    bench = os.path.join(root, "benchmarks")
    shutil.copy(os.path.join(root, old_config["file"]),
                os.path.join(bench, "configs", "another-model.json"))
    shutil.copy(os.path.join(bench, "traffic", old["traffic"] + ".json"),
                os.path.join(bench, "traffic", "another-mix.json"))
    data["configs"].append({**old_config, "name": "another-model",
                            "file": "benchmarks/configs/another-model.json"})
    data["workloads"].append({**old, "name": "another-model.another-mix",
                              "config": "another-model",
                              "traffic": "another-mix"})
    # the new pair's own checks: a file named after the workload
    os.makedirs(os.path.join(bench, "cells"), exist_ok=True)
    with open(os.path.join(bench, "cells",
                           "another-model.another-mix.json"), "w") as f:
        json.dump({"warmup_steps": 2}, f)
    # 4 cells now; keep the 25% rule by counting, not by editing names
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    manifest = Manifest(root)
    assert lint(manifest) == []
    checks = manifest.cell("another-model.another-mix")[1]["checks"]
    assert checks["warmup_steps"] == 2
    assert checks["learn_check"] == \
        manifest.cell(old["name"])[1]["checks"]["learn_check"]
    result = rehearse("another-model.another-mix", root=root, seconds=0.3,
                      tmp=str(tmp_path))
    assert result["correct"] is True and result["attempted"] > 0


def test_no_name_of_the_manifest_appears_in_code():
    data = Manifest().data
    names = [x["name"] for group in ("configs", "workloads", "per_layer")
             for x in data[group]] + [w["traffic"] for w in data["workloads"]]
    bench = os.path.join(ROOT, "benchmarks")
    for folder, _, files in os.walk(bench):
        if os.path.basename(folder) in ("tests", "__pycache__"):
            continue
        for name in files:
            if name.endswith(".py"):
                text = open(os.path.join(folder, name)).read()
                for n in names:
                    assert n not in text, f"{name} names {n}"
