"""BENCHMARK.json against the contract's limits, and the requirement that
a later PR adds a cell with files and one entry, and no edit."""
import copy
import json
import os
import shutil

import pytest

from harness.manifest import Manifest, ROOT, lint
from rehearse import rehearse


def test_manifest_is_within_the_contract():
    assert lint(Manifest()) == []


def test_lint_catches_what_the_driver_refuses():
    manifest = Manifest()
    manifest.data = copy.deepcopy(manifest.data)
    manifest.data["workloads"][0]["name"] = "has space"
    manifest.data["end_to_end"][0]["unit"] = "tokens per second"
    # one four-chip cell more than the quarter of the cells there are
    cells = manifest.data["workloads"]
    one_chip = [w for w in cells if w["chips"] == 1]
    four_chip = len(cells) - len(one_chip)
    for w in one_chip[:max(1, len(cells) // 4) + 1 - four_chip]:
        w["chips"] = 4
    complaints = " | ".join(lint(manifest))
    assert "bad name" in complaints and "bad unit" in complaints
    assert "over 25%" in complaints


FLASH = "attention.flash_fwd_roofline"


def edited_tree(tmp_path, edit):
    """A checkout in a temporary tree: the benchmark's files as they are (a
    link) under a BENCHMARK.json whose per-layer entries ``edit`` has
    changed, handed to it by name."""
    data = copy.deepcopy(Manifest().data)
    edit({m["name"]: m for m in data["per_layer"]})
    os.symlink(os.path.join(ROOT, "benchmarks"),
               str(tmp_path / "benchmarks"))
    with open(str(tmp_path / "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    return Manifest(str(tmp_path))


def test_a_roofline_without_a_list_is_refused(tmp_path):
    shares = [m for m in Manifest().data["per_layer"]
              if "roofline" in m["name"] or "mfu" in m["name"]]
    assert FLASH in [m["name"] for m in shares]
    assert all(m.get("workloads") for m in shares)
    complaints = lint(edited_tree(
        tmp_path, lambda entries: entries[FLASH].pop("workloads")))
    assert any(c.startswith(FLASH + ": no workloads list: a share of a peak "
                            "is counted for named cells")
               for c in complaints)


@pytest.mark.parametrize("cell", [w["name"]
                                  for w in Manifest().data["workloads"]])
def test_a_bare_count_is_listed_only_where_the_step_has_none_of_its_own(
        cell, tmp_path):
    """``causal_attention_flops`` counts every layer at d_model: a cell
    whose configuration brought a ``required_ops`` file for its mfu_pct
    cannot list a roofline counted by it; the others can."""
    manifest = Manifest()
    own = manifest.cell(cell)[1].get("flops", {}).get("train", "")
    listed = cell in next(m for m in manifest.data["per_layer"]
                          if m["name"] == FLASH)["workloads"]
    assert listed == (":" not in own)
    complaints = lint(edited_tree(
        tmp_path, lambda entries: entries[FLASH].update(workloads=[cell])))
    if listed:
        assert complaints == []
    else:
        assert len(complaints) == 1 and complaints[0].startswith(
            f"{FLASH}: causal_attention_flops of harness/flops.py is "
            f"listed for {cell}, whose configuration counts its step with "
            f"{own}")
        assert "needs it for the kernel too" in complaints[0]


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
