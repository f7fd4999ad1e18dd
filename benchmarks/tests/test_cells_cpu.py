"""Every cell of BENCHMARK.json end to end at toy size on the CPU, through
the driver's own functions (the four-chip cell on four virtual devices),
and the command itself refusing anything but a TPU."""
import os
import subprocess
import sys

import pytest

from harness.manifest import Manifest, ROOT
from rehearse import rehearse

CELLS = [w["name"] for w in Manifest().data["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(cell, tmp_path):
    manifest = Manifest()
    result = rehearse(cell, seconds=0.5, tmp=str(tmp_path))
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["count"] == manifest.workload(cell)["chips"]
    wanted = {m["name"] for m in manifest.metrics("end_to_end", cell)}
    assert wanted <= set(result["metrics"])


@pytest.mark.parametrize("cell", CELLS)
def test_traced_cell_rehearses(cell, tmp_path):
    """A CPU trace has no TPU plane: the device readers find nothing and
    their metrics are left out; the host-span readers still report."""
    result = rehearse(cell, trace=True, tmp=str(tmp_path))
    assert result["correct"] is True
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert result["metrics"], "no per-layer metric at all"
    assert all(isinstance(v, float) for v in result["metrics"].values())


def test_command_refuses_a_cpu():
    cell = CELLS[0]
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "0", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert run.returncode != 0
    assert run.stdout.strip() == ""
    assert "needs a TPU" in run.stderr
