"""``benchmarks/tests`` name cells by their place in ``BENCHMARK.json``:
``CELLS[0]`` for a one-chip cell and ``CELLS[-1]`` for the four-chip one.
A new cell goes at the end of ``workloads`` (the driver reads one put
anywhere else as a change to the cells that were there), so inside a test
``CELLS`` keeps the file's order with the four-chip cells moved last.
Until those tests pick their cell by ``chips`` this keeps them true."""
import pytest


@pytest.fixture(autouse=True)
def _four_chip_cells_last(request, monkeypatch):
    cells = getattr(request.module, "CELLS", None)
    if cells:
        from harness.manifest import Manifest
        chips = {w["name"]: w["chips"] for w in Manifest().data["workloads"]}
        monkeypatch.setattr(request.module, "CELLS",
                            sorted(cells, key=chips.__getitem__))
