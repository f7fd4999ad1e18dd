"""The reader of the step's own account of one chip's memory
(step_memory): known answers on a hand-made account, nothing to read
without one, the raise on a field the account lacks, the identity the
five metrics keep with the allocator's peak, and a traced rehearsal on
the CPU (three counts; no ``mem.step_workspace_gib`` — the CPU's
``memory_analysis()`` states no peak that covers temporaries, and the
account has no stand-in for it — and no ``mem.unaccounted_gib``: its
allocator says nothing)."""
import pytest

from harness.manifest import Manifest
from layer_metrics.readers import step_memory as reader
from layer_metrics.readers.step_phase import BrokenJoin
from rehearse import rehearse

memory = pytest.importorskip("deepspeed_tpu.telemetry.memory")
METRICS = ["mem.params_gib", "mem.optimizer_gib", "mem.gradients_gib",
           "mem.step_workspace_gib", "mem.unaccounted_gib"]
GIB = 2 ** 30
CELLS = [w["name"] for w in Manifest().data["workloads"]]


def synthetic():
    """Cell 1 as ISSUE 52 predicts it: 760.3 M parameters under the diet,
    a chip whose allocator peaks at 6.12e9 in use + 4.69e9 reserved."""
    state = {"params": 1520600064, "optimizer": 4561800192,
             "state_other": 36}
    program = {"argument": 6082500000, "output": 6082400400,
               "alias": 6082400292, "temp": 5600000000,
               "generated_code": 1000000, "peak": 10772500108}
    batch, gradients, temporaries = 98304, 1520600064, 4690000000
    expected = sum(state.values()) + batch + program["output"] \
        - program["alias"] + temporaries + program["generated_code"]
    return {"state": state, "batch": batch, "program": program,
            "gradients": gradients, "temporaries": temporaries,
            "workspace": temporaries - gradients,
            "expected_peak": expected,
            "layout_padding": program["argument"]
            - sum(state.values()) - batch,
            "allocator": {"peak_bytes_in_use": 6120000000,
                          "peak_bytes_reserved": 4690000000,
                          "bytes_limit": 16909334528},
            "unaccounted": 6120000000 + 4690000000 - expected}


def read(metric, account, monkeypatch):
    monkeypatch.setattr(memory, "step_memory", lambda name: {
        "train/step": account}.get(name))
    return reader.read({}, Manifest().layer_metric(metric)["params"])


def test_known_answers(monkeypatch):
    account = synthetic()
    got = {m: read(m, account, monkeypatch) for m in METRICS}
    assert got["mem.params_gib"] == 1520600064 / GIB
    assert got["mem.optimizer_gib"] == (4561800192 + 36) / GIB
    assert got["mem.gradients_gib"] == 1520600064 / GIB
    assert got["mem.step_workspace_gib"] == (4690000000 - 1520600064) / GIB
    assert got["mem.unaccounted_gib"] == account["unaccounted"] / GIB
    assert all(isinstance(v, float) for v in got.values())


def test_the_five_add_up_to_the_allocators_peak(monkeypatch):
    """What ISSUE 52 holds every traced chip run to: the five metrics are
    the allocator's peak less batch, outputs not aliased and code."""
    account = synthetic()
    program, allocator = account["program"], account["allocator"]
    rest = account["batch"] + program["output"] - program["alias"] \
        + program["generated_code"]
    peak = allocator["peak_bytes_in_use"] + allocator["peak_bytes_reserved"]
    assert sum(read(m, account, monkeypatch) for m in METRICS) \
        == pytest.approx((peak - rest) / GIB, abs=1e-12)


def test_nothing_to_read_without_an_account(monkeypatch):
    params = {"program": "train/step", "field": "gradients"}
    assert read("mem.gradients_gib", None, monkeypatch) is None
    monkeypatch.delattr(memory, "step_memory")     # a commit from before it
    assert reader.read({}, params) is None


def test_a_backend_without_an_allocator_reads_no_remainder(monkeypatch):
    account = dict(synthetic(), allocator=None, unaccounted=None)
    assert read("mem.unaccounted_gib", account, monkeypatch) is None
    assert read("mem.step_workspace_gib", account, monkeypatch) > 0


def test_a_peak_that_covers_no_temporaries_reads_no_workspace(monkeypatch):
    """One definition: where the account cannot state the temporaries
    live at the program's peak it states None — the metric is absent,
    and ``program.temp`` does not stand in for it."""
    account = dict(synthetic(), temporaries=None, workspace=None,
                   expected_peak=None, unaccounted=None)
    assert read("mem.step_workspace_gib", account, monkeypatch) is None
    assert read("mem.unaccounted_gib", account, monkeypatch) is None
    assert read("mem.gradients_gib", account, monkeypatch) > 0


def test_an_account_without_the_field_raises(monkeypatch):
    account = synthetic()
    del account["state"]["state_other"]
    with pytest.raises(BrokenJoin, match="state.state_other"):
        read("mem.optimizer_gib", account, monkeypatch)
    del account["workspace"]
    with pytest.raises(BrokenJoin, match="workspace"):
        read("mem.step_workspace_gib", account, monkeypatch)


def test_the_entries_move_peak_hbm_in_every_cell():
    manifest = Manifest()
    entries = {m["name"]: m for m in manifest.data["per_layer"]}
    assert [m["name"] for m in manifest.data["per_layer"]
            if m["moves"] == "peak_hbm_gib"] == METRICS
    for name in METRICS:
        entry = entries[name]
        assert entry == {"name": name, "unit": "GiB", "better": "lower",
                         "source": "program_counter", "layer": "engine",
                         "moves": "peak_hbm_gib"}      # no workloads: all
        spec = manifest.layer_metric(name)
        assert spec["reader"] == "step_memory"
        assert "telemetry/memory.py step_memory" in spec["reads"]
        assert spec["what"]


@pytest.mark.parametrize("cell", [CELLS[0], CELLS[-1]])
def test_a_traced_rehearsal_reads_three_counts(cell, tmp_path):
    """The program's real account in a cell's rehearsal, one chip and
    four: state and gradients are numbers and are what the account
    says; the CPU's ``memory_analysis()`` states no peak that covers
    temporaries and its allocator reports nothing, so there is no
    workspace and no remainder — and no other quantity in their place."""
    from deepspeed_tpu.telemetry import tracing
    tracing.reset_programs()
    result = rehearse(cell, trace=True, tmp=str(tmp_path))
    account = memory.step_memory("train/step")
    tracing.reset_programs()
    got = result["metrics"]
    assert account["allocator"] is None and account["temporaries"] is None
    assert "mem.unaccounted_gib" not in got
    assert "mem.step_workspace_gib" not in got
    assert all(got[m] > 0 for m in METRICS[:3])
    assert got["mem.params_gib"] + got["mem.optimizer_gib"] \
        == pytest.approx(sum(account["state"].values()) / GIB)
    assert got["mem.gradients_gib"] == account["gradients"] / GIB
    assert account["program"]["temp"] > account["gradients"]
    assert account["layout_padding"] == account["program"]["argument"] \
        - sum(account["state"].values()) - account["batch"]
