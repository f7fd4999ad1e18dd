"""check_result_line on hand-made output: what the driver accepts passes,
and each way PR 24's kind of refusal can come about is caught."""
import json

import pytest

from check_result_line import check
from harness.manifest import Manifest

MANIFEST = Manifest()
CELLS = [w["name"] for w in MANIFEST.data["workloads"]]


def line(cell, traced, **changes):
    group = "per_layer" if traced else "end_to_end"
    result = {
        "correct": True, "attempted": 4, "failed": 0,
        "metrics": {m["name"]: {"value": 1.5, "unit": m["unit"]}
                    for m in MANIFEST.metrics(group, cell)},
        "device": {"platform": "tpu", "kind": "TPU v5 lite",
                   "count": MANIFEST.workload(cell)["chips"],
                   "memory_peak_bytes": 10 ** 10}}
    if traced:
        result["device"].update(busy_s=2.0, window_s=2.5)
        result["breakdown"] = {"device_ops": [], "idle_gaps": []}
    result.update(changes)
    return json.dumps(result)


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_full_line_passes(cell, traced):
    out = '{"line": "program"}\n{"line": "run"}\n' + line(cell, traced) + "\n"
    assert check(cell, out, traced) == []


def test_every_listed_metric_and_no_other():
    # the cell that gathers its parameters, by the metric's own list, and
    # one that does not: no place in the manifest's order is any cell's
    gathers = next(m for m in MANIFEST.data["per_layer"]
                   if m["name"] == "comm.gather_gbps_per_chip")["workloads"]
    four_chip = gathers[0]
    one_chip = next(c for c in CELLS if c not in gathers)
    assert MANIFEST.workload(four_chip)["chips"] == 4
    assert MANIFEST.workload(one_chip)["chips"] == 1
    good = json.loads(line(four_chip, True))
    assert "comm.gather_gbps_per_chip" in good["metrics"]
    assert "comm.gather_gbps_per_chip" not in \
        json.loads(line(one_chip, True))["metrics"]
    # a reader that returned None: the metric is left out of the line
    del good["metrics"]["step.optimizer_ms_per_step"]
    bad = check(four_chip, json.dumps(good), True)
    assert bad == ["metric step.optimizer_ms_per_step is listed for the "
                   "cell and not on the line"]
    # the four-chip cell's metrics on a one-chip line
    bad = check(one_chip, line(four_chip, True, device=json.loads(
        line(one_chip, True))["device"]), True)
    assert len(bad) == 4 and all("not listed for the cell" in b for b in bad)
    # a traced line where an untraced one is due
    assert check(one_chip, line(one_chip, True), False)


BROKEN = {
    "nan": lambda s: s.replace("1.5", "NaN", 1),
    "infinity": lambda s: s.replace("1.5", "-Infinity", 1),
    "printed_after": lambda s: s + "\nwrote the trace to /tmp/x",
    "second_object_after": lambda s: s + '\n{"line": "map", "rows": 3}',
    "not_an_object": lambda s: "[" + s + "]",
    "empty": lambda s: "",
    "string_value": lambda s: s.replace("1.5", '"1.5"', 1),
    "wrong_unit": lambda s: s.replace('"unit": "ms"', '"unit": "s"', 1),
    "bare_number": lambda s: s.replace('{"value": 1.5, "unit": "%"}', "1.5",
                                       1),
    "no_device_kind": lambda s: s.replace('"kind": "TPU v5 lite", ', ""),
    "wrong_chip_count": lambda s: s.replace('"count": 1', '"count": 4'),
    "busy_over_window": lambda s: s.replace('"busy_s": 2.0',
                                            '"busy_s": 2.6'),
    "idle_device": lambda s: s.replace('"busy_s": 2.0', '"busy_s": 0.0'),
    "no_attempt": lambda s: s.replace('"attempted": 4', '"attempted": 0'),
    "correct_as_text": lambda s: s.replace('"correct": true',
                                           '"correct": "true"'),
    "no_failed": lambda s: s.replace('"failed": 0, ', ""),
}


@pytest.mark.parametrize("how", sorted(BROKEN))
def test_a_broken_line_is_refused(how):
    cell = CELLS[0]
    good = line(cell, True)
    assert check(cell, good, True) == []
    broken = BROKEN[how](good)
    assert broken != good
    assert check(cell, broken, True), how
