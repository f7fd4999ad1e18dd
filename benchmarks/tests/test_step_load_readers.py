"""The reader of the router's load (``step_load``) and its four files: known
answers on a hand-made account of ``tracing.step_load`` — a load that
drifts, of which the last ``steps`` entries alone are read — and nothing,
not an error, from a program that has no such fact.  The cases are also
tier-1's (tests/test_step_load.py takes ``CASES`` from this file); the
files wait for an entry (``per_layer`` holds the contract's 128 of 128)."""
import pytest


def account(*steps):
    return {"steps": len(steps), "totals": {}, "last": list(steps)}


DRIFT = account(
    {"moe/routed_rows": 100, "moe/even_rows": 100},
    {"moe/routed_rows": 120, "moe/even_rows": 100},
    {"moe/routed_rows": 150, "moe/even_rows": 100,
     "moe/held_live_rows": 64, "moe/held_plan_rows": 256},
    {"moe/routed_rows": 170, "moe/even_rows": 100,
     "moe/held_live_rows": 128, "moe/held_plan_rows": 256})
RATIO = {"program": "train/step", "numerator": "moe/routed_rows",
         "denominator": "moe/even_rows"}
SHARE = {"program": "train/step", "numerator": "moe/held_live_rows",
         "denominator": "moe/held_plan_rows", "percent": True}
#: (name, the account, the traced steps, the file's params, the value)
CASES = [
    ("drift_last_two", DRIFT, 2, RATIO, 1.6),
    ("drift_all_four", DRIFT, 4, RATIO, 1.35),
    ("percent", DRIFT, 2, SHARE, 37.5),
    # a fact only some of the window's steps have: the program has it
    ("partly_there", DRIFT, 4, SHARE, 37.5),
    ("fewer_steps_than_asked", DRIFT, 9, RATIO, 1.35),
    ("no_such_fact", DRIFT, 2, {**RATIO, "numerator": "moe/nothing"}, None),
    ("no_denominator", DRIFT, 2,
     {**RATIO, "denominator": "moe/fullest_chip_rows"}, None),
    ("empty_account", account(), 4, RATIO, None),
    ("no_program", None, 4, RATIO, None),
]
FILES = {"moe.routed_over_even_rows", "moe.fullest_expert_over_even",
         "moe.live_row_share_pct", "moe.ep_fullest_chip_over_even"}


def check(read, monkeypatch, account, steps, params, want):
    from deepspeed_tpu.telemetry import tracing
    monkeypatch.setattr(tracing, "step_load", lambda program: account)
    got = read({"steps": steps}, params)
    assert got is None if want is None else abs(got - want) < 1e-12


@pytest.mark.parametrize("name, account, steps, params, want", CASES,
                         ids=[case[0] for case in CASES])
def test_the_reader_over_a_hand_made_account(monkeypatch, name, account,
                                             steps, params, want):
    from layer_metrics.readers import step_load
    check(step_load.read, monkeypatch, account, steps, params, want)


def test_the_four_files_wait_unlisted_in_a_clean_manifest():
    from harness.manifest import Manifest, lint
    manifest = Manifest()
    assert len(manifest.data["per_layer"]) == 128      # the contract's most
    assert lint(manifest) == []
    listed = {m["name"] for m in manifest.data["per_layer"]}
    for name in FILES:
        spec = manifest.layer_metric(name)
        assert name not in listed and spec["reader"] == "step_load"
        # no constant of a cell: two facts' names and the program's
        assert set(spec["params"]) <= {"program", "numerator",
                                       "denominator", "percent"}
        assert spec["params"]["program"] == "train/step"
