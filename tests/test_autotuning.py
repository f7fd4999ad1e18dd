"""Autotuner tests (reference: tests/unit/autotuning/test_autotuning.py —
config-space construction + best-selection logic)."""
import json

import numpy as np
import pytest

from deepspeed_tpu.autotuning.autotuner import Autotuner, TrialResult
from tests.util import base_config, child_env, tiny_gpt2


def _factory(**kw):
    return tiny_gpt2(**kw)


def test_autotuner_picks_fastest_feasible(devices8, tmp_path):
    """Grid over stages/micro-batches picks the highest-throughput config
    and writes ranked results + best config (VERDICT round-1 item 9)."""
    tuner = Autotuner(
        base_config(), _factory,
        stages=(0, 2), micro_batches=(1, 2), remat_policies=("nothing",),
        steps=2, warmup_steps=1, seq_len=16,
        results_dir=str(tmp_path / "autotune"))
    best = tuner.tune()
    assert best is not None and best.ok
    rows = json.load(open(tmp_path / "autotune" / "results.json"))
    assert len(rows) == 4
    assert all(r["ok"] for r in rows)
    # the emitted best is the argmax of the *measured* throughputs (which
    # config wins on a loaded CI box is timing noise, not the contract)
    fastest = max(rows, key=lambda r: r["samples_per_sec"])
    assert round(best.samples_per_sec, 2) == fastest["samples_per_sec"]
    assert (best.stage, best.micro_batch) == (fastest["zero_stage"],
                                              fastest["micro_batch"])
    best_cfg = json.load(open(tmp_path / "autotune" / "best_config.json"))
    assert best_cfg["zero_optimization"]["stage"] == best.stage
    assert best_cfg["train_micro_batch_size_per_gpu"] == best.micro_batch
    assert best_cfg["_autotuning"]["samples_per_sec"] > 0


def test_autotuner_marks_failures_infeasible(devices8, tmp_path):
    """A failing candidate (model factory raises) is recorded, not fatal,
    and stops the micro-batch ramp for that (stage, remat) cell."""
    calls = []

    def flaky_factory(**kw):
        calls.append(kw)
        raise MemoryError("simulated OOM")

    tuner = Autotuner(
        base_config(), flaky_factory,
        stages=(0,), micro_batches=(1, 2, 4), remat_policies=("nothing",),
        steps=1, warmup_steps=0, seq_len=16,
        results_dir=str(tmp_path / "autotune"))
    best = tuner.tune()
    assert best is None
    assert len(tuner.results) == 1          # stopped after first failure
    assert not tuner.results[0].ok
    assert "MemoryError" in tuner.results[0].error


def test_subprocess_isolation_survives_hard_crash(devices8, tmp_path,
                                                  monkeypatch):
    """VERDICT r4 item 7 (reference scheduler.py:1 launches every
    experiment as a job): with trial_isolation=subprocess, a candidate
    that HARD-KILLS its process (os._exit — the OOM-killer failure class
    nothing in-process can catch) is recorded infeasible and tuning still
    completes with a best config from the surviving trials."""
    from deepspeed_tpu.autotuning.autotuner import resolve_model_factory
    for name, value in child_env().items():     # the trials inherit it
        monkeypatch.setenv(name, value)
    spec = "tests.autotune_crash:factory"
    tuner = Autotuner(
        base_config(), resolve_model_factory(spec),
        stages=(0,), micro_batches=(1, 2),
        remat_policies=("nothing", "save_attn"),
        steps=1, warmup_steps=1, seq_len=16,
        results_dir=str(tmp_path / "autotune"),
        isolation="subprocess", model_spec=spec, trial_timeout_s=120)
    best = tuner.tune()
    assert best is not None and best.ok and best.remat == "nothing"
    rows = json.load(open(tmp_path / "autotune" / "results.json"))
    crashed = [r for r in rows if r["remat"] == "save_attn"]
    assert crashed and not any(r["ok"] for r in crashed)
    assert any("exit 13" in r["error"] for r in crashed)
    ok_rows = [r for r in rows if r["ok"]]
    assert ok_rows and all(r["remat"] == "nothing" for r in ok_rows)
    assert all(r["samples_per_sec"] > 0 for r in ok_rows)


def test_subprocess_isolation_requires_model_spec():
    with pytest.raises(ValueError, match="model_spec"):
        Autotuner(base_config(), _factory, isolation="subprocess")


def test_best_ranks_by_throughput():
    t = Autotuner({}, None)
    t.results = [
        TrialResult({}, 1, 0, "nothing", True, samples_per_sec=10),
        TrialResult({}, 2, 2, "nothing", True, samples_per_sec=30),
        TrialResult({}, 4, 3, "nothing", False),
    ]
    assert t.best().samples_per_sec == 30


# ------------------------------------------------- generality + cost model

def test_resolve_model_factory_registry_and_entry_point():
    from deepspeed_tpu.autotuning.autotuner import resolve_model_factory
    f = resolve_model_factory("llama:tiny",
                              {"attention_impl": "xla", "dtype": "float32"})
    m = f(remat=False, remat_policy="nothing")
    assert m.meta["name"] == "llama-tiny"
    # entry point form: any importable pkg.module:fn works
    f2 = resolve_model_factory(
        "deepspeed_tpu.models.llama:llama_model",
        {"size": "tiny", "attention_impl": "xla"})
    m2 = f2(remat=False, remat_policy="nothing")
    assert m2.meta["name"] == "llama-tiny"


def test_cost_model_prunes_and_orders():
    from deepspeed_tpu.autotuning.tuner import (Candidate, CostModel,
                                                order_candidates)
    cm = CostModel(n_params=1e9, d_model=2048, num_layers=24, seq_len=1024,
                   dp_world=1, hbm_bytes=16 << 30)
    cands = [Candidate(s, mb, "dots") for s in (0, 3) for mb in (1, 256)]
    to_run, pruned = order_candidates(cands, "model_based", cm)
    # stage-0 fp32 state alone is 16 GB at 1B params: pruned without compile
    assert any(c.stage == 0 for c in pruned)
    assert all(c.stage == 3 or c.micro_batch <= 1 for c in to_run)
    # gridsearch never prunes
    all_run, none = order_candidates(cands, "gridsearch", cm)
    assert len(all_run) == 4 and not none


def test_autotune_llama_end_to_end_cli(devices8, tmp_path):
    """round-2 VERDICT item 9 done-criterion: tune a llama config from the
    CLI entry (run_autotuning), model-based tuner with early stopping."""
    import types
    from deepspeed_tpu.autotuning.autotuner import run_autotuning
    cfg = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "steps_per_print": 0,
        "autotuning": {
            "model": "llama:tiny",
            "model_kwargs": {"attention_impl": "xla", "dtype": "float32"},
            "stages": [0, 2], "micro_batches": [1, 2],
            "remat_policies": ["nothing"], "steps": 1, "seq_len": 16,
            "tuner_type": "model_based", "tuner_early_stopping": 3,
            "results_dir": str(tmp_path / "at")},
    }
    cfg_path = tmp_path / "ds_config.json"
    cfg_path.write_text(json.dumps(cfg))
    args = types.SimpleNamespace(
        user_args=["train.py", "--deepspeed_config", str(cfg_path)])
    assert run_autotuning(args) == 0
    best = json.load(open(tmp_path / "at" / "best_config.json"))
    assert best["zero_optimization"]["stage"] in (0, 2)
    rows = json.load(open(tmp_path / "at" / "results.json"))
    assert any(r["ok"] for r in rows)
