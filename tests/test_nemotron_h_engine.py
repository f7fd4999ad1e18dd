"""Nemotron-H's toy model (tests/test_nemotron_h.py: the same sizes, seeded
weights, packed batch and reference) through the engine: the first step's
loss against the plain reference under ZeRO 0 and 2, the count of rows
over a share's bound in the engine's account, the scopes and accounts of a
toy step.  A file of its own so that ``--dist loadfile`` gives the
family's tests to two workers."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.telemetry import tracing
from tests.test_nemotron_h import (  # noqa: F401 (the fixture comes by name)
    B, GAS, LOSS_TOL, S, TOY, one_device, packed_batch, real_kernels,
    reference, sizes_of, toy, toy_model)
from tests.util import base_config, scope_parts


@functools.lru_cache(maxsize=None)
def reference_first_step_loss():
    """What both stages' first steps are held to: the same weights and
    batch, so the reference runs once."""
    model, start, _, _ = toy()
    return reference.step_loss(start, packed_batch(), sizes_of(model),
                               chunk=1)


@pytest.mark.parametrize("stage", [0, 2])
def test_engine_first_step_loss_matches_the_reference(stage):
    model = toy_model()
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=base_config(
            train_micro_batch_size_per_gpu=B,
            gradient_accumulation_steps=GAS, seed=3,
            zero_optimization={"stage": stage}), mesh=one_device())
    # a copy: the step donates what it is given, and the weights are
    # every test's
    start = jax.tree.map(jnp.copy, toy()[1])
    engine.state["params"] = jax.tree.map(
        lambda new, old: jax.device_put(new.astype(old.dtype), old.sharding),
        start, engine.state["params"])
    batch = packed_batch()
    want = reference_first_step_loss()
    bias = lambda p: np.asarray(
        p["blocks"]["experts"]["moe"]["e_score_correction_bias"])
    bias_was = bias(start)
    got = float(engine.train_batch(batch=batch))
    assert abs(got - want) < LOSS_TOL, (got, want)
    assert np.isfinite(float(engine.train_batch(batch=packed_batch(1))))
    # the selection bias is a leaf the loss does not train: a gradient of
    # exactly zero leaves it where it was
    assert np.abs(bias_was).max() > 0
    np.testing.assert_array_equal(bias(engine.state["params"]), bias_was)


def test_the_engine_counts_a_row_over_the_bound(monkeypatch):
    """The engine's half of ``test_a_row_over_the_bound_is_counted``: a
    plan too short for the rows the router sends here, and the step's
    account carries the count."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    monkeypatch.setattr(gg, "default_block_m", lambda: 8)
    monkeypatch.setattr(gg, "held_rows_bound", lambda *a, **k: 16)
    engine, *_ = deepspeed_tpu.initialize(
        model=toy_model(), config=base_config(
            train_micro_batch_size_per_gpu=B,
            gradient_accumulation_steps=GAS, seed=3), mesh=one_device())
    engine.train_batch(batch=packed_batch())
    assert engine.step_counts()["moe/rows_over_bound"] > 0
    # ... and the router's load left the step beside it
    load = engine.step_load()["totals"]
    assert load["moe/routed_rows"] > 0 and load["moe/even_rows"] > 0


def test_scopes_and_counts_of_a_toy_step():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        engine, *_ = deepspeed_tpu.initialize(
            model=toy_model(), config=base_config(
                train_micro_batch_size_per_gpu=B,
                gradient_accumulation_steps=GAS), mesh=one_device())
        engine.train_batch(batch=packed_batch())
        table = tracing.get_program_map("train/step")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    scopes = [row["scope"] or "" for row in table.values()]
    for name in ("ds.embed", "ds.head_loss", "ds.block/attn",
                 "ds.block/ssm/in_proj", "ds.block/ssm/conv",
                 "ds.block/ssm/scan", "ds.block/ssm/gate_norm",
                 "ds.block/ssm/out_proj", "ds.block/mlp/router",
                 "ds.block/mlp/dispatch", "ds.block/mlp/experts",
                 "ds.block/mlp/combine", "ds.block/mlp/shared_expert",
                 "ds_ggemm_fwd", "ds_ggemm_dx", "ds_ggemm_dw"):
        assert any(name in s for s in scopes), name
    for phase in ("forward", "recompute", "backward"):
        assert any(row["phase"] == phase and "/ssm/scan/" in row["scope"]
                   for row in table.values() if row["scope"]), phase
    # an instruction of a block is under one of the block's own scopes: a
    # family that writes none reads ``other`` in every step.* metric
    inside = ("/ssm/", "/attn/", "/mlp/")
    for row in table.values():
        if "ds.block" in (row["scope"] or ""):
            assert row["phase"] != "other", row
            assert any(part in row["scope"] for part in inside), row
    assert scope_parts(scopes) >= {"ssm", "scan", "in_proj", "conv",
                                   "gate_norm", "out_proj"}
    rows = tracing.grouped_gemm_rows("train/step")
    T, k = B * S, TOY["top_k"]
    bound = -(-(2 * T * k * 4 // 16) // 128) * 128
    assert rows["held_rows_bound"] == bound
    assert rows["padded_rows_per_call"] == bound + 4 * 128
    assert (rows["experts_held"], rows["experts_routed"]) == (4, 16)
    assert {c["kernel"] for c in rows["calls"]} == {
        "ds_ggemm_fwd", "ds_ggemm_dx", "ds_ggemm_dw"}
    assert tracing.ssd_chunks("train/step") == [
        {"chunks": -(-S // 16), "chunk_len": 16, "batch": B, "heads": 8,
         "groups": 2, "head_dim": 8, "state": 16, "path": "xla"}]
    assert tracing.delta_rule_chunks("train/step") is None
