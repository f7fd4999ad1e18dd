"""Granite 4.0-H's toy share (tests/test_granite_hybrid.py: the same sizes,
seeded weights, packed batch and reference) through the engine:
``initialize`` -> ``train_batch`` with the first step's loss against the
plain reference and the loss falling over three steps, the count of rows
over a share's bound summed over every layer's expert sublayer, the scopes
and accounts of a toy step.  A file of its own so that ``--dist loadfile``
gives the family's tests to two workers."""
import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.telemetry import tracing
from tests.test_granite_hybrid import (  # noqa: F401 (the fixture by name)
    B, GAS, LOSS_TOL, S, TOY, one_device, packed_batch, real_kernels,
    reference, share_of, sizes_of, toy, toy_model)
from tests.util import base_config, scope_parts


def _engine(model, **config):
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=base_config(
            train_micro_batch_size_per_gpu=B,
            gradient_accumulation_steps=GAS, seed=3, **config),
        mesh=one_device())
    return engine


def _start_at(engine, params):
    # a copy: the step donates what it is given, and the weights are
    # every test's
    engine.state["params"] = jax.tree.map(
        lambda new, old: jax.device_put(new.astype(old.dtype), old.sharding),
        jax.tree.map(jnp.copy, params), engine.state["params"])


def test_three_engine_steps_from_the_references_loss_downwards():
    model, start, _ = toy("a_share")
    engine = _engine(model, zero_optimization={"stage": 2}, optimizer={
        "type": "AdamW", "params": {"lr": 3e-3}})
    _start_at(engine, start)
    batch = packed_batch()
    want = reference.step_loss(start, batch, sizes_of(model), chunk=1)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(3)]
    assert abs(losses[0] - want) < LOSS_TOL, (losses[0], want)
    assert losses[2] < losses[1] < losses[0] - 0.02, losses
    # a plan of every routed row: nothing is left out of the loss
    assert engine.step_counts() == {"moe/rows_over_bound": 0}
    # ... and the router's load left the step beside it
    load = engine.step_load()["totals"]
    assert load["moe/routed_rows"] > 0 and load["moe/even_rows"] > 0


def test_the_engine_counts_rows_over_the_bound_in_every_layer(monkeypatch):
    """A plan too short for the rows the router sends here: the step's
    account carries the model's count — each micro-batch's, which the
    layer loop summed over the four layers' expert sublayers."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    monkeypatch.setattr(gg, "default_block_m", lambda: 8)
    monkeypatch.setattr(gg, "held_rows_bound", lambda *a, **k: 16)
    model, start, _ = toy("a_share")
    engine = _engine(model)
    _start_at(engine, start)
    batch = packed_batch()
    engine.train_batch(batch=batch)
    over = [int(jax.jit(model.loss_with_counts_fn)(
        start, {k: jnp.asarray(v[g]) for k, v in batch.items()})[1][
            "moe/rows_over_bound"]) for g in range(GAS)]
    assert engine.step_counts()["moe/rows_over_bound"] == sum(over)
    assert min(over) > 0


def test_scopes_and_counts_of_a_toy_step():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        engine = _engine(toy_model(**share_of(3)))
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
        for part in ("/ssm/scan/", "/mlp/experts/", "/attn/"):
            assert any(row["phase"] == phase and part in row["scope"]
                       for row in table.values() if row["scope"]), \
                (phase, part)
    # an instruction of a block is under one of the block's own scopes: a
    # family that writes none reads ``other`` in every step.* metric
    inside = ("/ssm/", "/attn/", "/mlp/")
    for row in table.values():
        if "ds.block" in (row["scope"] or ""):
            assert row["phase"] != "other", row
            assert any(part in row["scope"] for part in inside), row
    assert scope_parts(scopes) >= {"ssm", "scan", "in_proj", "conv",
                                   "gate_norm", "out_proj", "attn", "mlp"}
    rows = tracing.grouped_gemm_rows("train/step")
    T, k = B * S, TOY["top_k"]
    bound = -(-(8 * T * k * 2 // 16) // 128) * 128
    assert rows["held_rows_bound"] == bound >= T * k
    assert (rows["experts_held"], rows["experts_routed"]) == (2, 16)
    assert {c["kernel"] for c in rows["calls"]} == {
        "ds_ggemm_fwd", "ds_ggemm_dx", "ds_ggemm_dw"}
    # two heads of the sixteen over the one group, B and C whole
    assert tracing.ssd_chunks("train/step") == [
        {"chunks": -(-S // 16), "chunk_len": 16, "batch": B, "heads": 2,
         "groups": 1, "head_dim": 8, "state": 16, "path": "xla"}]
