"""Xing4.0's toy model (tests/test_xing.py: the same sizes, seeded weights,
packed batch and reference) through the engine: the first step's loss
against the plain reference under ZeRO 0 and 2, one ``train_batch`` after
another lowering the loss, what a step saves under remat, and the scopes
and accounts of a toy step.  A file of its own so that ``--dist loadfile``
gives the family's tests to two workers."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.telemetry import tracing
from tests.test_xing import (  # noqa: F401 (the fixtures come by name)
    B, GAS, LOSS_TOL, S, TOY, _isolation, one_device, packed_batch,
    reference, seeded_toy, sizes_of, toy_model)
from tests.util import base_config


@functools.lru_cache(maxsize=None)
def reference_first_step_loss():
    model, start, _, _ = seeded_toy()
    return reference.step_loss(start, packed_batch(), sizes_of(model),
                               chunk=1)


def _engine(model, **config):
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=base_config(
            train_micro_batch_size_per_gpu=B,
            gradient_accumulation_steps=GAS, seed=3, **config),
        mesh=one_device())
    return engine


@pytest.mark.parametrize("stage", [0, 2])
def test_engine_first_step_loss_matches_the_reference(stage):
    engine = _engine(toy_model(), zero_optimization={"stage": stage})
    # a copy: the step donates what it is given, and the weights are
    # every test's
    start = jax.tree.map(jnp.copy, seeded_toy()[1])
    engine.state["params"] = jax.tree.map(
        lambda new, old: jax.device_put(new.astype(old.dtype), old.sharding),
        start, engine.state["params"])
    want = reference_first_step_loss()
    got = float(engine.train_batch(batch=packed_batch()))
    assert abs(got - want) < LOSS_TOL, (got, want)
    if stage == 2:      # a second step on the state the first one left
        assert np.isfinite(float(engine.train_batch(batch=packed_batch(1))))
    assert engine.step_counts() == {"moe/rows_over_bound": 0}
    # ... and the router's load left the step beside it
    load = engine.step_load()["totals"]
    assert load["moe/routed_rows"] > 0 and load["moe/even_rows"] > 0


def test_train_batch_lowers_the_loss_and_moves_every_stream_leaf():
    """From the model's own start (close to the pre-norm residual), the
    same batch again and again: the loss falls, and the hyper-connections'
    leaves are among what the optimizer moved."""
    engine = _engine(toy_model(), optimizer={
        "type": "AdamW", "params": {"lr": 3e-3}})
    before = jax.tree.map(np.asarray, engine.state["params"])
    batch = packed_batch()
    losses = [float(engine.train_batch(batch=batch)) for _ in range(4)]
    assert losses[-1] < losses[0] - 0.05, losses
    after = engine.state["params"]
    for block in ("dense", "blocks"):
        for sub in ("hc_attn", "hc_mlp"):
            for leaf in ("phi", "alpha", "b_post", "b_res", "b_pre"):
                moved = np.abs(np.asarray(after[block][sub][leaf])
                               - before[block][sub][leaf]).max()
                # the first attention's b_pre and b_res see copies of one
                # row (tests/test_xing.py BLIND): Adam moves them on noise
                assert moved > 0, (block, sub, leaf)


def test_a_block_saves_its_stream_and_nothing_else():
    """Under per-layer remat the residuals of the expert stack's scan are
    the stacked carry — the n-wide stream — and what the loop must keep
    whatever the policy (the layer's parameters, counters): no [B, S, D]
    array (``h`` or ``y``) a layer."""
    model, params, mb, _ = seeded_toy()
    cfg = model.config
    text = jax.jit(jax.grad(model.loss)).lower(params, mb).as_text()
    L, n, D = cfg.expert_layers, cfg.hc_mult, cfg.d_model
    stacked_stream = f"tensor<{L}x{B}x{S}x{n * D}xf32>"
    stacked_hidden = f"tensor<{L}x{B}x{S}x{D}xf32>"
    assert stacked_stream in text
    assert stacked_hidden not in text


def test_scopes_and_accounts_of_a_toy_step(interpret_pallas, monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        engine = _engine(toy_model(attention_impl="flash"))
        engine.train_batch(batch=packed_batch())
        table = tracing.get_program_map("train/step")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    scopes = [row["scope"] or "" for row in table.values()]
    for name in ("ds.embed", "ds.head_loss", "ds.block/attn/hc/coeff",
                 "ds.block/attn/hc/read", "ds.block/attn/hc/write",
                 "ds.block/mlp/hc/coeff", "ds.block/mlp/hc/read",
                 "ds.block/mlp/hc/write", "ds.block/hc/",
                 "ds.block/attn/q_latent", "ds.block/attn/kv_latent",
                 "ds.block/attn/rope", "ds.block/attn/scores",
                 "ds.block/attn/out_proj", "ds.block/mlp/router",
                 "ds.block/mlp/dispatch", "ds.block/mlp/experts",
                 "ds.block/mlp/combine", "ds.block/mlp/shared_expert",
                 "ds.mtp", "ds_ggemm_fwd", "ds_ggemm_dx", "ds_ggemm_dw"):
        assert any(name in s for s in scopes), name
    # the module's block is under ds.mtp, with ds.block's own scopes
    assert any("ds.mtp" in s and "ds.block/attn/hc/write" in s
               for s in scopes)
    assert any("ds.mtp" in s and "ds.block/mlp/experts" in s for s in scopes)
    for phase in ("forward", "recompute", "backward"):
        for part in ("/hc/coeff/", "/hc/read/", "/hc/write/",
                     "/attn/scores/"):
            assert any(row["phase"] == phase and part in row["scope"]
                       for row in table.values() if row["scope"]), (
                phase, part)
    # an instruction of a block is under one of the block's own scopes
    for row in table.values():
        scope = row["scope"] or ""
        if "ds.block" in scope or "ds.mtp" in scope:
            assert row["phase"] != "other", row
        if "ds.block" in scope:
            assert any(part in scope
                       for part in ("/attn/", "/mlp/", "/hc/")), row
    # the stream's metrics' expressions, as the benchmark's files have them
    attn_less_stream = re.compile(r"/attn/(?!hc/)")
    assert any(attn_less_stream.search(s) for s in scopes)
    assert not any(attn_less_stream.search(s) for s in scopes
                   if "/attn/hc/" in s)
    rows = tracing.hc_calls("train/step")
    assert sorted((r["site"], r["calls_per_pass"]) for r in rows) == [
        ("blocks/attn", 2), ("blocks/mlp", 2), ("dense/attn", 1),
        ("dense/mlp", 1), ("mtp/attn", 1), ("mtp/mlp", 1)]
    assert sum(r["calls_per_pass"] for r in rows) == 2 * (
        TOY["num_layers"] + 1)
    assert {(r["tokens"], r["streams"], r["width"]) for r in rows} == {
        (B * S, 4, TOY["d_model"])}
    grouped = tracing.grouped_gemm_rows("train/step")
    assert (grouped["experts_held"], grouped["experts_routed"]) == (4, 16)
    flash = tracing.flash_calls("train/step")
    assert [(c["dk"], c["dv"], c["heads"], c["kv_heads"], c["seq_len"],
             c["packed"]) for c in flash] == [(24, 16, 4, 4, S, True)]
