"""Kimi-Linear's toy model (tests/test_kimi_linear.py: the same sizes,
seeded weights, packed batch and reference) through the engine: the first
step's loss against the plain reference, one ``train_batch`` after another
lowering the loss and moving every KDA leaf, what a layer saves under
remat, and the scopes and accounts of a toy step — no instruction of a
layer without a scope of the layer's own.  A file of its own so that
``--dist loadfile`` gives the family's tests to two workers."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models.kimi_linear import KDA, MLA
from deepspeed_tpu.telemetry import tracing
from tests.test_kimi_linear import (  # noqa: F401 (the fixtures come by name)
    B, GAS, LOSS_TOL, S, _isolation, micro, one_device, packed_batch,
    reference, seeded_params, sizes_of)
from tests.test_kimi_linear import toy_model as deep_toy_model
from tests.util import base_config

#: the lead and one period, K K M: both mixers, both feed-forwards, half
#: the text of the eight layers tests/test_kimi_linear.py holds to the
#: reference leaf by leaf
toy_model = functools.partial(deep_toy_model, num_layers=4)


@functools.lru_cache(maxsize=None)
def seeded_toy():
    model = toy_model()
    return model, seeded_params(model), micro(packed_batch())


@functools.lru_cache(maxsize=None)
def reference_first_step_loss():
    model, start, _ = seeded_toy()
    return reference.step_loss(start, packed_batch(), sizes_of(model),
                               chunk=1)


def _engine(model, **config):
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=base_config(
            train_micro_batch_size_per_gpu=B,
            gradient_accumulation_steps=GAS, seed=3, **config),
        mesh=one_device())
    return engine


def test_engine_first_step_loss_matches_the_reference():
    engine = _engine(toy_model(), zero_optimization={"stage": 2})
    # a copy: the step donates what it is given, and the weights are
    # every test's
    start = jax.tree.map(jnp.copy, seeded_toy()[1])
    engine.state["params"] = jax.tree.map(
        lambda new, old: jax.device_put(new.astype(old.dtype), old.sharding),
        start, engine.state["params"])
    want = reference_first_step_loss()
    got = float(engine.train_batch(batch=packed_batch()))
    assert abs(got - want) < LOSS_TOL, (got, want)
    # a second step on the state the first one left
    assert np.isfinite(float(engine.train_batch(batch=packed_batch(1))))
    assert engine.step_counts() == {"moe/rows_over_bound": 0}
    # ... and the router's load left the step beside it
    load = engine.step_load()["totals"]
    assert load["moe/routed_rows"] > 0 and load["moe/even_rows"] > 0


def test_train_batch_lowers_the_loss_and_moves_every_mixer_leaf():
    """From the model's own start, the same batch again and again: the
    loss falls, and every leaf of both mixers is among what the optimizer
    moved — the decay's (``A_log``, ``dt_bias``, its low-rank pair), the
    write strength's, the taps, the output gate's pair and norm."""
    engine = _engine(toy_model(), optimizer={
        "type": "AdamW", "params": {"lr": 3e-3}})
    before = jax.tree.map(np.asarray, engine.state["params"])
    batch = packed_batch()
    losses = [float(engine.train_batch(batch=batch)) for _ in range(4)]
    assert losses[-1] < losses[0] - 0.05, losses
    after = engine.state["params"]
    moved = lambda a, b: float(np.abs(np.asarray(a) - b).max())
    for where, kind in ((("lead",), KDA), (("blocks", "run0", KDA), KDA),
                        (("blocks", "run0", MLA), MLA)):
        new, old = after, before
        for key in where:
            new, old = new[key], old[key]
        for leaf in old:
            if leaf != "moe":
                assert moved(new[leaf], old[leaf]) > 0, (where, leaf)
        assert ("w_q" in old) == (kind == MLA) and "w_dq" not in old


def test_a_layer_saves_its_input_and_its_mixers_output():
    """Under per-layer remat the residuals of a run's scan are two [B, S,
    D] arrays a layer — the layer's input and what its mixer left, the two
    halves being rematerialised each on its own — and nothing of a
    mixer's inside (no [B, S, H hd] array a layer)."""
    model, params, mb = seeded_toy()
    cfg = model.config
    text = jax.jit(jax.grad(model.loss)).lower(params, mb).as_text()
    D, wide = cfg.d_model, cfg.kda_num_heads * cfg.kda_head_dim
    assert f"tensor<1x{B}x{S}x{D}xf32>" in text       # a period's, stacked
    for inside in (wide, 3 * wide, cfg.d_ff_dense):
        assert f"tensor<1x{B}x{S}x{inside}xf32>" not in text


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
    for name in ("ds.embed", "ds.head_loss", "ds.block/linear_attn/in_proj",
                 "ds.block/linear_attn/low_rank_gate",
                 "ds.block/linear_attn/conv",
                 "ds.block/linear_attn/delta_rule",
                 "ds.block/linear_attn/gate_norm",
                 "ds.block/linear_attn/out_proj", "ds.block/attn/in_proj",
                 "ds.block/attn/kv_latent", "ds.block/attn/scores",
                 "ds.block/attn/out_proj", "ds.block/mlp/router",
                 "ds.block/mlp/dispatch", "ds.block/mlp/experts",
                 "ds.block/mlp/combine", "ds.block/mlp/shared_expert",
                 "ds.block/ds.lead_mlp/mlp", "ds_ggemm_fwd", "ds_ggemm_dx",
                 "ds_ggemm_dw"):
        assert any(name in s for s in scopes), name
    # nothing turns and no query has a latent
    assert not any("/attn/rope" in s or "/attn/q_latent" in s
                   for s in scopes)
    for phase in ("forward", "recompute", "backward"):
        for part in ("/linear_attn/delta_rule/", "/linear_attn/conv/",
                     "/linear_attn/low_rank_gate/", "/attn/scores/",
                     "/mlp/experts/"):
            assert any(row["phase"] == phase and part in row["scope"]
                       for row in table.values() if row["scope"]), (
                phase, part)
    # the program map leaves no instruction of a layer without a scope of
    # the layer's own, nor without a phase
    for row in table.values():
        scope = row["scope"] or ""
        if "ds.block" in scope:
            assert row["phase"] != "other", row
            assert any(part in scope for part in (
                "/linear_attn/", "/attn/", "/mlp/", "/ds.lead_mlp/")), row
        if "/linear_attn/" in scope:
            assert any(part in scope for part in (
                "/in_proj", "/low_rank_gate", "/conv", "/delta_rule",
                "/gate_norm", "/out_proj")), row
    # three KDA calls of one shape a pass, a decay a key channel each
    assert tracing.delta_rule_chunks("train/step") == [
        {"chunks": S // 16, "chunk_len": 16, "batch": B, "heads": 2,
         "dk": 16, "dv": 16, "decay": "channel", "path": "xla"}]
    convs = tracing.conv_calls("train/step")
    assert [(c["positions"], c["channels"], c["taps"]) for c in convs] == [
        (S, 32, 4)]
    grouped = tracing.grouped_gemm_rows("train/step")
    assert (grouped["experts_held"], grouped["experts_routed"]) == (4, 16)
    flash = tracing.flash_calls("train/step")
    assert [(c["dk"], c["dv"], c["heads"], c["kv_heads"], c["seq_len"],
             c["packed"]) for c in flash] == [(24, 16, 4, 4, S, True)]
