"""Laguna's toy model (tests/test_laguna.py: the same sizes, seeded weights,
packed batch and reference) through the engine, and with a fault planted
in each thing that makes the model itself: the first step's loss against
the plain reference under ZeRO 0 and 2, every fault outside the tolerance
and the control inside it, the scopes and accounts of a toy step.  A file
of its own so that ``--dist loadfile`` gives the family's tests to two
workers."""
import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import laguna
from deepspeed_tpu.models.laguna import FULL, SLIDING, LagunaConfig
from deepspeed_tpu.telemetry import tracing
from tests.test_laguna import (  # noqa: F401 (the fixtures come by name)
    B, GAS, LOSS_TOL, S, TOY, _isolation, one_device, packed_batch,
    real_kernels, reference, seeded_toy, sizes_of, toy_model)
from tests.util import base_config, scope_parts


@functools.lru_cache(maxsize=None)
def reference_first_step_loss():
    """What both stages' first steps are held to: the same weights and
    batch, so the reference runs once."""
    model, start, _, _ = seeded_toy()
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
    start = jax.tree.map(jnp.copy, seeded_toy()[1])
    engine.state["params"] = jax.tree.map(
        lambda new, old: jax.device_put(new.astype(old.dtype), old.sharding),
        start, engine.state["params"])
    batch = packed_batch()
    want = reference_first_step_loss()
    got = float(engine.train_batch(batch=batch))
    assert abs(got - want) < LOSS_TOL, (got, want)
    if stage == 2:      # a second step on the state the first one left
        assert np.isfinite(float(engine.train_batch(batch=packed_batch(1))))
    assert engine.step_counts() == {"moe/rows_over_bound": 0}
    # ... and the router's load left the step beside it
    load = engine.step_load()["totals"]
    assert load["moe/routed_rows"] > 0 and load["moe/even_rows"] > 0


# ----------------------------------------------- what makes it this model
def _attention_given(monkeypatch, change):
    """``causal_attention`` as the model calls it, its arguments changed."""
    real = laguna.causal_attention

    def patched(q, k, v, **kw):
        return real(*change(q, k, v, kw), **kw)

    monkeypatch.setattr(laguna, "causal_attention", patched)


def _a_window_on_the_full_layers(monkeypatch):
    def change(q, k, v, kw):
        kw["window"] = TOY["sliding_window"]
        return q, k, v
    _attention_given(monkeypatch, change)


def _no_window(monkeypatch):
    def change(q, k, v, kw):
        kw["window"] = None
        return q, k, v
    _attention_given(monkeypatch, change)


def _groups_interleaved(monkeypatch):
    """Query head n reads KV head n % KV, not n // (H / KV)."""
    def change(q, k, v, kw):
        rep = q.shape[2] // k.shape[2]
        return q, jnp.tile(k, (1, 1, rep, 1)), jnp.tile(v, (1, 1, rep, 1))
    _attention_given(monkeypatch, change)


def _groups_of(size):
    """Both kinds' groups read as ``size`` query heads to a KV head (the
    sizes the kernels had before: 8, 16), the last KV head taking what is
    left over."""
    def plant(monkeypatch):
        def change(q, k, v, kw):
            of = np.minimum(np.arange(q.shape[2]) // size, k.shape[2] - 1)
            return q, k[:, :, of], v[:, :, of]
        _attention_given(monkeypatch, change)
    return plant


def _rotary_tables_swapped(monkeypatch):
    real = laguna.rotary_table
    monkeypatch.setattr(
        laguna, "rotary_table",
        lambda config, kind: real(config, SLIDING if kind == FULL else FULL))


def _gate_left_out(monkeypatch):
    monkeypatch.setattr(laguna, "_gate_heads", lambda attn, gate: attn)


def _gate_per_element(monkeypatch):
    """The H gate values laid along the head's elements instead of one a
    head: element e of the concatenated heads takes gate e % H."""
    def per_element(attn, gate):
        B, S, H, hd = attn.shape
        flat = attn.reshape(B, S, H * hd) * jnp.tile(gate, (1, 1, hd))
        return flat.reshape(attn.shape)
    monkeypatch.setattr(laguna, "_gate_heads", per_element)


def _gate_reads_the_residual_stream(monkeypatch):
    """g = sigmoid(x W_g) on the un-normed input."""
    real = laguna.qdot

    def qdot(h, w):
        if w.shape[-1] in (TOY["num_heads_full"], TOY["num_heads_sliding"]):
            return real(h * 1.7, w)
        return real(h, w)

    monkeypatch.setattr(laguna, "qdot", qdot)


def _with_moe(monkeypatch, **changes):
    real = LagunaConfig.moe.fget
    monkeypatch.setattr(LagunaConfig, "moe", property(
        lambda self: replace(real(self), **changes)))


FAULTS = {
    "window_one_short": (None, dict(sliding_window=7)),
    "window_one_long": (None, dict(sliding_window=9)),
    "no_window": (_no_window, {}),
    "a_window_on_the_full_layers": (_a_window_on_the_full_layers, {}),
    "rotary_tables_swapped": (_rotary_tables_swapped, {}),
    "yarn_factor_left_out": (None, dict(rope_factor=1.0)),
    "attention_factor_left_out": (None, dict(attention_factor=1.0)),
    "rotary_on_the_whole_full_head": (
        None, dict(partial_rotary_factor=1.0)),
    "one_theta_for_both_kinds": (None, dict(sliding_rope_theta=500000.0)),
    "gate_left_out": (_gate_left_out, {}),
    "gate_per_element": (_gate_per_element, {}),
    "gate_reads_another_input": (_gate_reads_the_residual_stream, {}),
    "groups_interleaved": (_groups_interleaved, {}),
    "groups_of_8": (_groups_of(8), {}),
    "groups_of_16": (_groups_of(16), {}),
    "top_k_not_renormalised": (None, dict(norm_topk_prob=False)),
    "no_scaling_factor": (None, dict(routed_scaling_factor=1.0)),
    "no_shared_expert": (
        lambda mp: _with_moe(mp, shared_expert_d_ff=0), {}),
}


def test_scopes_and_accounts_of_a_toy_step(interpret_pallas, real_kernels):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        engine, *_ = deepspeed_tpu.initialize(
            model=toy_model(attention_impl="flash"), config=base_config(
                train_micro_batch_size_per_gpu=B,
                gradient_accumulation_steps=GAS), mesh=one_device())
        engine.train_batch(batch=packed_batch())
        table = tracing.get_program_map("train/step")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    scopes = [row["scope"] or "" for row in table.values()]
    for kind in ("ds.attn_full", "ds.attn_sliding"):
        for part in ("attn/rope", "attn/scores", "attn/ds.head_gate",
                     "attn/out_proj"):
            name = f"ds.block/{kind}/{part}"
            assert any(name in s for s in scopes), name
    for name in ("ds.embed", "ds.head_loss", "ds.block/ds.lead_mlp/mlp",
                 "ds.block/mlp/router", "ds.block/mlp/dispatch",
                 "ds.block/mlp/experts", "ds.block/mlp/combine",
                 "ds.block/mlp/shared_expert", "ds_ggemm_fwd",
                 "ds_ggemm_dx", "ds_ggemm_dw"):
        assert any(name in s for s in scopes), name
    # a sliding layer's calls are the windowed kernels, a full layer's the
    # causal ones: a metric can tell them apart by name alone
    # (in the interpreter a kernel's name is a scope of its body's ops)
    import re
    kernels = {}
    for scope in scopes:
        for name in re.findall(r"ds_flash_[a-z_]+", scope):
            kernels.setdefault(name, set()).add("ds.attn_sliding" in scope)
    assert kernels == {
        "ds_flash_fwd": {False}, "ds_flash_bwd_dkv": {False},
        "ds_flash_bwd_dq": {False}, "ds_flash_win_fwd": {True},
        "ds_flash_win_bwd_dkv": {True}, "ds_flash_win_bwd_dq": {True}}
    for phase in ("forward", "recompute", "backward"):
        for kind in ("ds.attn_full", "ds.attn_sliding"):
            assert any(row["phase"] == phase
                       and f"{kind}/attn/scores/" in row["scope"]
                       for row in table.values() if row["scope"]), phase
    for row in table.values():
        if "ds.block" in (row["scope"] or ""):
            assert row["phase"] != "other", row
            assert any(part in row["scope"]
                       for part in ("/attn/", "/mlp/")), row
    assert scope_parts(scopes) >= {"ds.attn_full", "ds.attn_sliding",
                                   "ds.head_gate", "ds.lead_mlp"}
    rows = tracing.grouped_gemm_rows("train/step")
    assert (rows["experts_held"], rows["experts_routed"]) == (2, 8)
    flash = sorted(tracing.flash_calls("train/step"),
                   key=lambda c: "window" in c)
    assert [(c["heads"], c["kv_heads"], c["dk"], c["seq_len"], c["packed"],
             c.get("window"), c.get("k_tiles_per_q_block")) for c in flash] \
        == [(12, 2, 16, S, True, None, None), (18, 2, 16, S, True, 8, 2)]
