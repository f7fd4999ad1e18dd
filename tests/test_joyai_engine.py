"""JoyAI-LLM-Flash's toy model (tests/test_joyai.py: the same sizes, seeded
weights, packed batch and reference) through the engine, and with each
thing that makes the model itself left out in turn: the first step's loss
against the plain reference under ZeRO 0 and 2, every departure outside
the tolerance and the control inside it, the scopes and accounts of a toy
step.  A file of its own so that ``--dist loadfile`` gives the family's
tests to two workers."""
import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import joyai
from deepspeed_tpu.models.joyai import JoyAIConfig
from deepspeed_tpu.models.model import layer_block
from deepspeed_tpu.moe import layer as moe_layer
from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.telemetry import tracing
from tests.test_joyai import (  # noqa: F401 (the fixtures come by name)
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
    bias = lambda p: np.asarray(
        p["blocks"]["moe"]["e_score_correction_bias"])
    bias_was = bias(start)
    got = float(engine.train_batch(batch=batch))
    assert abs(got - want) < LOSS_TOL, (got, want)
    if stage == 2:      # a second step on the state the first one left
        assert np.isfinite(float(engine.train_batch(batch=packed_batch(1))))
    # the selection bias is a leaf the loss does not train
    assert np.abs(bias_was).max() > 0
    np.testing.assert_array_equal(bias(engine.state["params"]), bias_was)
    assert engine.step_counts() == {"moe/rows_over_bound": 0}
    # ... and the router's load left the step beside it
    load = engine.step_load()["totals"]
    assert load["moe/routed_rows"] > 0 and load["moe/even_rows"] > 0


# ----------------------------------------------- what makes it this model
def _with_moe(monkeypatch, **changes):
    explicit = JoyAIConfig.moe.fget
    monkeypatch.setattr(JoyAIConfig, "moe", property(
        lambda self: replace(explicit(self), **changes)))


def _attention_patched(monkeypatch, change):
    """``change(q, k, v) -> (q, k, v, kwargs)`` just before the product."""
    attend = joyai.causal_attention

    def patched(q, k, v, **kw):
        q, k, v, more = change(q, k, v)
        if "sm_scale" in more:
            q = q * (more["sm_scale"] * q.shape[-1] ** 0.5)
        return attend(q, k, v, **kw)

    monkeypatch.setattr(joyai, "causal_attention", patched)


def _scale_of_the_nope_width(monkeypatch):
    nope = TOY["qk_nope_head_dim"]
    _attention_patched(monkeypatch, lambda q, k, v: (
        q, k, v, {"sm_scale": nope ** -0.5}))


def _rotary_on_the_whole_head(monkeypatch):
    turn = joyai.rope
    nope = TOY["qk_nope_head_dim"]

    def whole(q, k, v):
        # the rotary parts are turned already: turn the rest too
        return (jnp.concatenate([turn(q[..., :nope], TOY["rope_theta"],
                                      interleaved=True), q[..., nope:]], -1),
                jnp.concatenate([turn(k[..., :nope], TOY["rope_theta"],
                                      interleaved=True), k[..., nope:]], -1),
                v, {})
    _attention_patched(monkeypatch, whole)


def _no_rotary(monkeypatch):
    monkeypatch.setattr(joyai, "rope", lambda x, *a, **kw: x)


def _a_rotary_key_per_head(monkeypatch):
    """Each head's copy of the shared key scaled by its own factor: what a
    key per head would be, where the weights give only one."""
    H = TOY["num_heads"]
    nope = TOY["qk_nope_head_dim"]
    factor = (1.0 + 0.5 * jnp.arange(H))[None, None, :, None]
    _attention_patched(monkeypatch, lambda q, k, v: (
        q, jnp.concatenate([k[..., :nope], k[..., nope:] * factor], -1), v,
        {}))


def _norm_dropped(which):
    def patch(monkeypatch):
        norm = joyai._rms_norm

        def keep_others(x, w, eps):
            width = TOY["q_lora_rank" if which == "q" else "kv_lora_rank"]
            return x if x.shape[-1] == width else norm(x, w, eps)
        monkeypatch.setattr(joyai, "_rms_norm", keep_others)
    return patch


def _bias_in_the_weights(monkeypatch):
    route = sharded_moe.topk_routing

    def biased(logits, k, *args, selection_bias=None, scale=1.0, **kw):
        routing = route(logits, k, *args, selection_bias=selection_bias,
                        scale=scale, **kw)
        picked = jnp.take_along_axis(
            jax.nn.sigmoid(logits) + selection_bias, routing.expert_idx, 1)
        return routing._replace(gate_weights=picked / jnp.sum(
            picked, axis=1, keepdims=True) * scale)

    monkeypatch.setattr(moe_layer, "topk_routing", biased)


def _dense_layer_as_an_expert_layer(monkeypatch):
    """The leading block built as the expert blocks are, with the first
    expert layer's own experts behind the leading layer's attention."""
    def hidden(params, batch, config, train=True, rng=None):
        dense = params["dense"]
        lead = {**{k: w for k, w in dense.items()
                   if k not in ("w_gate", "w_up", "w_down")},
                "moe": jax.tree.map(lambda a: a[0], params["blocks"]["moe"])}
        seg = batch.get("segment_ids")
        x = params["wte"].astype(jnp.dtype(config.dtype))[batch["input_ids"]]
        fn = layer_block(joyai._expert_block, config, train=train, rng=rng,
                         segment_ids=seg)
        x, _ = fn(x, lead)
        x, (aux, over) = jax.lax.scan(fn, x, params["blocks"])
        return x, jnp.sum(aux), jnp.sum(over, 0)

    monkeypatch.setattr(joyai, "hidden_with_aux", hidden)


def _module_scored_against_the_next_token(monkeypatch):
    real = joyai.mtp_targets

    def next_token(batch):
        _, scored = real(batch)
        return jnp.roll(batch["input_ids"], -1, axis=1), scored
    monkeypatch.setattr(joyai, "mtp_targets", next_token)


def _module_loss_crossing_documents(monkeypatch):
    real = joyai.mtp_targets

    def crossing(batch):
        return real({"input_ids": batch["input_ids"]})
    monkeypatch.setattr(joyai, "mtp_targets", crossing)


def _module_with_its_own_embedding(monkeypatch):
    """The module reads another table than ``wte`` (the same shape, other
    numbers) for token t+1."""
    real = joyai.mtp_hidden_with_aux

    def own(params, x, batch, config, train=True, rng=None):
        other = jnp.roll(params["wte"], 7, axis=0)
        return real({**params, "wte": other}, x, batch, config, train, rng)
    monkeypatch.setattr(joyai, "mtp_hidden_with_aux", own)


#: name -> (what it does to the MODEL's side: a patch, overrides of the
#: builder).  The reference keeps the equations; the loss then has to
#: leave the tolerance.
DEPARTURES = {
    "scale_of_the_nope_width": (_scale_of_the_nope_width, {}),
    "rotary_on_the_whole_head": (_rotary_on_the_whole_head, {}),
    "no_rotary": (_no_rotary, {}),
    "a_rotary_key_per_head": (_a_rotary_key_per_head, {}),
    "no_q_latent_norm": (_norm_dropped("q"), {}),
    "no_kv_latent_norm": (_norm_dropped("kv"), {}),
    "softmax_for_sigmoid": (
        lambda mp: _with_moe(mp, router="softmax"), {}),
    "bias_added_to_the_weights": (_bias_in_the_weights, {}),
    "no_scaling_factor": (None, dict(routed_scaling_factor=1.0)),
    "no_shared_expert": (
        lambda mp: _with_moe(mp, shared_expert_d_ff=0), {}),
    "dense_layer_as_an_expert_layer": (_dense_layer_as_an_expert_layer, {}),
    "module_off": (None, dict(num_mtp_layers=0)),
    "module_scored_against_the_next_token": (
        _module_scored_against_the_next_token, {}),
    "module_loss_crossing_documents": (_module_loss_crossing_documents, {}),
    "module_with_its_own_embedding": (_module_with_its_own_embedding, {}),
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
    for name in ("ds.embed", "ds.head_loss", "ds.block/attn/q_latent",
                 "ds.block/attn/kv_latent", "ds.block/attn/rope",
                 "ds.block/attn/scores", "ds.block/attn/out_proj",
                 "ds.block/mlp/router", "ds.block/mlp/dispatch",
                 "ds.block/mlp/experts", "ds.block/mlp/combine",
                 "ds.block/mlp/shared_expert", "ds.mtp", "ds_ggemm_fwd",
                 "ds_ggemm_dx", "ds_ggemm_dw"):
        assert any(name in s for s in scopes), name
    # the module's own embedding lookup, head pass and loss are its scope's
    for inner in ("ds.embed", "ds.head_loss"):
        assert any("ds.mtp" in s and inner in s for s in scopes), inner
    # the module's block is under ds.mtp, with ds.block's own scopes
    assert any("ds.mtp" in s and "ds.block/attn/scores" in s for s in scopes)
    assert any("ds.mtp" in s and "ds.block/mlp/experts" in s for s in scopes)
    for phase in ("forward", "recompute", "backward"):
        assert any(row["phase"] == phase and "/attn/scores/" in row["scope"]
                   for row in table.values() if row["scope"]), phase
    # an instruction of a block is under one of the block's own scopes: a
    # family that writes none reads ``other`` in every step.* metric
    for row in table.values():
        if "ds.block" in (row["scope"] or "") or "ds.mtp" in (
                row["scope"] or ""):
            assert row["phase"] != "other", row
        if "ds.block" in (row["scope"] or ""):
            assert any(part in row["scope"]
                       for part in ("/attn/", "/mlp/")), row
    assert scope_parts(scopes) >= {"q_latent", "kv_latent", "rope",
                                   "scores", "out_proj", "ds.mtp"}
    rows = tracing.grouped_gemm_rows("train/step")
    T, k = B * S, TOY["top_k"]
    bound = -(-(2 * T * k * 4 // 16) // 128) * 128
    assert rows["held_rows_bound"] == bound
    assert (rows["experts_held"], rows["experts_routed"]) == (4, 16)
    assert {c["kernel"] for c in rows["calls"]} == {
        "ds_ggemm_fwd", "ds_ggemm_dx", "ds_ggemm_dw"}
    flash = tracing.flash_calls("train/step")
    assert [(c["dk"], c["dv"], c["heads"], c["kv_heads"], c["seq_len"],
             c["packed"]) for c in flash] == [(24, 16, 4, 4, S, True)]
