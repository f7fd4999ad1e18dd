"""MiniCPM-SALA's toy model (tests/test_minicpm_sala.py: the same sizes,
seeded weights, packed batch and reference) through the engine: the first
step's loss against the plain reference, one ``train_batch`` after another
lowering the loss and moving every leaf of both mixers, what a layer saves
under remat, and the scopes and accounts of a toy step — no instruction of
a layer without a scope of the layer's own.  A file of its own so that
``--dist loadfile`` gives the family's tests to two workers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.telemetry import tracing
from tests.test_minicpm_sala import (  # noqa: F401 (fixtures come by name)
    B, GAS, LOSS_TOL, S, _isolation, micro, packed_batch, reference,
    seeded_params, sizes_of, toy_model)
from tests.util import base_config


def one_device():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))


def _engine(model, **config):
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=base_config(
            train_micro_batch_size_per_gpu=B,
            gradient_accumulation_steps=GAS, seed=3, **config),
        mesh=one_device())
    return engine


def test_engine_first_step_loss_matches_the_reference():
    model = toy_model()
    engine = _engine(model, zero_optimization={"stage": 2})
    start = seeded_params(model)
    engine.state["params"] = jax.tree.map(
        lambda new, old: jax.device_put(new.astype(old.dtype), old.sharding),
        jax.tree.map(jnp.copy, start), engine.state["params"])
    want = reference.step_loss(start, packed_batch(), sizes_of(model),
                               chunk=1)
    got = float(engine.train_batch(batch=packed_batch()))
    assert abs(got - want) < LOSS_TOL, (got, want)
    assert np.isfinite(float(engine.train_batch(batch=packed_batch(1))))
    # no count leaves this model's loss: nothing is left out of it
    assert not engine.step_counts()


def test_train_batch_lowers_the_loss_and_moves_every_mixer_leaf():
    engine = _engine(toy_model(), optimizer={
        "type": "AdamW", "params": {"lr": 3e-3}})
    before = jax.tree.map(np.asarray, engine.state["params"])
    batch = packed_batch()
    losses = [float(engine.train_batch(batch=batch)) for _ in range(4)]
    assert losses[-1] < losses[0] - 0.05, losses
    after = engine.state["params"]
    for name in ("00", "01"):           # a sparse layer, a Lightning one
        for leaf, old in before["layers"][name].items():
            moved = np.abs(np.asarray(after["layers"][name][leaf]) - old)
            assert float(moved.max()) > 0, (name, leaf)
    assert "o_norm" in before["layers"]["01"] \
        and "o_norm" not in before["layers"]["00"]


def test_a_layer_saves_its_input_and_its_mixers_output():
    """Under remat a layer keeps two [B, S, D] arrays — its input and what
    its mixer left — and nothing of a mixer's or a feed-forward's inside
    (no [B, S, d_ff] array, no score block)."""
    model = toy_model()
    cfg = model.config
    params, mb = seeded_params(model), micro(packed_batch())
    text = jax.jit(jax.grad(model.loss)).lower(params, mb).as_text()
    assert f"tensor<{B}x{S}x{cfg.d_model}xf32>" in text
    assert f"tensor<{B}x{S}x{cfg.d_ff}xf32>" not in text
    # the feed-forward runs in tiles of mlp_token_tile tokens
    assert f"tensor<{cfg.mlp_token_tile}x{cfg.d_ff}xf32>" in text


@pytest.mark.parametrize("lowering", ["masked_chunks", "mosaic_tiles"])
def test_scopes_and_accounts_of_a_toy_step(lowering, monkeypatch):
    """On the CPU the attend stage is the XLA form; its twin runs the
    stage's kernels (interpret mode, tiles of 32 x 16) through the same
    engine, so that the scopes and the account read as they do on the
    chip."""
    from jax.experimental.compilation_cache import compilation_cache
    from deepspeed_tpu.ops import sparse_attention as sa
    from deepspeed_tpu.ops.pallas import selected_attention as kernels
    if lowering == "mosaic_tiles":
        rule = sa._attend_blocking
        monkeypatch.setattr(sa, "_attend_blocking",
                            lambda interpret, *a: rule(True, *a))
        monkeypatch.setattr(kernels, "TILES", (32, 16))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        engine = _engine(toy_model())
        engine.train_batch(batch=packed_batch())
        table = tracing.get_program_map("train/step")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    scopes = [row["scope"] or "" for row in table.values()]
    parts = {"/sparse_attn/": ("/qkv", "/select", "/attend", "/out_proj"),
             "/lightning/": ("/in_proj", "/rope", "/scan", "/gate_norm",
                             "/out_proj")}
    for name in ["ds.embed", "ds.head_loss", "ds.block/mlp"] + [
            "ds.block" + mixer + part[1:]
            for mixer, inside in parts.items() for part in inside]:
        assert any(name in s for s in scopes), name
    # the selection has no backward; the attend stage and the scan do
    phases = lambda part: {row["phase"] for row in table.values()
                           if part in (row["scope"] or "")}
    assert phases("/sparse_attn/select/") == {"forward", "recompute"}
    for part in ("/sparse_attn/attend/", "/lightning/scan/", "/mlp/"):
        assert {"forward", "recompute", "backward"} <= phases(part), part
    # no instruction of a layer without a scope of the layer's own
    for row in table.values():
        scope = row["scope"] or ""
        if "ds.block" in scope:
            assert row["phase"] != "other", row
            assert any(p in scope for p in (*parts, "/mlp/")), row
        for mixer, inside in parts.items():
            if mixer in scope:
                assert any(p in scope for p in inside), row
    sparse, = tracing.sparse_attention_calls("train/step")
    assert sparse["lowering"] == lowering
    assert (sparse["sparse/topk"], sparse["sparse/block_size"],
            sparse["sparse/dense_len"]) == (4, 4, 32)
    if lowering == "masked_chunks":
        assert sparse["sparse/visited_keys_per_query"] == S * 3 / 4  # 2 spans
        assert (sparse["query_chunk"], sparse["key_spans"]) == (16, 2)
    else:
        # every tile pair with a causal (query, key) in it, the kernels'
        # three calls under the stage's scope in each of their phases
        assert sparse["blocks"] == [32, 16]
        assert sparse["tiles"] == kernels.visited_tiles(S, 32, 16)
        assert sparse["sparse/visited_keys_per_query"] == (S + 32) / 2
        for call, phases_ in (("ds_sel_fwd", {"forward", "recompute"}),
                              ("ds_sel_bwd_dq", {"backward"}),
                              ("ds_sel_bwd_dkv", {"backward"})):
            assert phases("/sparse_attn/attend/" + call) == phases_, call
    # the Lightning calls are the scan's row with one group a head
    scan, = tracing.ssd_chunks("train/step")
    assert (scan["groups"], scan["heads"], scan["path"]) == (4, 4, "xla")
    assert scan["chunks"] == S // 16
    # what depends on the data is the model's diagnostic, not the account
    counts = engine.model.meta["sparse_counts"](
        engine.state["params"], micro(packed_batch()))
    assert set(counts) == {"sparse/selected_blocks_per_query",
                           "sparse/required_keys_per_query",
                           "sparse/dense_documents"}
    assert 0 < float(counts["sparse/required_keys_per_query"]) < S / 2
