"""Phi-4-mini-flash's toy model (tests/test_phi4flash.py: the same sizes,
seeded weights, packed batch and reference) through the engine: the first
step's loss against the plain reference under ZeRO 0 and 2, and the scopes
and accounts of a toy step — one selective-scan row a Mamba layer, ``kv_of``
on the cross layer's flash calls.  A file of its own so that ``--dist
loadfile`` gives the family's tests to three workers."""
import re
from dataclasses import asdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.ops import attention
from deepspeed_tpu.telemetry import tracing
from tests.test_phi4flash import (B, LOSS_TOL, S, micro, reference,
                                  seeded_toy, toy_model)
from tests.util import base_config


def one_device():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))


def batch_of(mb):
    return {k: np.asarray(v)[None] for k, v in mb.items()}   # gas 1


@pytest.mark.parametrize("stage", [0, 2])
def test_engine_first_step_loss_matches_the_reference(stage):
    model, start, mb, _ = seeded_toy()
    engine, *_ = deepspeed_tpu.initialize(
        model=toy_model(), config=base_config(
            train_micro_batch_size_per_gpu=B, gradient_accumulation_steps=1,
            seed=3, zero_optimization={"stage": stage}), mesh=one_device())
    # a copy: the step donates what it is given, and the weights are
    # every test's
    engine.state["params"] = jax.tree.map(
        lambda new, old: jax.device_put(
            jnp.copy(new).astype(old.dtype), old.sharding),
        start, engine.state["params"])
    want = reference.step_loss(start, batch_of(mb), asdict(model.config),
                               chunk=1)
    got = float(engine.train_batch(batch=batch_of(mb)))
    assert abs(got - want) < LOSS_TOL, (got, want)
    assert np.isfinite(float(engine.train_batch(batch=batch_of(micro(4)))))
    # no counts of rows left out: the model has no loss_with_counts_fn
    assert not engine.step_counts()


def test_scopes_and_accounts_of_a_toy_step(interpret_pallas, monkeypatch):
    """The kernels' lowerings (interpret mode), so that the accounts read
    as they do on the chip: S 256 for ``auto`` to take the flash calls."""
    from jax.experimental.compilation_cache import compilation_cache
    from deepspeed_tpu.ops.pallas import vmem
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention, "_FLASH_STATUS", {})
    lowering = vmem.lowering
    monkeypatch.setattr(vmem, "lowering", lambda interpret, *a: lowering(
        True if interpret is None else interpret, *a))
    long = 256
    model = toy_model(d_model=64, mamba_expand=2, scan_chunk=128,
                      max_seq_len=long)
    mb = {"input_ids": np.asarray(jax.random.randint(
              jax.random.PRNGKey(0), (1, B, long), 0, 256)),
          "segment_ids": np.broadcast_to(
              np.repeat([0, 1, 2], [100, 60, 96]), (1, B, long)).astype(
                  np.int32)}
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        engine, *_ = deepspeed_tpu.initialize(
            model=model, config=base_config(
                train_micro_batch_size_per_gpu=B,
                gradient_accumulation_steps=1), mesh=one_device())
        engine.train_batch(batch=mb)
        table = tracing.get_program_map("train/step")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    scopes = [row["scope"] or "" for row in table.values()]
    for name in ("ds.embed", "ds.head_loss", "ds.block/mlp",
                 "ds.block/mamba/in_proj", "ds.block/mamba/conv",
                 "ds.block/mamba/scan", "ds.block/mamba/gate",
                 "ds.block/mamba/out_proj", "ds.block/gmu",
                 "ds.block/diff_attn/qkv", "ds.block/diff_attn/flash",
                 "ds.block/diff_attn/combine", "ds.block/diff_attn/out_proj"):
        assert any(name in s for s in scopes), name
    for phase in ("forward", "recompute", "backward"):
        for part in ("/mamba/scan/", "/diff_attn/flash/", "/gmu/"):
            assert any(row["phase"] == phase and part in row["scope"]
                       for row in table.values() if row["scope"]), \
                (phase, part)
    # an instruction of a block is under one of the block's own scopes
    inside = ("/mamba/", "/gmu/", "/diff_attn/", "/mlp/")
    for row in table.values():
        if "ds.block" in (row["scope"] or ""):
            assert row["phase"] != "other", row
            assert any(part in row["scope"] + "/" for part in inside), row
    # (in the interpreter a kernel's name is a scope of its body's ops)
    kernels = {name for s in scopes
               for name in re.findall(r"ds_(?:sscan|flash|conv)_[a-z_]+", s)}
    assert kernels >= {
        "ds_sscan_fwd", "ds_sscan_bwd", "ds_conv_fwd", "ds_conv_bwd",
        "ds_flash_fwd", "ds_flash_bwd_dkv", "ds_flash_bwd_dq",
        "ds_flash_win_fwd", "ds_flash_win_bwd_dkv", "ds_flash_win_bwd_dq"}
    # one row a Mamba layer, by its layer
    scans = tracing.selective_scan_calls("train/step")
    assert [(r["layer"], r["path"], r["channels"], r["state"], r["chunk"],
             r["channels_per_step"]) for r in scans] \
        == [(l, "kernel", 128, 16, 128, 128) for l in (0, 2, 4)]
    # the flash calls: two windowed layers' (one row: the same shape), the
    # full layer's, and the cross layer's on the full layer's keys
    flash = tracing.flash_calls("train/step")
    told_apart = lambda r: (r["dk"], r["dv"], r["heads"], r["kv_heads"],
                            r.get("window", 0), r.get("kv_of", -1))
    assert sorted(map(told_apart, flash)) == [
        (16, 32, 2, 1, 0, -1), (16, 32, 2, 1, 0, 5), (16, 32, 2, 1, 16, -1)]
    assert tracing.conv_calls("train/step")[0]["channels"] == 128
    assert tracing.ssd_chunks("train/step") is None
