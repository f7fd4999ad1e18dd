"""Qwen3-Next's toy model (tests/test_qwen3_next.py: the same sizes, seeded
weights, packed batch and reference) through the engine: the first step's
loss against the plain reference under ZeRO 0, 1 and 2, the count of rows
over a share's bound beside the loss, the scopes and accounts of a toy
step.  A file of its own so that ``--dist loadfile`` gives the family's
tests to two workers."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from tests.test_qwen3_next import (  # noqa: F401 (the fixtures come by name)
    B, GAS, LOSS_TOL, one_device, packed_batch, real_kernels, reference,
    sizes_of, toy, toy_model)
from tests.util import base_config


@functools.lru_cache(maxsize=None)
def reference_first_step_loss():
    """What the three stages' first steps are held to: the same weights and
    batch, so the reference runs once."""
    model, start, _, _ = toy()
    return reference.step_loss(start, packed_batch(), sizes_of(model),
                               chunk=1)


@pytest.mark.parametrize("stage", [0, 1, 2])
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
    got = float(engine.train_batch(batch=batch))
    assert abs(got - want) < LOSS_TOL, (got, want)
    assert np.isfinite(float(engine.train_batch(batch=packed_batch(1))))
