"""Mellum's toy model (tests/test_mellum.py: the same sizes, seeded weights,
packed batch and reference) through the engine: the first step's loss
against the plain reference under ZeRO 0 and 2, and on a four-wide expert
axis where the experts are really spread.  A file of its own so that
``--dist loadfile`` gives the family's tests to two workers."""
import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.mellum import SLIDING
from deepspeed_tpu.telemetry import tracing

from tests.util import base_config
from tests.test_mellum import (  # noqa: F401 (the fixtures come by name)
    B, GAS, _isolation, LOSS_TOL, packed_batch, reference, seeded_params,
    sizes_of, toy_model)


# ------------------------------------------------------------ the engine
def _engine(model, mesh, stage, **mesh_config):
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=base_config(
            train_micro_batch_size_per_gpu=B // mesh.size,
            gradient_accumulation_steps=GAS, seed=3,
            zero_optimization={"stage": stage},
            **({"mesh": mesh_config} if mesh_config else {})), mesh=mesh)
    start = seeded_params(model)
    engine.state["params"] = jax.tree.map(
        lambda new, old: jax.device_put(new.astype(old.dtype), old.sharding),
        start, engine.state["params"])
    return engine, start


@pytest.mark.parametrize("stage", [0, 2])
def test_engine_first_step_loss_matches_the_reference(stage):
    model = toy_model()
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    engine, start = _engine(model, mesh, stage)
    batch = packed_batch()
    want = reference.step_loss(start, batch, sizes_of(model), chunk=1)
    got = float(engine.train_batch(batch=batch))
    assert abs(got - want) < LOSS_TOL, (got, want)
    assert engine.step_counts() == {"moe/rows_over_bound": 0}
    # ... and the router's load left the step beside it
    load = engine.step_load()["totals"]
    assert load["moe/routed_rows"] > 0 and load["moe/even_rows"] > 0


def test_engine_on_a_four_wide_expert_axis_matches_the_reference():
    """The deployment at toy size: experts spread four ways (2 of 8 a
    device), every expert layer through the exchange, ZeRO-2 over the same
    four.  The first step's loss is the uncut reference's, two steps leave
    the parameters where one device's engine leaves them, and the expert
    leaves stay split by expert."""
    model = toy_model()
    four = jax.sharding.Mesh(np.asarray(jax.devices()[:4]), ("expert",))
    engine, start = _engine(model, four, 2, expert_parallel_size=4)
    batch = packed_batch()
    want = reference.step_loss(start, batch, sizes_of(model), chunk=1)
    got = float(engine.train_batch(batch=batch))
    assert abs(got - want) < LOSS_TOL, (got, want)
    engine.train_batch(batch=packed_batch(1))
    assert engine.step_counts() == {"moe/rows_over_bound": 0}
    # ... and the router's load left the step beside it
    load = engine.step_load()["totals"]
    assert load["moe/routed_rows"] > 0 and load["moe/even_rows"] > 0
    w_in = engine.state["params"]["blocks"][SLIDING]["moe"]["w_in"]
    assert {s.data.shape for s in w_in.addressable_shards} \
        == {(1, 3, 2, 64, 32)}
    assert len({str(s.index) for s in w_in.addressable_shards}) == 4
    after_four = jax.tree.map(np.asarray, engine.state["params"])

    tracing.reset_programs()
    one = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    single, _ = _engine(toy_model(), one, 2)
    single.train_batch(batch=batch)
    single.train_batch(batch=packed_batch(1))
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(after_four),
            jax.tree.leaves(single.state["params"])):
        # Adam moves a weight whose gradient is rounding alone by +-lr a
        # step: a handful of such elements may differ by that, no more
        off = np.abs(a - np.asarray(b))
        assert (off > 2e-5).mean() < 1e-3 and off.max() < 2.5e-3, \
            (jax.tree_util.keystr(path), off.max())
