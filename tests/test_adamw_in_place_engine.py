"""The engine's step through ``mp_adamw``'s second entry
(``update_in_place``: runtime/step_programs.py ``apply_grads``) against the
optax entry it took before: the same state to the last bit, the same
``grad_norm`` and numerics-tier metrics, and the step's account of which
leaves were updated behind the barrier."""
import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

import deepspeed_tpu
from deepspeed_tpu.runtime.bf16_optimizer import MPAdamW
from deepspeed_tpu.telemetry import tracing
from tests.util import base_config, random_batches, tiny_gpt2

BF16_DIET = dict(
    bf16={"enabled": True, "master_weights_dtype": "bfloat16",
          "optimizer_states_dtype": "bfloat16"},
    data_types={"grad_accum_dtype": "bf16"})
METRICS = ("grad_norm", "num_group_norms", "num_nonfinite",
           "num_update_ratio")


def _one_device():
    return Mesh(np.asarray(jax.devices()[:1]), ("data",))


def _train(model, steps=3, mesh=None, entry="in_place", **config):
    """-> (engine, the metrics of each step).  ``entry`` ``"optax"`` hides
    the second entry, as a composed transform would."""
    config = base_config(**{**BF16_DIET, "zero_optimization": {"stage": 2},
                            **config})
    engine, *_ = deepspeed_tpu.initialize(model=model, config=config,
                                          mesh=mesh)
    if entry == "optax":
        tx = engine.optimizer
        assert isinstance(tx, MPAdamW)
        engine._step_ctx = dataclasses.replace(
            engine._step_ctx,
            optimizer=optax.GradientTransformation(tx.init, tx.update))
    seen, finish = [], engine._finish_step
    engine._finish_step = lambda m: (seen.append(
        {k: np.asarray(m[k]) for k in METRICS if k in m}), finish(m))[1]
    for i in range(steps):
        batch = random_batches(1, batch_size=8, seed=100 + i)[0]
        engine.train_batch(batch={"input_ids": batch["input_ids"][None]})
    return engine, seen


def _same_state(a, b):
    for x, y in zip(jax.tree.leaves(a.state), jax.tree.leaves(b.state)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x.astype(jnp.float32)),
                                      np.asarray(y.astype(jnp.float32)))


def _same_metrics(a, b, rtol=1e-6):
    assert len(a) == len(b) and all(set(x) == set(METRICS) for x in a)
    for x, y in zip(a, b):
        for k in METRICS:
            np.testing.assert_allclose(x[k], y[k], rtol=rtol, err_msg=k)


def _wide_gpt2():
    # d_model 128: the stacked blocks' matrices are whole tiles
    return tiny_gpt2(d_model=128, num_heads=4)


def _stacked_bytes(engine):
    leaves = jax.tree.leaves(engine.state["params"])
    return (sum(p.ndim >= 3 for p in leaves),
            sum(p.size * p.dtype.itemsize for p in leaves if p.ndim >= 3),
            sum(p.ndim < 3 for p in leaves),
            sum(p.size * p.dtype.itemsize for p in leaves if p.ndim < 3))


@pytest.mark.parametrize("mesh", ["one_device", "zero2_eight_devices",
                                  "zero3_eight_devices"])
def test_second_entry_gives_the_optax_entrys_step(devices8, mesh):
    kwargs = {
        "one_device": dict(mesh=_one_device(),
                           train_micro_batch_size_per_gpu=8),
        # the state split over ``data``, the parameters whole
        "zero2_eight_devices": {},
        "zero3_eight_devices": dict(zero_optimization={"stage": 3}),
    }[mesh]
    optax_way, want = _train(_wide_gpt2(), entry="optax", **kwargs)
    assert tracing.optimizer_fused() is None
    in_place, got = _train(_wide_gpt2(), **kwargs)
    assert in_place.mesh.size == (1 if mesh == "one_device" else 8)
    fused = tracing.optimizer_fused()
    # the four stacked matrices of the blocks ([2, 128, 384] qkv, [2, 128,
    # 128] proj, [2, 128, 512] and [2, 512, 128] mlp) are updated behind
    # the barrier; their biases and norms ([2, n]), the embeddings and the
    # final norm are left to XLA
    assert fused["leaves"] == 4 and fused["xla_leaves"] == 12
    assert fused["param_bytes"] == 2 * 2 * 128 * (384 + 128 + 512 + 512)
    assert tuple(fused.values()) == _stacked_bytes(in_place)
    _same_state(optax_way, in_place)
    _same_metrics(want, got)


def test_a_per_layer_matrix_model_has_no_stacked_leaf(devices8):
    """The Phi-4-mini-flash rehearsal sizes: a subtree a layer, every
    weight a matrix or a vector — the step has no barrier, and XLA may
    finish each update where its gradient is made."""
    from deepspeed_tpu.models.phi4flash import phi4flash_model
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "configs", "phi-4-mini-flash-reasoning.json")
    with open(path) as f:
        sizes = json.load(f)["rehearsal"]["builder_kwargs"]
    model = phi4flash_model("tiny", **sizes)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, mesh=_one_device(), config=base_config(
            **BF16_DIET, zero_optimization={"stage": 2}))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, sizes["vocab_size"], (1, 1, 64), dtype=np.int32)
    engine.train_batch(batch={"input_ids": ids})
    fused = tracing.optimizer_fused()
    assert fused["leaves"] == 0 and fused["xla_leaves"] == len(
        jax.tree.leaves(engine.state["params"]))
    assert max(p.ndim for p in jax.tree.leaves(engine.state["params"])) == 2


@pytest.mark.parametrize("config", [
    dict(gradient_clipping=1.0),
    dict(fp16={"enabled": True}, bf16={"enabled": False}, data_types={}),
], ids=["clipping", "fp16"])
def test_a_chained_optimizer_and_fp16_take_the_optax_entry(devices8, config):
    """``optax.chain`` offers no second entry, and fp16's skip-on-overflow
    is not its: the step takes ``update`` + ``apply_updates`` and its
    account has no ``optimizer_fused``."""
    engine, seen = _train(_wide_gpt2(), steps=1, mesh=_one_device(),
                          train_micro_batch_size_per_gpu=8, **config)
    assert tracing.optimizer_fused() is None
    assert np.isfinite(seen[0]["grad_norm"])
