"""ZeRO++ tests (reference: tests/unit/runtime/zero/test_zeropp.py +
docs/_tutorials/zeropp.md): int8 block quantization, qwZ quantized weight
gather, qgZ quantized gradient reduce-scatter, hpZ secondary shard."""
import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from deepspeed_tpu.utils.jax_compat import shard_map

import deepspeed_tpu

from deepspeed_tpu.ops.pallas.quantization import (
    block_quantize_int8, block_dequantize_int8)
from deepspeed_tpu.runtime.zero.zeropp import quantized_psum_scatter
from tests.util import tiny_gpt2, base_config, random_batches


# ------------------------------------------------------------------ quant ops

def test_block_quant_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 1024)).astype(np.float32))
    q, s = block_quantize_int8(x)
    assert q.dtype == jnp.int8
    assert s.shape == (64, 1024 // 256)
    deq = block_dequantize_int8(q, s)
    # symmetric int8: |err| <= scale/2 = amax/254 per block
    err = np.abs(np.asarray(deq - x))
    amax = np.abs(np.asarray(x)).reshape(64, 4, 256).max(-1)
    bound = np.repeat(amax / 254.0, 256, axis=-1).reshape(64, 1024) + 1e-7
    assert (err <= bound + 1e-6).all()


def test_block_quant_preserves_zeros_and_extremes():
    x = jnp.zeros((8, 256))
    q, s = block_quantize_int8(x)
    assert np.asarray(q).sum() == 0
    assert np.isfinite(np.asarray(s)).all()
    x = jnp.full((8, 256), -3.5)
    q, s = block_quantize_int8(x)
    np.testing.assert_allclose(np.asarray(block_dequantize_int8(q, s)),
                               -3.5, rtol=1e-2)


def test_block_quant_3d_and_ragged():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 8, 512)).astype(np.float32))
    q, s = block_quantize_int8(x)
    assert q.shape == x.shape and s.shape == (4, 8, 2)
    # C not divisible by block: one block per row
    x = jnp.asarray(rng.normal(size=(4, 100)).astype(np.float32))
    q, s = block_quantize_int8(x)
    assert s.shape == (4, 1)
    np.testing.assert_allclose(np.asarray(block_dequantize_int8(q, s)),
                               np.asarray(x), atol=0.1)


# ------------------------------------------------------------------------ qgZ

def test_quantized_psum_scatter_matches_exact(devices8):
    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("dp",))
    rng = np.random.default_rng(2)
    # distinct per-device local grads: [8, 16, 256] leading = device dim
    local = rng.normal(size=(8, 16, 256)).astype(np.float32)
    x = jax.device_put(jnp.asarray(local),
                       NamedSharding(mesh, P("dp", None, None)))

    def body(v):
        # v: [1, 16, 256] this device's local grad
        return quantized_psum_scatter(v[0], "dp", n=8, scatter_dim=0)[None]

    out = shard_map(body, mesh=mesh, in_specs=P("dp", None, None),
                    out_specs=P(None, "dp", None))(x)
    exact = local.sum(axis=0)                     # [16, 256]
    got = np.asarray(out)[0]
    # int8-quantized contributions: tolerance scales with amax/127 * ndev
    tol = np.abs(local).max() / 127.0 * 8 * 0.75 + 1e-5
    np.testing.assert_allclose(got, exact, atol=tol)


def test_quantized_psum_scatter_uneven_falls_back(devices8):
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    x = jnp.ones((8, 3, 256))

    def body(v):
        return quantized_psum_scatter(v[0], "dp", n=8, scatter_dim=0)[None]

    out = shard_map(body, mesh=mesh, in_specs=P("dp", None, None),
                    out_specs=P("dp", None, None))(x)
    np.testing.assert_allclose(np.asarray(out)[0], 8.0)


# ------------------------------------------------------------------------ qwZ

def _train(engine, steps, seed):
    losses = []
    for i in range(steps):
        b = random_batches(1, batch_size=8, seed=seed + i)[0]
        losses.append(float(engine.train_batch(
            batch={"input_ids": b["input_ids"][None]})))
    return losses


def test_qwz_trains_to_parity(devices8):
    """stage-3 + zero_quantized_weights trains within tolerance of plain
    stage-3 (VERDICT round-1 item 6 'Done =' criterion)."""
    ref, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(), config=base_config(
            zero_optimization={"stage": 3}))
    qwz, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(), config=base_config(
            zero_optimization={"stage": 3, "zero_quantized_weights": True,
                               "stage3_param_persistence_threshold": 0}))
    l_ref = _train(ref, steps=4, seed=31)
    l_qwz = _train(qwz, steps=4, seed=31)
    # int8 weight gather is lossy: losses track but are not bit-equal
    np.testing.assert_allclose(l_qwz, l_ref, rtol=0.05, atol=0.05)


def test_qwz_gathers_int8(devices8):
    """The all-gather in the compiled step must move s8 elements — the 2-4x
    comm-volume reduction is the whole point (comm-bytes assertion)."""
    engine, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(), config=base_config(
            zero_optimization={"stage": 3, "zero_quantized_weights": True,
                               "stage3_param_persistence_threshold": 0}))
    b = random_batches(1, batch_size=8, seed=1)[0]
    batch = engine._shard_batch({"input_ids": b["input_ids"][None]},
                                stacked=True)
    fn = engine._get_compiled("train_step")
    with engine._stream_scope():
        lowered = fn.lower(engine.state, batch, engine._next_rng())
    hlo = lowered.compile().as_text()
    ag_lines = [l for l in hlo.splitlines() if "all-gather" in l]
    assert ag_lines, "no all-gather in compiled step"
    assert any("s8[" in l for l in ag_lines), ag_lines[:5]


# ------------------------------------------------------------------------ hpZ

def test_hpz_mesh_axis(devices8):
    engine, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(), config=base_config(
            zero_optimization={"stage": 3, "zero_hpz_partition_size": 2,
                               "stage3_param_persistence_threshold": 0}))
    shape = dict(engine.mesh.shape)
    assert shape["hpz"] == 2 and shape["data"] == 4
    # param STORAGE shards over the hpz axis only (secondary shard);
    # optimizer state keeps the full zero sharding
    qkv_spec = engine.param_specs["blocks"]["qkv_w"]
    flat = [a for e in qkv_spec if e is not None
            for a in ((e,) if isinstance(e, str) else e)]
    assert "hpz" in flat and "data" not in flat, qkv_spec


def test_hpz_trains_to_parity(devices8):
    ref, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(), config=base_config(
            zero_optimization={"stage": 3}))
    hpz, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(), config=base_config(
            zero_optimization={"stage": 3, "zero_hpz_partition_size": 2}))
    l_ref = _train(ref, steps=3, seed=17)
    l_hpz = _train(hpz, steps=3, seed=17)
    np.testing.assert_allclose(l_hpz, l_ref, rtol=1e-4, atol=1e-4)


def test_qwz_int8_gather_when_layers_divisible(devices8):
    """When num_layers is divisible by the zero world size the shard would
    land on the stacked layer dim (where the scan slice, not an all-gather,
    gathers the layer) — the engine must move it onto weight dims so the
    quantized gather still engages."""
    engine, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(num_layers=8), config=base_config(
            zero_optimization={"stage": 3, "zero_quantized_weights": True,
                               "stage3_param_persistence_threshold": 0}))
    spec = tuple(engine.param_specs["blocks"]["qkv_w"])
    assert spec[0] is None, spec     # layer dim left unsharded
    b = random_batches(1, batch_size=8, seed=1)[0]
    batch = engine._shard_batch({"input_ids": b["input_ids"][None]},
                                stacked=True)
    fn = engine._get_compiled("train_step")
    with engine._stream_scope():
        lowered = fn.lower(engine.state, batch, engine._next_rng())
    hlo = lowered.compile().as_text()
    ag_lines = [l for l in hlo.splitlines() if "all-gather" in l]
    assert any("s8[" in l for l in ag_lines), ag_lines[:5]


# ----------------------------------------------------------------------- MiCS

def test_mics_shards_within_subgroup(devices8):
    """mics_shard_size=2 on 8 devices: state shards over 2-device groups and
    replicates across the 4 groups (reference mics.py:55)."""
    engine, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(), config=base_config(
            zero_optimization={"stage": 3, "mics_shard_size": 2,
                               "stage3_param_persistence_threshold": 0}))
    assert dict(engine.mesh.shape)["hpz"] == 2
    spec = engine.param_specs["blocks"]["qkv_w"]
    flat = [a for e in spec if e is not None
            for a in ((e,) if isinstance(e, str) else e)]
    assert "hpz" in flat and "data" not in flat, spec
    # grads/opt also restricted to the sub-group (unlike hpZ)
    gspec = engine.grad_specs["blocks"]["qkv_w"]
    gflat = [a for e in gspec if e is not None
             for a in ((e,) if isinstance(e, str) else e)]
    assert "hpz" in gflat and "data" not in gflat, gspec


def test_mics_trains_to_parity(devices8):
    ref, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(), config=base_config(
            zero_optimization={"stage": 3}))
    mics, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(), config=base_config(
            zero_optimization={"stage": 3, "mics_shard_size": 2}))
    l_ref = _train(ref, steps=3, seed=41)
    l_mics = _train(mics, steps=3, seed=41)
    np.testing.assert_allclose(l_mics, l_ref, rtol=1e-4, atol=1e-4)


# ------------------------------------------------- qgZ × pipeline (r3 item 4)

def _pipe_cfg(gas, qgz, **extra_pipe):
    cfg = base_config(
        train_micro_batch_size_per_gpu=1, gradient_accumulation_steps=gas,
        zero_optimization={"stage": 1,
                           **({"zero_quantized_gradients": True}
                              if qgz else {})},
        mesh={"pipe_parallel_size": 2, "data_parallel_size": 4})
    if extra_pipe:
        cfg["pipeline"] = extra_pipe
    return cfg


def _pipe_train(engine, gas, steps, seed):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(steps):
        batch = {"input_ids": rng.integers(0, 128, size=(gas, 4, 16),
                                           dtype=np.int32)}
        out.append(float(engine.train_batch(batch=batch)))
    return out
