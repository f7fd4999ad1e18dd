"""Ulysses sequence-parallel tests (reference capability:
deepspeed/sequence/layer.py + ZeRO over seq-data group)."""
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import MeshTopology, set_topology
from deepspeed_tpu.ops.attention import xla_causal_attention
from deepspeed_tpu.sequence.layer import distributed_attention
from tests.util import tiny_gpt2, base_config, random_batches


def test_distributed_attention_matches_local(devices8):
    import jax
    set_topology(MeshTopology(sequence_parallel_size=4))
    rng = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(r, (2, 32, 8, 16))
               for r in jax.random.split(rng, 3))
    ref = xla_causal_attention(q, k, v)
    out = jax.jit(lambda a, b, c: distributed_attention(
        a, b, c, xla_causal_attention))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["dense", "gqa"])
@pytest.mark.parametrize("mesh_kw", [
    dict(data_parallel_size=4), dict(data_parallel_size=2,
                                     model_parallel_size=2)],
    ids=["data4", "data2_model2"])
def test_flash_kernel_partitioned_by_shard_map(devices8, interpret_pallas,
                                               mesh_kw, kv_heads):
    """At sp == 1 on a multi-device mesh the Pallas kernel runs per device
    inside distributed_attention's shard_map (GSPMD cannot split a Mosaic
    call): output and gradients equal the one-device kernel's."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.ops.attention import causal_attention
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(4, 128, 4, 64)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(4, 128, kv_heads, 64)), jnp.float32)
            for _ in range(2))
    attn = lambda *a: causal_attention(*a, impl="flash")
    loss = lambda *a: jnp.sum(attn(*a) ** 2)

    set_topology(MeshTopology(devices=devices8[:1]))
    ref, ref_grads = attn(q, k, v), jax.grad(loss, (0, 1, 2))(q, k, v)

    topo = MeshTopology(devices=devices8[:4], **mesh_kw)
    set_topology(topo)
    sharding = NamedSharding(
        topo.mesh, P(topo.data_parallel_axes, None, "model", None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
    assert "shard_map" in str(jax.make_jaxpr(attn)(qs, ks, vs))
    out = jax.jit(attn)(qs, ks, vs)
    assert out.sharding.is_equivalent_to(sharding, out.ndim)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    for g, r in zip(jax.jit(jax.grad(loss, (0, 1, 2)))(qs, ks, vs),
                    ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-5)


@pytest.mark.parametrize("padded", [False, True])
def test_bidirectional_flash_partitioned_by_shard_map(
        devices8, interpret_pallas, padded):
    """The encoder path shares the wrap: pads ride as segment ids."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.attention import bidirectional_attention
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(4, 128, 4, 64)), jnp.float32)
               for _ in range(3))
    pad = (jnp.asarray(np.arange(128)[None] < [[128], [96], [64], [32]],
                       jnp.int32) if padded else None)
    attn = lambda *a: bidirectional_attention(*a, pad_mask=pad, impl="flash")
    set_topology(MeshTopology(devices=devices8[:1]))
    ref = attn(q, k, v)
    set_topology(MeshTopology(devices=devices8[:4], data_parallel_size=2,
                              model_parallel_size=2))
    assert "shard_map" in str(jax.make_jaxpr(attn)(q, k, v))
    out = jax.jit(attn)(q, k, v)
    keep = np.asarray(pad, bool) if padded else np.ones((4, 128), bool)
    np.testing.assert_allclose(np.asarray(out)[keep], np.asarray(ref)[keep],
                               atol=1e-6)


@pytest.mark.parametrize("stage", [0, 2])
def test_sp_training_matches_dp(devices8, stage):
    """sp=2 engine must produce the same losses as pure dp (ZeRO over the
    seq-data combined group, reference engine.py:1460)."""
    ref, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(), config=base_config(
            zero_optimization={"stage": stage}))
    sp, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(), config=base_config(
            zero_optimization={"stage": stage},
            mesh={"sequence_parallel_size": 2}))
    for i in range(2):
        batches = random_batches(1, batch_size=8, seq_len=16, seed=40 + i)
        l_ref = float(ref.train_batch(
            batch={"input_ids": batches[0]["input_ids"][None]}))
        l_sp = float(sp.train_batch(
            batch={"input_ids": batches[0]["input_ids"][None]}))
        assert abs(l_ref - l_sp) < 2e-4, f"step {i}: {l_ref} vs {l_sp}"


def test_ring_cp_training_matches_dp(devices8):
    """mesh.sequence_parallel_impl="ring": the engine's seq axis runs
    ring-attention context parallelism end-to-end in training (round-4:
    ring CP reachable from config, not just the direct API) and matches
    pure DP."""
    ref, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(), config=base_config(
            zero_optimization={"stage": 2}))
    ring, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(), config=base_config(
            zero_optimization={"stage": 2},
            mesh={"sequence_parallel_size": 2,
                  "sequence_parallel_impl": "ring"}))
    assert ring.topology.sequence_parallel_impl == "ring"
    for i in range(2):
        batches = random_batches(1, batch_size=8, seq_len=16, seed=50 + i)
        l_ref = float(ref.train_batch(
            batch={"input_ids": batches[0]["input_ids"][None]}))
        l_ring = float(ring.train_batch(
            batch={"input_ids": batches[0]["input_ids"][None]}))
        assert abs(l_ref - l_ring) < 2e-4, f"step {i}: {l_ref} vs {l_ring}"
