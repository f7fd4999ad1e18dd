"""Ring attention (sequence/ring.py) on the eight-device mesh: the plain ring
and the ring of flash chunks against dense attention, forward and
gradients (tests/test_long_context.py has sparse attention, the flash
kernel and packed training).  A file of its own so that ``--dist
loadfile`` gives the long-context tests to two workers."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.comm.mesh import MeshTopology, set_topology
from deepspeed_tpu.sequence.ring_attention import (ring_attention,
                                                   DistributedRingAttention)

from tests.test_long_context import (  # noqa: F401 (the fixtures come by name)
    _dense_causal)


# -------------------------------------------------------------- ring attention

def test_ring_attention_matches_dense(devices8):
    """sp=8 ring attention must equal single-device dense causal attention."""
    set_topology(MeshTopology(sequence_parallel_size=8))
    rng = np.random.default_rng(0)
    B, S, H, hd = 2, 64, 4, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
               for _ in range(3))
    out = ring_attention(q, k, v, causal=True)
    want = _dense_causal(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_non_causal(devices8):
    set_topology(MeshTopology(sequence_parallel_size=4))
    rng = np.random.default_rng(1)
    B, S, H, hd = 2, 32, 2, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
               for _ in range(3))
    out = ring_attention(q, k, v, causal=False)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    p = jax.nn.softmax(s, axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_grad_flows(devices8):
    set_topology(MeshTopology(sequence_parallel_size=8))
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)

    def loss(q):
        return jnp.sum(ring_attention(q, q, q, causal=True) ** 2)

    g = jax.grad(loss)(q)
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).sum()) > 0


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_matches_dense_impl(devices8, causal):
    """round-3 VERDICT item 8: the per-chunk product rides the
    from-scratch flash kernel (chunk_fwd/chunk_bwd + global-lse merge);
    forward AND all three gradients must match the dense ring path."""
    set_topology(MeshTopology(sequence_parallel_size=4))
    rng = np.random.default_rng(9)
    B, S, H, hd = 2, 64, 2, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
               for _ in range(3))
    out_f = ring_attention(q, k, v, causal=causal, impl="flash")
    out_d = ring_attention(q, k, v, causal=causal, impl="dense")
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               rtol=2e-5, atol=2e-5)

    def loss(impl):
        return lambda q, k, v: jnp.sum(
            ring_attention(q, k, v, causal=causal, impl=impl) ** 2)

    gf = jax.grad(loss("flash"), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss("dense"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)


def test_ring_flash_bf16_grads(devices8):
    """The training dtype: bf16 forward + backward through the flash ring
    must trace (review round 4 caught a branch-dtype mismatch here) and
    track the dense ring within bf16 tolerance."""
    set_topology(MeshTopology(sequence_parallel_size=4))
    rng = np.random.default_rng(12)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 32, 2, 16)), jnp.bfloat16)
               for _ in range(3))

    def loss(impl):
        return lambda q, k, v: jnp.sum(
            ring_attention(q, k, v, causal=True, impl=impl)
            .astype(jnp.float32) ** 2)

    gf = jax.grad(loss("flash"), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss("dense"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=0.1, atol=0.05)


def test_ring_auto_routes_flash(devices8):
    """auto dispatch selects the kernel path for kernel-friendly chunks
    and the dense path for chunks that do not block-decompose."""
    from deepspeed_tpu.sequence.ring_attention import _flash_chunks_ok
    assert _flash_chunks_ok(512, 64, 4, True)
    assert not _flash_chunks_ok(4, 64, 4, True)     # chunk -> blocks < 8
    assert not _flash_chunks_ok(512, 64, 4, False)  # GQA stays dense
    assert not _flash_chunks_ok(16384, 64, 4, True)  # VMEM budget


def test_distributed_ring_attention_wrapper(devices8):
    set_topology(MeshTopology(sequence_parallel_size=2))
    attn = DistributedRingAttention(causal=True)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(4, 16, 2, 8)), jnp.float32)
    out = attn(q, q, q)
    assert out.shape == q.shape
