"""The gated delta rule's Mosaic kernels (ops/pallas/gated_delta_rule.py) in
interpret mode against the per-token recurrence and the XLA chunked form,
on tests/test_linear_attention.py's inputs.  A file of its own so that
``--dist loadfile`` gives the delta rule's tests to two workers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.linear_attention import (
    gated_delta_rule, gated_delta_rule_recurrent)
from deepspeed_tpu.telemetry import tracing

from tests.test_linear_attention import (  # noqa: F401 (the fixtures come by name)
    B, HK, HV, S, _wide_inputs, _wide_segments)


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("S", [256, 300, 512])
def test_kernels_match_the_recurrence(S, packed):
    """ds_gdr_fwd / ds_gdr_bwd in interpret mode at dk = dv = 128, two key
    heads serving four value heads: the values and the gradient in all
    five arguments against the per-token recurrence and against the XLA
    chunked form.  64 divides 256 and 512 (one and two blocks of four
    chunks) and not 300 (five chunks, one a step, the tail padded)."""
    args = _wide_inputs(S)
    seg = _wide_segments(S) if packed else None
    with jax.default_matmul_precision("highest"):
        got = gated_delta_rule(*args, seg, interpret=True)
        np.testing.assert_allclose(
            got, gated_delta_rule_recurrent(*args, seg), atol=5e-6)
        np.testing.assert_allclose(
            got, gated_delta_rule(*args, seg, interpret=False), atol=5e-6)

        def loss(fn, **how):
            return lambda *a: jnp.sum(jnp.sin(fn(*a, seg, **how)))

        grad = lambda fn, **how: jax.grad(loss(fn, **how),
                                          argnums=range(5))(*args)
        g_got = grad(gated_delta_rule, interpret=True)
        g_rec = grad(gated_delta_rule_recurrent)
        g_xla = grad(gated_delta_rule, interpret=False)
    for a, b, c in zip(g_got, g_rec, g_xla):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.abs(b).max()))
        np.testing.assert_allclose(a, c, atol=1e-5 * float(jnp.abs(c).max()))


def test_kernels_see_a_document_alone():
    q, k, v, g, beta = _wide_inputs(256, seed=1)
    whole = gated_delta_rule(q, k, v, g, beta, _wide_segments(256),
                             interpret=True)
    for lo, hi in ((0, 64), (64, 65), (65, 100), (100, 192), (192, 256)):
        alone = gated_delta_rule_recurrent(
            *(t[:1, lo:hi] for t in (q, k, v, g, beta)))
        np.testing.assert_allclose(whole[:1, lo:hi], alone, atol=5e-6)


def test_kernels_bf16_operands_keep_a_float32_state():
    """bf16 operands, as the cell runs them: the output is bf16 and near
    the float32 recurrence; what the forward rule saves for the backward
    is the state in float32 and T in ``v``'s dtype."""
    from deepspeed_tpu.ops.pallas import gated_delta_rule as gdr
    q, k, v, g, beta = _wide_inputs(256, seed=2)
    seg = _wide_segments(256)
    got = gated_delta_rule(q, k, v.astype(jnp.bfloat16), g, beta, seg,
                           interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        got.astype(jnp.float32),
        gated_delta_rule_recurrent(q, k, v, g, beta, seg), atol=0.05)
    blocking = gdr.chunks_per_step(4, 64, 2, 128, 128, 2)
    bf = lambda a: a.astype(jnp.bfloat16)
    _, (*_, s_in, t) = jax.eval_shape(
        lambda *a: gdr._gdr_fwd(*a, seg, blocking, None, True),
        bf(q), bf(k), bf(v), g, beta)
    assert (s_in.dtype, t.dtype) == (jnp.float32, jnp.bfloat16)
    assert s_in.shape == (B, HK, 4, 2, 128, 128)


@pytest.mark.parametrize("why,width,chunk,interpret", [
    ("heads narrower than a lane tile", 8, 16, True),
    ("a chunk that does not halve down to one token", 128, 24, True),
    ("no TPU here, nothing asked", 128, 64, None),
    ("the XLA form asked for", 128, 64, False),
])
def test_calls_the_kernels_refuse_fall_back_and_say_so(why, width, chunk,
                                                       interpret):
    rng = np.random.default_rng(0)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    S = 96
    args = (f(B, S, HK, width), f(B, S, HK, width), f(B, S, HV, width),
            -jnp.abs(f(B, S, HV)), jax.nn.sigmoid(f(B, S, HV)))
    with tracing.step_account("test/delta"):
        got = gated_delta_rule(*args, chunk=chunk, interpret=interpret)
    (row,) = tracing.delta_rule_chunks("test/delta")
    assert row["path"] == "xla" and "chunks_per_step" not in row, why
    assert got.shape == (B, S, HV, width)


@pytest.mark.parametrize("n,itemsize,chunks", [
    (128, 2, 8), (128, 4, 8), (5, 4, 1), (6, 2, 2)])
def test_chunks_per_step_is_a_rule_of_shapes(n, itemsize, chunks):
    """The most chunks of 8, 4, 2, 1 that divide the sequence's and whose
    blocks fit what a call is granted unasked; the cell (128 chunks, bf16,
    two value heads of 128 x 128 a key head) walks 8 a step."""
    from deepspeed_tpu.ops.pallas import gated_delta_rule as gdr
    blocking = gdr.chunks_per_step(n, 64, 2, 128, 128, itemsize)
    assert (blocking.chunk, blocking.chunks, blocking.heads) \
        == (64, chunks, 2)
    assert blocking.vmem_bytes <= gdr.vmem.UNASKED
