"""ops/linear_attention.py: the chunked gated delta rule — the XLA form
and, in interpret mode, the Mosaic kernels of ops/pallas/
gated_delta_rule.py — against the literal per-token recurrence, forward
and gradient, with packed documents whose boundaries fall inside a chunk,
at a chunk's edge and around a one-token document; which of the two a
call takes and what it tells the step's account; the causal convolution's
reset."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.linear_attention import (
    causal_conv, gated_delta_rule, gated_delta_rule_recurrent, l2norm)
from deepspeed_tpu.telemetry import tracing

B, S, HK, HV, DK, DV = 2, 50, 2, 4, 8, 8


def _inputs(seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q = l2norm(f(B, S, HK, DK)) / np.sqrt(DK)
    k = l2norm(f(B, S, HK, DK))
    v = f(B, S, HV, DV)
    # decays as the layer makes them: -exp(A_log) * softplus(.), A to 16
    g = -jnp.asarray(rng.uniform(0, 16, size=(B, S, HV)) ** 2 / 16,
                     jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, size=(B, S, HV)), jnp.float32)
    return q, k, v, g, beta


def _segments():
    """Row 0: documents of 16, 1 (a one-token document at a chunk's edge
    for chunk 16), 13, 20 tokens; row 1: boundaries at 5 and 32."""
    seg = np.zeros((B, S), np.int32)
    seg[0, 16:] = 1
    seg[0, 17:] = 2
    seg[0, 30:] = 3
    seg[1, 5:] = 1
    seg[1, 32:] = 2
    return jnp.asarray(seg)


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("chunk", [16, 7, 25, 64])
def test_chunked_matches_the_recurrence(chunk, packed):
    """16 and 25 divide or halve S=50, 7 does not divide it, 64 is one
    chunk; forward and the gradient in all five arguments."""
    args = _inputs()
    seg = _segments() if packed else None
    with jax.default_matmul_precision("highest"):
        got = gated_delta_rule(*args, seg, chunk=chunk)
        want = gated_delta_rule_recurrent(*args, seg)
        np.testing.assert_allclose(got, want, atol=5e-6)

        def loss(fn, *extra):
            return lambda *a: jnp.sum(jnp.sin(fn(*a, *extra)))

        g_got = jax.grad(loss(gated_delta_rule, seg, chunk),
                         argnums=range(5))(*args)
        g_want = jax.grad(loss(gated_delta_rule_recurrent, seg),
                          argnums=range(5))(*args)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.abs(b).max()))


def test_a_document_sees_only_itself():
    """The output inside a document is what the document gives alone."""
    q, k, v, g, beta = _inputs(1)
    seg = _segments()
    whole = gated_delta_rule(q, k, v, g, beta, seg, chunk=16)
    for lo, hi in ((0, 16), (16, 17), (17, 30), (30, 50)):
        alone = gated_delta_rule(*(t[:1, lo:hi] for t in (q, k, v, g, beta)),
                                 chunk=16)
        np.testing.assert_allclose(whole[:1, lo:hi], alone, atol=5e-6)


def test_bf16_operands_keep_a_float32_state():
    q, k, v, g, beta = _inputs(2)
    got = gated_delta_rule(q, k, v.astype(jnp.bfloat16), g, beta,
                           _segments(), chunk=16)
    want = gated_delta_rule_recurrent(q, k, v, g, beta, _segments())
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=0.05)


def test_the_step_account_holds_the_chunks():
    """... and which lowering ran: the XLA form here (no TPU), the
    kernels where a call asks for them, with the value heads and chunks
    one grid step takes."""
    with tracing.step_account("test/delta"):
        gated_delta_rule(*_inputs(), chunk=16)
    assert tracing.delta_rule_chunks("test/delta") == [
        {"chunks": 4, "chunk_len": 16, "batch": B, "heads": HV,
         "dk": DK, "dv": DV, "decay": "head", "path": "xla"}]
    assert tracing.delta_rule_chunks("test/none") is None
    with tracing.step_account("test/delta"):
        gated_delta_rule(*_wide_inputs(256), interpret=True)
    assert tracing.delta_rule_chunks("test/delta") == [
        {"chunks": 4, "chunk_len": 64, "batch": B, "heads": HV,
         "dk": 128, "dv": 128, "decay": "head", "path": "kernel",
         "heads_per_step": 2,
         "chunks_per_step": 4}]


# ------------------------------------------------------ the Mosaic kernels
def _wide_inputs(S, seed=0, dt=jnp.float32):
    """Heads of 128, the width the kernels take; milder decays than
    ``_inputs`` so that the state matters across chunks of 64."""
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q = l2norm(f(B, S, HK, 128)) / np.sqrt(128)
    k = l2norm(f(B, S, HK, 128))
    v = f(B, S, HV, 128).astype(dt)
    g = -jnp.asarray(rng.uniform(0, 4, size=(B, S, HV)) ** 2 / 16,
                     jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, size=(B, S, HV)), jnp.float32)
    return q, k, v, g, beta


def _wide_segments(S):
    """Row 0: a boundary at a chunk's edge (64), a one-token document
    behind it, one inside a chunk (100) and one at 192; row 1: boundaries
    at 5 and 128."""
    seg = np.zeros((B, S), np.int32)
    for at in (64, 65, 100, 192):
        seg[0, at:] += 1
    seg[1, 5:] += 1
    seg[1, 128:] += 1
    return jnp.asarray(seg)


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_l2norm_scales_are_the_norm_taken_outside(kernels):
    """``l2norm_scales`` = (q's, k's): the call normalises and scales raw
    q and k itself — XLA before the chunked form, the kernels on the tiles
    they hold, forward and backward — and gives what normalising outside
    gives, values and the gradient in all five raw arguments."""
    S, scales = 200, (128 ** -0.5, 1.0)
    rng = np.random.default_rng(3)
    raw = lambda: jnp.asarray(rng.normal(size=(B, S, HK, 128)), jnp.float32)
    args = (raw(), raw()) + _wide_inputs(S, seed=3)[2:]
    seg = _wide_segments(S)

    def outside(q, k, *rest):
        return gated_delta_rule_recurrent(
            l2norm(q) * scales[0], l2norm(k) * scales[1], *rest, seg)

    def inside(*a):
        return gated_delta_rule(*a, seg, interpret=kernels,
                                l2norm_scales=scales)

    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(inside(*args), outside(*args), atol=5e-6)
        got = jax.grad(loss(inside), argnums=range(5))(*args)
        want = jax.grad(loss(outside), argnums=range(5))(*args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("packed", [False, True])
def test_causal_conv_resets_at_a_document(packed):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(B, S, 6)).astype(np.float32)
    w = rng.normal(size=(4, 6)).astype(np.float32)
    seg = np.asarray(_segments()) if packed else np.zeros((B, S), np.int32)
    want = np.zeros_like(x)
    for b in range(B):
        for t in range(S):
            for j in range(4):
                s = t - 3 + j
                if s >= 0 and seg[b, s] == seg[b, t]:
                    want[b, t] += w[j] * x[b, s]
    got = causal_conv(jnp.asarray(x), jnp.asarray(w),
                      jnp.asarray(seg) if packed else None)
    np.testing.assert_allclose(got, want, atol=1e-5)
