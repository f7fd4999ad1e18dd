"""ops/linear_attention.py with a decay a key channel (``g`` [B, S, Hv,
dk]: Kimi Delta Attention): the chunked XLA form against the literal
per-token recurrence, output and gradient in all five arguments — with
documents that end inside a chunk, at its edge and after one token, a tail
that does not fill a chunk, a chunk that is not whole sub-blocks of 16, and
decays as strong as -20 a token on some channels beside 0 on others; a
vector constant across channels against the decay a head; what the call
tells the step's account, and that the shape of ``g`` alone chooses."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import linear_attention
from deepspeed_tpu.ops.linear_attention import (
    gated_delta_rule, gated_delta_rule_recurrent, l2norm)
from deepspeed_tpu.telemetry import tracing

ARGUMENTS = ("q", "k", "v", "g", "beta")
#: name: (B, S, Hk, Hv, dk, dv, chunk, decays)
CASES = {
    # chunks of 32 = two sub-blocks of 16, a tail of 6; two value heads a
    # key head
    "tail": (2, 70, 2, 4, 8, 8, 32, "mild"),
    # one chunk of 40 = five sub-blocks of 8 (S under the chunk)
    "odd_chunk": (1, 40, 1, 2, 16, 8, 64, "mild"),
    # -20 a token on every third channel, 0 on the next, chunks of 16
    "strong": (2, 48, 2, 2, 12, 8, 16, "strong"),
}


def _inputs(name):
    B, S, Hk, Hv, dk, dv, chunk, decays = CASES[name]
    rng = np.random.default_rng(sorted(CASES).index(name))
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q = l2norm(f(B, S, Hk, dk)) / np.sqrt(dk)
    k = l2norm(f(B, S, Hk, dk))
    v = f(B, S, Hv, dv)
    # as the layer makes them: -exp(A_log) * softplus(.), A to 16
    g = -jnp.asarray(rng.uniform(0, 4, size=(B, S, Hv, dk)) ** 2 / 16,
                     jnp.float32)
    if decays == "strong":
        channel = jnp.arange(dk) % 3
        g = jnp.where(channel == 0, -20.0, jnp.where(channel == 1, 0.0, g))
    beta = jnp.asarray(rng.uniform(0, 1, size=(B, S, Hv)), jnp.float32)
    # row 0: a document ends inside the first chunk (5), one at the edge
    # of a chunk of 16 or 32 (32) and a one-token document behind it
    seg = np.zeros((B, S), np.int32)
    seg[0, 5:] = 1
    seg[0, 32:] = 2
    seg[0, 33:] = 3
    seg[-1, 21:] += 4
    return (q, k, v, g, beta), jnp.asarray(seg), chunk


@functools.lru_cache(maxsize=None)
def _both(name):
    """(output, the five gradients) of the chunked form and of the
    recurrence, once a case."""
    args, seg, chunk = _inputs(name)
    weights = jnp.asarray(np.random.default_rng(7).normal(
        size=args[2].shape), jnp.float32)

    def of(fn):
        def loss(*a):
            o = fn(*a)
            return jnp.sum(o * weights), o
        (_, o), grads = jax.value_and_grad(loss, argnums=range(5),
                                           has_aux=True)(*args)
        return o, grads

    with jax.default_matmul_precision("highest"):
        return (of(lambda *a: gated_delta_rule(*a, seg, chunk=chunk)),
                of(lambda *a: gated_delta_rule_recurrent(*a, seg)))


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_output_is_the_recurrences(name):
    (got, _), (want, _) = _both(name)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=5e-6)


@pytest.mark.parametrize("argument", ARGUMENTS)
@pytest.mark.parametrize("name", sorted(CASES))
def test_the_gradient_is_the_recurrences(name, argument):
    (_, got), (_, want) = _both(name)
    i = ARGUMENTS.index(argument)
    assert bool(jnp.isfinite(got[i]).all())
    np.testing.assert_allclose(
        got[i], want[i], atol=1e-5 * float(jnp.abs(want[i]).max()))


def test_strong_decays_forget_and_zero_decays_keep():
    """What the 'strong' case is for: a channel at -20 a token holds
    nothing of the token before, one at 0 everything — the output differs
    from the mean decay's by far more than the tolerance."""
    (q, k, v, g, beta), seg, chunk = _inputs("strong")
    got = gated_delta_rule(q, k, v, g, beta, seg, chunk=chunk)
    mean = gated_delta_rule(q, k, v, g.mean(-1), beta, seg, chunk=chunk)
    assert float(jnp.abs(got - mean).max()) > 1e-2


def test_a_vector_constant_across_channels_is_the_decay_a_head():
    (q, k, v, g, beta), seg, chunk = _inputs("tail")
    a_head = g[..., 0]
    with jax.default_matmul_precision("highest"):
        want = gated_delta_rule(q, k, v, a_head, beta, seg, chunk=chunk)
        got = gated_delta_rule(
            q, k, v, jnp.broadcast_to(a_head[..., None], g.shape), beta, seg,
            chunk=chunk)
    np.testing.assert_allclose(got, want, atol=5e-6)


def test_a_document_sees_only_itself():
    (q, k, v, g, beta), seg, chunk = _inputs("tail")
    whole = gated_delta_rule(q, k, v, g, beta, seg, chunk=chunk)
    for lo, hi in ((0, 5), (5, 32), (32, 33), (33, 70)):
        alone = gated_delta_rule(*(t[:1, lo:hi] for t in (q, k, v, g, beta)),
                                 chunk=chunk)
        np.testing.assert_allclose(whole[:1, lo:hi], alone, atol=5e-6)


def test_bf16_operands_keep_float32_decays_and_state():
    (q, k, v, g, beta), seg, chunk = _inputs("strong")
    got = gated_delta_rule(q, k, v.astype(jnp.bfloat16), g, beta, seg,
                           chunk=chunk)
    (want, _) = _both("strong")[1]
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=0.05)


def test_no_exponential_of_a_positive_number():
    """Every ``exp`` of the traced program has an argument that is <= 0
    whatever the inputs: checked on the values, with decays of -20."""
    (q, k, v, g, beta), seg, chunk = _inputs("strong")
    seen = []
    real = jnp.exp

    def watched(x):
        jax.debug.callback(lambda m: seen.append(float(m)), jnp.max(
            jnp.where(jnp.isfinite(x), x, -jnp.inf)))
        return real(x)

    linear_attention.jnp.exp = watched
    try:
        jax.block_until_ready(linear_attention._chunked_xla_channel(
            q, k, v, g, beta, seg, n=3, C=16))
    finally:
        linear_attention.jnp.exp = real
    assert len(seen) >= 4 and max(seen) <= 0.0, seen


@pytest.mark.parametrize("C", [64, 40, 16, 5])
def test_the_inverse_by_halves_holds_where_neighbouring_keys_are_alike(C):
    """``L`` with 0.9 in a band of 8 under the diagonal — tokens whose
    keys are nearly one — is where the finite series ``sum (-L)^k`` loses
    every digit (5e8 at C 64); the inverse by halves stays at float32's
    rounding, at chunk lengths that are no power of two as well."""
    band = np.abs(np.arange(C)[:, None] - np.arange(C)[None, :]) <= 8
    L = np.tril(np.full((3, C, C), 0.9), -1) * band
    want = np.linalg.inv(np.eye(C) + L)
    got = np.asarray(linear_attention._unit_lower_inverse(
        jnp.asarray(L, jnp.float32)), np.float64)
    assert np.abs(got - want).max() < 2e-6 * np.abs(want).max()


def test_the_account_says_which_decay_and_the_shape_alone_chooses():
    (q, k, v, g, beta), seg, chunk = _inputs("tail")
    B, S, Hk, Hv, dk, dv, _, _ = CASES["tail"]
    with tracing.step_account("test/kda"):
        gated_delta_rule(q, k, v, g, beta, seg, chunk=chunk)
    assert tracing.delta_rule_chunks("test/kda") == [
        {"chunks": 3, "chunk_len": 32, "batch": B, "heads": Hv, "dk": dk,
         "dv": dv, "decay": "channel", "path": "xla"}]
    with tracing.step_account("test/kda"):
        gated_delta_rule(q, k, v, g[..., 0], beta, seg, chunk=chunk)
    (row,) = tracing.delta_rule_chunks("test/kda")
    assert row["decay"] == "head"
    # heads 8 wide are no shape of ops/pallas/kda.py's kernels: asked for
    # or not, the XLA form (tests/test_kda_kernels.py has the shapes they
    # take)
    with tracing.step_account("test/kda"):
        gated_delta_rule(q, k, v, g, beta, seg, chunk=chunk, interpret=True)
    (row,) = tracing.delta_rule_chunks("test/kda")
    assert (row["decay"], row["path"]) == ("channel", "xla")
