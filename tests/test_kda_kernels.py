"""ops/pallas/kda.py — ``ds_kda_fwd`` / ``ds_kda_bwd``, the delta rule with
a decay a key channel as Mosaic kernels, run in interpret mode at ``dk`` =
``dv`` = 128 against the literal per-token recurrence and against the XLA
chunked form (``_chunked_xla_channel``): output and the gradient in all
five arguments — with packed documents that end inside a chunk, at its edge
and after one token, a tail that does not fill a chunk, chunks of 64, 32
and 16, and decays of -20 a token on some channels beside 0 on others; the
l2-norm on the tiles; what bf16 operands keep in float32; the shapes the
kernels refuse; the account's row; the blocking as a rule of shapes."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.linear_attention import (
    gated_delta_rule, gated_delta_rule_recurrent, l2norm)
from deepspeed_tpu.ops.pallas import kda, vmem
from deepspeed_tpu.telemetry import tracing

ARGUMENTS = ("q", "k", "v", "g", "beta")
D = 128
#: name: (B, S, H, chunk, decays)
CASES = {
    # three chunks of 64 and a tail of 22 — four chunks a grid step, their
    # inverses two at a time; two heads, two sequences
    "tail": (2, 214, 2, 64, "mild"),
    # chunks of 32: two levels of products above the sub-blocks of 8; three
    # chunks, one a grid step
    "chunks_of_32": (1, 70, 1, 32, "mild"),
    # -20 a token on every third channel, 0 on the next; four chunks of 16
    "strong": (1, 64, 2, 16, "strong"),
}


def _inputs(name, raw=False):
    B, S, H, chunk, decays = CASES[name]
    rng = np.random.default_rng(sorted(CASES).index(name))
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k, v = f(B, S, H, D), f(B, S, H, D), f(B, S, H, D)
    if not raw:
        q, k = l2norm(q) / np.sqrt(D), l2norm(k)
    # as the layer makes them: -exp(A_log) * softplus(.), A to 16
    g = -jnp.asarray(rng.uniform(0, 4, size=(B, S, H, D)) ** 2 / 16,
                     jnp.float32)
    if decays == "strong":
        channel = jnp.arange(D) % 3
        g = jnp.where(channel == 0, -20.0, jnp.where(channel == 1, 0.0, g))
    beta = jnp.asarray(rng.uniform(0, 1, size=(B, S, H)), jnp.float32)
    # row 0: a document ends inside the first chunk (5), one at the edge of
    # a chunk of 16, 32 or 64 and a one-token document behind it
    seg = np.zeros((B, S), np.int32)
    edge = 64 if S > 64 else 32
    seg[0, 5:] = 1
    seg[0, edge:] = 2
    seg[0, edge + 1:] = 3
    seg[-1, 21:] += 4
    return (q, k, v, g, beta), jnp.asarray(seg), chunk


def _value_and_grads(fn, args):
    weights = jnp.asarray(np.random.default_rng(7).normal(
        size=args[2].shape), jnp.float32)

    def loss(*a):
        o = fn(*a)
        return jnp.sum(o.astype(jnp.float32) * weights), o
    (_, o), grads = jax.value_and_grad(loss, argnums=range(5),
                                       has_aux=True)(*args)
    return o, grads


@functools.lru_cache(maxsize=None)
def _three(name):
    """(output, the five gradients) of the kernels interpreted, of the XLA
    chunked form and of the recurrence, once a case."""
    args, seg, chunk = _inputs(name)
    with jax.default_matmul_precision("highest"):
        return tuple(
            _value_and_grads(fn, args) for fn in (
                lambda *a: gated_delta_rule(*a, seg, chunk=chunk,
                                            interpret=True),
                lambda *a: gated_delta_rule(*a, seg, chunk=chunk,
                                            interpret=False),
                lambda *a: gated_delta_rule_recurrent(*a, seg)))


@pytest.mark.parametrize("oracle", ["xla", "recurrence"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_the_output_is_the_oracles(name, oracle):
    (got, _), *oracles = _three(name)
    want, _ = oracles[oracle == "recurrence"]
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=5e-6)


@pytest.mark.parametrize("oracle", ["xla", "recurrence"])
@pytest.mark.parametrize("argument", ARGUMENTS)
@pytest.mark.parametrize("name", sorted(CASES))
def test_the_gradient_is_the_oracles(name, argument, oracle):
    (_, got), *oracles = _three(name)
    _, want = oracles[oracle == "recurrence"]
    i = ARGUMENTS.index(argument)
    assert bool(jnp.isfinite(got[i]).all())
    np.testing.assert_allclose(
        got[i], want[i], atol=1e-5 * float(jnp.abs(want[i]).max()))


def test_strong_decays_are_felt():
    """What the 'strong' case is for: the output differs from the mean
    decay's by far more than the tolerance."""
    (q, k, v, g, beta), seg, chunk = _inputs("strong")
    (got, _), _, _ = _three("strong")
    mean = gated_delta_rule(q, k, v, g.mean(-1), beta, seg, chunk=chunk)
    assert float(jnp.abs(got - mean).max()) > 1e-2


def test_no_exponential_of_a_positive_number(monkeypatch):
    """Every ``exp`` of the kernels has an argument that is <= 0 whatever
    the inputs: checked on the values, with decays of -20."""
    args, seg, chunk = _inputs("strong")
    seen = []
    real = jnp.exp

    def watched(x):
        jax.debug.callback(lambda m: seen.append(float(m)), jnp.max(x))
        return real(x)

    monkeypatch.setattr(kda.jnp, "exp", watched)
    # chunks of 32, so that the levels above the sub-blocks run too
    o, grads = _value_and_grads(
        lambda *a: gated_delta_rule(*a, seg, chunk=32, interpret=True), args)
    jax.block_until_ready((o, grads))
    jax.effects_barrier()
    assert len(seen) >= 20 and max(seen) <= 0.0, seen


def test_the_tiles_are_l2_normalised_in_the_kernels():
    """With ``l2norm_scales`` q and k come as the layer made them: the
    kernels normalise, and differentiate the normalisation."""
    args, seg, chunk = _inputs("chunks_of_32", raw=True)
    scales = (D ** -0.5, 1.0)
    with jax.default_matmul_precision("highest"):
        got, want = (
            _value_and_grads(
                lambda *a: gated_delta_rule(*a, seg, chunk=chunk,
                                            interpret=interpret,
                                            l2norm_scales=scales), args)
            for interpret in (True, False))
    np.testing.assert_allclose(got[0], want[0], atol=5e-6)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, atol=1e-5 * float(jnp.abs(w).max()))


def test_bf16_operands_keep_a_float32_state_and_what_the_forward_saves():
    (q, k, v, g, beta), seg, chunk = _inputs("tail")
    bf16 = lambda a: a.astype(jnp.bfloat16)
    got, grads = _value_and_grads(
        lambda *a: gated_delta_rule(*a, seg, chunk=chunk, interpret=True),
        (bf16(q), bf16(k), bf16(v), g, beta))
    want, wants = _three("tail")[2]
    assert got.dtype == jnp.bfloat16
    assert [x.dtype for x in grads] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=0.05)
    for x, w in zip(grads, wants):
        assert float(jnp.linalg.norm(x.astype(jnp.float32) - w)
                     / jnp.linalg.norm(w)) < 0.02
    # the forward rule's residuals: the incoming states float32, T bf16
    B, S, H = q.shape[:3]
    pad = lambda a: jnp.pad(a, ((0, 0), (0, 256 - S))
                            + ((0, 0),) * (a.ndim - 2))
    blocking = kda.chunks_per_step(4, 64, 1, D, D, 2)
    _, (*_, s_in, t) = kda._kda_fwd(
        *(pad(a) for a in (bf16(q), bf16(k), bf16(v), g, beta)),
        jnp.pad(seg, ((0, 0), (0, 256 - S)), mode="edge"), blocking, None,
        True)
    assert (s_in.dtype, s_in.shape) == (jnp.float32, (B, H, 4, D, D))
    assert (t.dtype, t.shape) == (jnp.bfloat16, (B, H, 4, 64, 64))
    assert float(jnp.abs(s_in[:, :, 0]).max()) == 0.0
    assert float(jnp.abs(s_in[:, :, 1]).max()) > 0.0


def test_the_account_says_kernel_and_a_refused_shape_falls_back():
    (q, k, v, g, beta), seg, chunk = _inputs("chunks_of_32")
    with tracing.step_account("test/kda"):
        gated_delta_rule(q, k, v, g, beta, seg, chunk=chunk, interpret=True)
    assert tracing.delta_rule_chunks("test/kda") == [
        {"chunks": 3, "chunk_len": 32, "batch": 1, "heads": 1, "dk": D,
         "dv": D, "decay": "channel", "path": "kernel", "heads_per_step": 1,
         "chunks_per_step": 1}]
    # on the CPU nothing is asked for: the XLA form
    with tracing.step_account("test/kda"):
        gated_delta_rule(q, k, v, g, beta, seg, chunk=chunk)
    (row,) = tracing.delta_rule_chunks("test/kda")
    assert (row["decay"], row["path"]) == ("channel", "xla")
    assert "chunks_per_step" not in row
    # heads 64 wide, and two value heads to a key head: refused, so the
    # XLA form even where the kernels are asked for
    for sl, heads in ((slice(0, 64), 1), (slice(None), 2)):
        vv = jnp.concatenate([v] * heads, axis=2)
        gg = jnp.concatenate([g] * heads, axis=2)[..., sl]
        bb = jnp.concatenate([beta] * heads, axis=2)
        with tracing.step_account("test/kda"):
            o = gated_delta_rule(q[..., sl], k[..., sl], vv, gg, bb, seg,
                                 chunk=chunk, interpret=True)
        (row,) = tracing.delta_rule_chunks("test/kda")
        assert (row["decay"], row["path"]) == ("channel", "xla")
        assert bool(jnp.isfinite(o).all())


@pytest.mark.parametrize("dk,dv,chunk,rep,takes", [
    (128, 128, 64, 1, True), (256, 128, 16, 1, True),
    (128, 128, 64, 2, False),       # a decay a channel shares nothing
    (64, 128, 64, 1, False), (128, 96, 64, 1, False),
    (128, 128, 48, 1, False), (128, 128, 8, 1, False)])
def test_the_shapes_the_kernels_take(dk, dv, chunk, rep, takes):
    assert kda.supported(dk, dv, chunk, rep) is takes


@pytest.mark.parametrize("n,itemsize,chunks", [
    (256, 2, 8), (256, 4, 8), (12, 2, 4), (6, 2, 2), (3, 2, 1)])
def test_the_blocking_is_a_rule_of_shapes(n, itemsize, chunks):
    """The cell's call (256 chunks of 64, bf16) walks eight chunks a grid
    step inside what a call is granted unasked; a count of chunks that
    eight does not divide takes the largest of 4, 2, 1 that does."""
    blocking = kda.chunks_per_step(n, 64, 1, D, D, itemsize)
    assert (blocking.chunk, blocking.chunks, blocking.heads) == (64, chunks, 1)
    assert blocking.vmem_bytes <= vmem.UNASKED
    assert vmem.limit_for(blocking.vmem_bytes) is None
    assert blocking == kda.chunks_per_step(n, 64, 1, D, D, itemsize)
