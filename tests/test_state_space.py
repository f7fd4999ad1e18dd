"""The state-space scan (ops/state_space.py) at toy size on the CPU: the
chunked form against the literal per-token recurrence, values and every
gradient, with document boundaries wherever they may fall."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.state_space import ssd_recurrent, ssd_scan
from deepspeed_tpu.telemetry import tracing

B, S, H, P, G, N, CHUNK = 2, 48, 4, 8, 2, 16, 16
VALUE_TOL = 2e-5        # max |a - b| / max |b|; measured <= 2e-6
GRAD_TOL = 1e-4         # per argument; measured <= 2e-5

#: document lengths of the two sequences (each sums to S)
LAYOUTS = {
    "one_document": [[S], [S]],
    "inside_a_chunk": [[5, 43], [21, 6, 21]],
    "at_a_chunks_edge": [[16, 32], [32, 16]],
    "longer_than_a_chunk": [[3, 40, 5], [7, 41]],
    "one_token_at_an_edge": [[15, 1, 32], [16, 1, 31]],
    "every_token_its_own": [[1] * S, [1] * S],
}


def _inputs(seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (B, S, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    Bm = jax.random.normal(k[3], (B, S, G, N)).astype(dtype)
    Cm = jax.random.normal(k[4], (B, S, G, N)).astype(dtype)
    D = jax.random.normal(k[5], (H,))
    return x, dt, A, Bm, Cm, D


def _segments(layout):
    return jnp.asarray(np.stack([
        np.repeat(np.arange(len(lengths)), lengths)
        for lengths in LAYOUTS[layout]]).astype(np.int32))


def _close(got, want, tol):
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < tol, err


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_chunked_form_is_the_recurrence(layout):
    args, seg = _inputs(), _segments(layout)
    with jax.default_matmul_precision("highest"):
        _close(ssd_scan(*args, seg, chunk=CHUNK),
               ssd_recurrent(*args, seg), VALUE_TOL)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_every_gradient_is_the_recurrences(layout):
    args, seg = _inputs(1), _segments(layout)
    weight = jax.random.normal(jax.random.PRNGKey(9), (B, S, H, P))

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(weight * fn(*a)),
                        argnums=tuple(range(6)))(*args)

    with jax.default_matmul_precision("highest"):
        got = grads(lambda *a: ssd_scan(*a, seg, chunk=CHUNK))
        want = grads(lambda *a: ssd_recurrent(*a, seg))
    for name, g, w in zip(("x", "dt", "A", "B", "C", "D"), got, want):
        if layout == "every_token_its_own" and name == "A":
            # no state outlives a token: the decay plays no part
            assert float(jnp.max(jnp.abs(w))) == 0 \
                and float(jnp.max(jnp.abs(g))) == 0
            continue
        assert float(jnp.max(jnp.abs(w))) > 0, name
        _close(g, w, GRAD_TOL)


@pytest.mark.parametrize("chunk", [7, 16, 48, 128])
def test_any_chunk_length_gives_the_same(chunk):
    """A chunk that does not divide the sequence (the tail is padded with
    tokens of step 0), one that is the sequence, one longer than it."""
    args, seg = _inputs(2), _segments("inside_a_chunk")
    with jax.default_matmul_precision("highest"):
        _close(ssd_scan(*args, seg, chunk=chunk),
               ssd_recurrent(*args, seg), VALUE_TOL)


def test_without_segments_and_without_the_skip_term():
    x, dt, A, Bm, Cm, D = _inputs(3)
    with jax.default_matmul_precision("highest"):
        got = ssd_scan(x, dt, A, Bm, Cm, None, None, chunk=CHUNK)
        _close(got, ssd_recurrent(x, dt, A, Bm, Cm), VALUE_TOL)
        with_d = ssd_scan(x, dt, A, Bm, Cm, D, None, chunk=CHUNK)
    np.testing.assert_allclose(with_d - got, D[:, None] * x, atol=1e-5)


def test_a_document_sees_nothing_of_the_one_before():
    """The second document's outputs do not move when the first one's
    tokens do."""
    x, dt, A, Bm, Cm, D = _inputs(4)
    seg = _segments("inside_a_chunk")
    other = x.at[0, :5].set(x[0, :5] + 3.0)
    a = ssd_scan(x, dt, A, Bm, Cm, D, seg, chunk=CHUNK)
    b = ssd_scan(other, dt, A, Bm, Cm, D, seg, chunk=CHUNK)
    assert float(jnp.abs(a[0, :5] - b[0, :5]).max()) > 0.1
    np.testing.assert_array_equal(a[0, 5:], b[0, 5:])
    np.testing.assert_array_equal(a[1], b[1])


def test_bfloat16_operands_float32_state():
    """The model's dtype: products take bfloat16 operands, the decays and
    the state stay float32 — the result is bfloat16 and within bfloat16's
    rounding of the float32 recurrence."""
    args, seg = _inputs(5, jnp.bfloat16), _segments("longer_than_a_chunk")
    got = ssd_scan(*args, seg, chunk=CHUNK)
    assert got.dtype == jnp.bfloat16
    want = ssd_recurrent(*(a.astype(jnp.float32) for a in args), seg)
    _close(got.astype(jnp.float32), want, 3e-2)


def test_the_call_leaves_its_row_in_the_steps_account():
    args, seg = _inputs(), _segments("one_document")
    with tracing.step_account("test/ssd"):
        jax.eval_shape(lambda *a: ssd_scan(*a, seg, chunk=CHUNK), *args)
    assert tracing.ssd_chunks("test/ssd") == [
        {"chunks": S // CHUNK, "chunk_len": CHUNK, "batch": B, "heads": H,
         "groups": G, "head_dim": P, "state": N, "path": "xla"}]
    assert tracing.ssd_chunks("test/none") is None
