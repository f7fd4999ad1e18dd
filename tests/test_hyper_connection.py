"""ops/hyper_connection.py on the CPU, float32, seeded: the coefficients,
the read and the write against the equations spelled out token by token in
numpy; what the Sinkhorn sweeps reach after 20 and after 1; the clamp and
``hc_eps`` each on a case built to show them; the start; the dtypes; the
step's account."""
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.hyper_connection import (HyperConnection, exit_sum,
                                                hc_coefficients, hc_read,
                                                hc_write, init_hc_params,
                                                replicate, sinkhorn)
from deepspeed_tpu.telemetry import tracing
from tests.util import scope_parts

HC = HyperConnection()
T, C = 6, 16


def drawn(hc=HC, seed=0, width=C):
    """Leaves at order 1 (projections of order 1 too): ``H_res`` far from
    the identity and from the uniform matrix."""
    n = hc.streams
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 6))
    norm = jax.random.normal
    return {"phi": norm(next(k), (n * width, hc.columns))
            / np.sqrt(n * width),
            "alpha": norm(next(k), (3,)), "b_pre": norm(next(k), (n,)),
            "b_post": norm(next(k), (n,)), "b_res": norm(next(k), (n, n))}


def stream(seed=1, n=HC.streams, tokens=T, width=C):
    """[tokens, n C]: the n streams side by side."""
    return jax.random.normal(jax.random.PRNGKey(seed), (tokens, n * width))


def by_hand(x, p, hc):
    """The docstring's equations, one token at a time."""
    n = hc.streams
    x, p = np.asarray(x, np.float64).reshape(len(x), n, -1), jax.tree.map(
        lambda a: np.asarray(a, np.float64), p)
    sig = lambda a: 1.0 / (1.0 + np.exp(-a))
    pre, post, res = [], [], []
    for X in x:
        v = X.reshape(-1)
        z = (v / np.sqrt(np.mean(v * v) + hc.norm_eps)) @ p["phi"]
        pre.append(sig(p["alpha"][0] * z[:n] + p["b_pre"]))
        post.append(2 * sig(p["alpha"][1] * z[n:2 * n] + p["b_post"]))
        M = np.exp(np.clip(p["alpha"][2] * z[2 * n:].reshape(n, n)
                           + p["b_res"], hc.clamp_min, hc.clamp_max))
        for _ in range(hc.sweeps):
            for j in range(n):
                M[:, j] = M[:, j] / (M[:, j].sum() + hc.sinkhorn_eps)
            for i in range(n):
                M[i, :] = M[i, :] / (M[i, :].sum() + hc.sinkhorn_eps)
        res.append(M)
    return np.stack(pre), np.stack(post), np.stack(res)


def test_coefficients_read_and_write_are_the_equations():
    x, p = stream(), drawn()
    pre, post, res = hc_coefficients(x, p, HC)
    want_pre, want_post, want_res = by_hand(x, p, HC)
    np.testing.assert_allclose(pre, want_pre, atol=2e-6)
    np.testing.assert_allclose(post, want_post, atol=4e-6)
    np.testing.assert_allclose(res, want_res, atol=2e-6)
    # far from the identity and from the uniform matrix
    assert np.abs(want_res - np.eye(4)).max() > 0.3
    assert np.abs(want_res - 0.25).max() > 0.3
    y = jax.random.normal(jax.random.PRNGKey(2), (T, C))
    xs = np.asarray(x, np.float64).reshape(T, 4, C)
    np.testing.assert_allclose(
        hc_read(x, pre), np.einsum("ti,tic->tc", want_pre, xs), atol=1e-5)
    np.testing.assert_allclose(
        hc_write(x, y, post, res).reshape(T, 4, C),
        np.einsum("tij,tjc->tic", want_res, xs)
        + want_post[:, :, None] * np.asarray(y, np.float64)[:, None, :],
        atol=1e-5)
    np.testing.assert_allclose(exit_sum(x, 4), xs.sum(1), atol=1e-5)
    np.testing.assert_array_equal(replicate(y, 4).reshape(T, 4, C),
                                  np.broadcast_to(y[:, None], (T, 4, C)))


def test_the_lead_dimensions_are_the_callers():
    x, p = stream(tokens=8), drawn()
    flat = hc_coefficients(x, p, HC)
    shaped = hc_coefficients(x.reshape(2, 4, 4 * C), p, HC)
    for a, b, tail in zip(flat, shaped, ((4,), (4,), (4, 4))):
        assert b.shape == (2, 4) + tail
        np.testing.assert_array_equal(a.reshape(b.shape), b)
    assert hc_read(x.reshape(2, 4, 4 * C), shaped[0]).shape == (2, 4, C)


def test_twenty_sweeps_reach_a_doubly_stochastic_matrix_and_one_does_not():
    x, p = stream(tokens=64), drawn()
    _, _, res = hc_coefficients(x, p, HC)
    assert np.abs(np.asarray(res).sum(-1) - 1).max() < 1e-4
    assert np.abs(np.asarray(res).sum(-2) - 1).max() < 1e-4
    _, _, once = hc_coefficients(x, p, replace(HC, sweeps=1))
    # rows are normalised last, so it is the columns that show
    assert np.abs(np.asarray(once).sum(-2) - 1).max() > 0.05
    assert float(jnp.min(res)) > 0


def test_columns_go_before_rows():
    m = jnp.asarray([[1.0, 2.0], [3.0, 4.0]])
    got = sinkhorn(m, 1, 0.0)
    cols = np.asarray(m) / np.asarray(m).sum(0, keepdims=True)
    np.testing.assert_allclose(got, cols / cols.sum(1, keepdims=True),
                               rtol=1e-6)
    rows_first = np.asarray(m) / np.asarray(m).sum(1, keepdims=True)
    rows_first = rows_first / rows_first.sum(0, keepdims=True)
    assert np.abs(np.asarray(got) - rows_first).max() > 1e-2


@pytest.mark.parametrize("what", ["clamp", "hc_eps"])
def test_a_case_built_to_show_it(what):
    x = stream()
    if what == "clamp":
        # entries of +-5 where the clamp is at +-3: the matrix is another,
        # and no gradient reaches b_res through a clamped entry
        p = {**drawn(), "alpha": jnp.asarray([1.0, 1.0, 0.0]),
             "b_res": 5.0 * (2 * jnp.eye(4) - 1)}
        tight = replace(HC, clamp_min=-3.0, clamp_max=3.0)
        there, gone = (hc_coefficients(x, p, hc)[2] for hc in (tight, HC))
        assert float(jnp.abs(there - gone).max()) > 1e-3
        entry = lambda hc: jax.grad(lambda b: hc_coefficients(
            x, {**p, "b_res": b}, hc)[2][0, 0, 1])(p["b_res"])
        assert float(jnp.abs(entry(tight)).max()) == 0
        assert float(jnp.abs(entry(HC)).max()) > 1e-6
        # the published clamp keeps exp() finite where the sum would not be
        wide = {**p, "b_res": 100.0 * (2 * jnp.eye(4) - 1)}
        assert bool(jnp.isfinite(hc_coefficients(x, wide, HC)[2]).all())
        assert not bool(jnp.isfinite(hc_coefficients(x, wide, replace(
            HC, clamp_min=-1e9, clamp_max=1e9))[2]).all())
    else:
        # one column so small that its sum is under hc_eps: the first
        # sweep leaves it small where a bare division makes it a column
        # like the others (later sweeps, whose sums are near 1, wash the
        # difference out to hc_eps' own size: the sweeps are cut to 1)
        once = replace(HC, sweeps=1)
        p = {**drawn(), "alpha": jnp.zeros(3),
             "b_res": jnp.zeros((4, 4)).at[:, 0].set(-20.0)}
        with_eps = hc_coefficients(x, p, once)[2]
        without = hc_coefficients(x, p, replace(once, sinkhorn_eps=0.0))[2]
        np.testing.assert_allclose(without, 0.25, atol=1e-6)
        assert float(with_eps[0, 0, 0]) < 0.01
        full = hc_coefficients(x, p, HC)[2]
        assert 0 < float(jnp.abs(full - 0.25).max()) < 1e-5


def test_gradients_pass_through_the_sweeps():
    """Autodiff through 20 sweeps against central differences, for the
    leaves the sweeps alone reach."""
    x, p = stream(tokens=3), drawn()
    y = jax.random.normal(jax.random.PRNGKey(3), (3, C))
    weight = jax.random.normal(jax.random.PRNGKey(4), (3, 4 * C))

    def f(p):
        pre, post, res = hc_coefficients(x, p, HC)
        return jnp.sum(hc_write(x, y * hc_read(x, pre), post, res) * weight)

    # float32 inside whatever comes in, so the step is a coarse one
    grads, h = jax.grad(f)(p), 4e-3
    for name in ("b_res", "alpha", "b_pre", "b_post"):
        for idx in np.ndindex(p[name].shape):
            step = jnp.zeros_like(p[name]).at[idx].set(h)
            numeric = (f({**p, name: p[name] + step})
                       - f({**p, name: p[name] - step})) / (2 * h)
            assert float(grads[name][idx]) == pytest.approx(
                float(numeric), rel=3e-2, abs=3e-3), (name, idx)
    assert float(jnp.abs(grads["b_res"]).max()) > 0.1
    assert float(jnp.abs(grads["phi"]).max()) > 0


def test_the_hand_written_backward_is_autodiffs_of_the_plain_form():
    x, p = stream(), drawn()
    pre, post, res = hc_coefficients(x, p, HC)
    y = jax.random.normal(jax.random.PRNGKey(3), (T, C))
    w_read = jax.random.normal(jax.random.PRNGKey(4), (T, C))
    w_write = jax.random.normal(jax.random.PRNGKey(5), (T, 4 * C))

    def plain_read(x, pre):
        return jnp.einsum("ti,tic->tc", pre, x.reshape(T, 4, C))

    def plain_write(x, y, post, res):
        return (jnp.einsum("tij,tjc->tic", res, x.reshape(T, 4, C))
                + post[:, :, None] * y[:, None, :]).reshape(T, 4 * C)

    got = jax.grad(lambda *a: jnp.sum(hc_read(*a) * w_read), (0, 1))(x, pre)
    want = jax.grad(lambda *a: jnp.sum(plain_read(*a) * w_read), (0, 1))(
        x, pre)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)
    args = (x, y, post, res)
    got = jax.grad(lambda *a: jnp.sum(hc_write(*a) * w_write),
                   (0, 1, 2, 3))(*args)
    want = jax.grad(lambda *a: jnp.sum(plain_write(*a) * w_write),
                    (0, 1, 2, 3))(*args)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("n", [1, 2, 4])
def test_the_start_is_the_plain_residual(n):
    hc = HyperConnection(streams=n)
    p = init_hc_params(hc, C, jax.random.PRNGKey(0),
                       **({"alpha": 0.0} if n == 1 else {}))
    assert p["phi"].shape == (n * C, 2 * n + n * n)
    assert sum(a.size for a in jax.tree.leaves(p)) \
        == n * C * hc.columns + hc.columns + 3
    one = jax.random.normal(jax.random.PRNGKey(1), (T, C))
    x = replicate(one, n)
    pre, post, res = hc_coefficients(x, p, hc)
    np.testing.assert_allclose(pre.sum(-1), 1.0, atol=2e-2)
    np.testing.assert_allclose(post, 1.0, atol=2e-2)
    assert float(jnp.abs(res - jnp.eye(n)).max()) < 0.05
    y = jax.random.normal(jax.random.PRNGKey(2), (T, C))
    # every stream is x + y to within the start's distance
    np.testing.assert_allclose(hc_write(x, y, post, res),
                               x + replicate(y, n), atol=0.1)
    np.testing.assert_allclose(hc_read(x, pre), one, atol=0.1)
    if n == 1:
        for coefficient in (pre, post, res):
            np.testing.assert_allclose(coefficient, 1.0, atol=1e-6)


def test_a_bfloat16_stream_stays_bfloat16_and_the_rest_is_float32():
    x = stream().astype(jnp.bfloat16)
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), drawn())
    pre, post, res = hc_coefficients(x, p, HC)
    assert {a.dtype for a in (pre, post, res)} == {jnp.dtype(jnp.float32)}
    y = jnp.ones((T, C), jnp.bfloat16)
    assert hc_read(x, pre).dtype == hc_write(x, y, post, res).dtype \
        == jnp.bfloat16
    # the same numbers as float32 arithmetic on the rounded operands
    exact = hc_coefficients(x.astype(jnp.float32), jax.tree.map(
        lambda a: a.astype(jnp.float32), p), HC)
    for a, b in zip((pre, post, res), exact):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_each_call_site_leaves_a_row_in_the_steps_account():
    tracing.reset_programs()
    x, p = stream(), drawn()
    with tracing.step_account("probe"):
        jax.eval_shape(lambda x: hc_coefficients(
            x, p, HC, at="blocks/attn", calls=4), x)
        jax.eval_shape(lambda x: hc_coefficients(
            x, p, HC, at="mtp/attn"), x)
    rows = tracing.hc_calls("probe")
    assert [(r["site"], r["calls_per_pass"]) for r in rows] \
        == [("blocks/attn", 4), ("mtp/attn", 1)]
    assert rows[0] == {"site": "blocks/attn", "tokens": T, "streams": 4,
                       "width": C, "calls_per_pass": 4}
    assert tracing.hc_calls("another") is None
    tracing.reset_programs()


def test_the_scopes_are_the_ones_the_readers_key_on():
    x, p = stream(), drawn()
    y = jnp.ones((T, C))

    def f(x):
        pre, post, res = hc_coefficients(x, p, HC)
        return hc_write(x, y * hc_read(x, pre), post, res)

    text = jax.jit(f).lower(x).as_text(debug_info=True)
    for scope in ("hc/coeff", "hc/read", "hc/write"):
        assert scope in text, scope
    assert {"hc", "coeff", "read", "write"} <= scope_parts(
        re.findall(r'"([^"]*\bhc/[^"]*)"', text))
