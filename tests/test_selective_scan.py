"""The selective scan of Mamba-1 (ops/selective_scan.py) at toy size on the
CPU: the XLA chunked form and the Mosaic kernels (interpret mode) against
the literal per-token recurrence — values and every gradient — with
document boundaries wherever they may fall."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.selective_scan import (selective_scan,
                                              selective_scan_recurrent)
from deepspeed_tpu.telemetry import tracing
from tests.util import kernel_names

B, S, D, N, CHUNK = 2, 256, 128, 16, 128
VALUE_TOL = 2e-5        # max |a - b| / max |b|; measured <= 1e-6
GRAD_TOL = 1e-4         # per argument; measured <= 5e-7
NAMES = ("u", "dt", "A_log", "B", "C", "D", "dt_bias")

#: document lengths of the two sequences (each sums to S)
LAYOUTS = {
    "one_document": [[S], [S]],
    "two_and_three_documents": [[100, 156], [60, 70, 126]],
    "ending_on_a_chunks_edge": [[128, 128], [28, 100, 128]],
    "one_token_at_an_edge": [[127, 1, 128], [128, 1, 127]],
}
#: the two lowerings, as ``interpret`` chooses them
FORMS = {"xla": False, "kernels": True}


def _inputs(seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    u = jax.random.normal(k[0], (B, S, D)).astype(dtype)
    dt = (jax.random.normal(k[1], (B, S, D)) - 2.0).astype(dtype)
    A_log = jnp.log(jnp.broadcast_to(
        jnp.arange(1, N + 1, dtype=jnp.float32), (D, N))) \
        + 0.1 * jax.random.normal(k[2], (D, N))
    Bm = jax.random.normal(k[3], (B, S, N)).astype(dtype)
    Cm = jax.random.normal(k[4], (B, S, N)).astype(dtype)
    skip = jax.random.normal(k[5], (D,))
    bias = jax.random.normal(k[6], (D,))
    return u, dt, A_log, Bm, Cm, skip, bias


def _segments(layout):
    return jnp.asarray(np.stack([
        np.repeat(np.arange(len(lengths)), lengths)
        for lengths in LAYOUTS[layout]]).astype(np.int32))


def _call(fn, seg, **kw):
    def call(u, dt, A_log, Bm, Cm, skip, bias):
        return fn(u, dt, -jnp.exp(A_log), Bm, Cm, skip, bias, seg, **kw)
    return call


def _close(got, want, tol):
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < tol, err


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_both_forms_are_the_recurrence(layout, form):
    args, seg = _inputs(), _segments(layout)
    _close(_call(selective_scan, seg, chunk=CHUNK,
                 interpret=FORMS[form])(*args),
           _call(selective_scan_recurrent, seg)(*args), VALUE_TOL)


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("layout", ["two_and_three_documents",
                                    "ending_on_a_chunks_edge"])
def test_every_gradient_is_the_recurrences(layout, form):
    args, seg = _inputs(1), _segments(layout)
    weight = jax.random.normal(jax.random.PRNGKey(9), (B, S, D))

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(weight * fn(*a)),
                        argnums=tuple(range(7)))(*args)

    got = grads(_call(selective_scan, seg, chunk=CHUNK,
                      interpret=FORMS[form]))
    want = grads(_call(selective_scan_recurrent, seg))
    for name, g, w in zip(NAMES, got, want):
        assert float(jnp.max(jnp.abs(w))) > 0, name
        _close(g, w, GRAD_TOL)


def test_a_documents_start_forgets_the_state():
    """What follows a boundary does not depend on what came before it, and
    no gradient crosses it."""
    args, seg = _inputs(2), _segments("two_and_three_documents")
    scan = _call(selective_scan, seg, chunk=CHUNK, interpret=True)
    changed = list(args)
    changed[0] = args[0].at[:, :100].set(0.0)
    a, b = scan(*args), scan(*changed)
    assert float(jnp.max(jnp.abs(a[0, 100:] - b[0, 100:]))) == 0.0
    assert float(jnp.max(jnp.abs(a[0, :100] - b[0, :100]))) > 0.0
    du = jax.grad(lambda u: jnp.sum(scan(u, *args[1:])[0, 100:]))(args[0])
    assert float(jnp.max(jnp.abs(du[0, :100]))) == 0.0


def test_y_is_returned_before_the_gate():
    """The op knows no gate: y is the recurrence's read plus the skip, so
    what a caller keeps for later layers is not what it gates."""
    u, dt, A_log, Bm, Cm, skip, bias = _inputs(3)
    y = selective_scan(u, dt, -jnp.exp(A_log), Bm, Cm, skip, bias,
                       interpret=True)
    bare = selective_scan(u, dt, -jnp.exp(A_log), Bm, Cm, None, bias,
                          interpret=True)
    _close(y - bare, skip * u, VALUE_TOL)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_bfloat16_operands_keep_a_float32_state(form):
    """Operands in bfloat16, the step, the decay, the state and the sums in
    float32: both forms round where the recurrence on the same rounded
    operands does — once, at y."""
    args = _inputs(4, jnp.bfloat16)
    seg = _segments("two_and_three_documents")
    got = _call(selective_scan, seg, interpret=FORMS[form])(*args)
    want = _call(selective_scan_recurrent, seg)(*args)
    assert got.dtype == jnp.bfloat16
    _close(got.astype(jnp.float32), want.astype(jnp.float32), 1e-2)


@pytest.mark.parametrize("chunk", [48, 100])
def test_a_chunk_that_does_not_divide_the_sequence(chunk):
    """The XLA form pads the tail with tokens of step 0 (the kernels take
    a chunk of 128 alone and are not chosen here)."""
    args, seg = _inputs(5), _segments("two_and_three_documents")
    _close(_call(selective_scan, seg, chunk=chunk)(*args),
           _call(selective_scan_recurrent, seg)(*args), VALUE_TOL)


def test_the_account_has_a_row_a_call():
    args = _inputs(6)
    with tracing.step_account("test/sscan"):
        for layer, interpret in ((0, True), (2, False)):
            _call(selective_scan, None, interpret=interpret,
                  layer=layer)(*args)
    kernel, xla = tracing.selective_scan_calls("test/sscan")
    assert kernel == {"batch": B, "positions": S, "channels": D, "state": N,
                      "chunk": CHUNK, "path": "kernel", "layer": 0,
                      "channels_per_step": 128, "chunks_per_step": 1}
    assert xla["path"] == "xla" and xla["layer"] == 2 \
        and "channels_per_step" not in xla
    assert tracing.selective_scan_calls("test/none") is None
    assert {"ds_sscan_fwd", "ds_sscan_bwd"} <= kernel_names(
        _call(selective_scan, None, interpret=True), *args)
    # the scopes the model puts the call under (models/phi4flash.py)
    assert (tracing.SCOPE_MAMBA, tracing.SCOPE_SCAN) == ("mamba", "scan")


def test_shapes_the_kernels_do_not_take_fall_back():
    """Channels that are not whole lane tiles: the XLA form, whatever
    ``interpret`` says."""
    u, dt, A_log, Bm, Cm, skip, bias = _inputs(7)
    cut = lambda t: t[..., :96]
    args = (cut(u), cut(dt), A_log[:96], Bm, Cm, skip[:96], bias[:96])
    with tracing.step_account("test/fallback"):
        got = _call(selective_scan, None, interpret=True)(*args)
    assert tracing.selective_scan_calls("test/fallback")[0]["path"] == "xla"
    _close(got, _call(selective_scan_recurrent, None)(*args), VALUE_TOL)
