"""The selective scan of Mamba-1 (Gu & Dao 2023, "Mamba: Linear-Time
Sequence Modeling with Selective State Spaces", arXiv:2312.00752) — what a
Mamba-1 mixer (models/phi4flash.py) computes between its convolution and
its gate.

Per channel ``d`` of ``D`` and state ``n`` of ``N``, with a float32 state
``h`` [D, N], ``h_0 = 0``, a decay rate ``A[d, n] < 0`` and a step
``delta_t[d] = softplus(dt_t[d] + dt_bias[d]) > 0`` a token and channel:

    h_t = exp(delta_t A) . h_{t-1} + (delta_t u_t) B_t^T
    y_t = h_t C_t + D . u_t

``B_t``, ``C_t`` [N] are shared by all channels.  The decay is one number a
(token, channel, state): there is no matrix form (ops/state_space.py's
``ssd_scan`` is the case of one decay a head), so the work is elementwise
on the state — ``D N`` elements a token, about seven multiply-adds and one
``exp`` each.  ``y`` is returned **before the gate** (a caller multiplies
by ``silu(z)``; another keeps ``y`` as it is for later layers).

Every operand is read in the dtype it comes in; the step, the decay, the
state and every sum are float32, and ``y`` is rounded once, to ``u``'s
dtype.

**Packed documents.**  With ``segment_ids`` the state is zero at a
document's first token, taken as a mask (the decay there is exactly 0, and
no gradient passes).  Where ``chunk`` does not divide the sequence the tail
is padded with tokens of step 0.

One algorithm, two lowerings (``ops/pallas/vmem.lowering`` chooses).  On
one TPU, for channels of whole lane tiles and a state of whole sublane
tiles, the Mosaic kernels of ops/pallas/selective_scan.py: the state stays
in VMEM across a sequence's chunks and the backward is written by hand.
Elsewhere :func:`_chunked_xla`: a ``lax.scan`` over chunks that carries
the state, an associative scan inside a chunk, autodiff's backward over
rematerialised chunks.  :func:`selective_scan_recurrent` is the literal
per-token recurrence: the oracle both are tested against, and what a
decode step would run.

The model writes the ``jax.named_scope`` ``scan`` around the call; each
call leaves a row in the step's account
(``tracing.selective_scan_calls``).
"""
import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.telemetry.tracing import count_in_step

DEFAULT_CHUNK = 128
#: the raw step of a padded token: softplus of it is exactly 0 in float32
_PAD_DT = -1e4


def _first_tokens(segment_ids, b, S):
    """[b, S] bool: where a document (or the sequence) starts."""
    if segment_ids is None:
        return jnp.zeros((b, S), bool).at[:, 0].set(True)
    seg = segment_ids.astype(jnp.int32)
    return jnp.concatenate(
        [jnp.ones((b, 1), bool), seg[:, 1:] != seg[:, :-1]], axis=1)


def _step(dt, dt_bias):
    dt = dt.astype(jnp.float32)
    if dt_bias is not None:
        dt = dt + dt_bias.astype(jnp.float32)
    return jax.nn.softplus(dt)


def _kernel_blocking(interpret, channels, state, chunk, dtype):
    """(the grid blocking of ops/pallas/selective_scan.py's kernels, or
    None for the XLA chunked form below; interpret), by
    ``vmem.lowering``'s rule."""
    from deepspeed_tpu.ops.pallas import selective_scan as kernels, vmem
    return vmem.lowering(
        interpret, kernels.supported(channels, state, chunk),
        lambda: kernels.blocking(channels, state, chunk,
                                 jnp.dtype(dtype).itemsize))


def selective_scan(u, dt, A, B, C, D=None, dt_bias=None, segment_ids=None,
                   chunk: int = DEFAULT_CHUNK, interpret=None, layer=None):
    """The recurrence of the module docstring for every channel at once.

    ``u`` [b, S, D]; ``dt`` [b, S, D], the raw step (``softplus(dt +
    dt_bias)`` is taken here, in float32); ``A`` [D, N] (< 0); ``B``, ``C``
    [b, S, N]; ``D`` [D] or None (no skip term); ``dt_bias`` [D] or None;
    ``segment_ids`` [b, S] int or None.  Returns ``y`` [b, S, D] in ``u``'s
    dtype, before any gate.  Differentiable in ``u``, ``dt``, ``A``, ``B``,
    ``C``, ``D`` and ``dt_bias``.  ``interpret``: None chooses the lowering
    (:func:`_kernel_blocking`), True runs the kernels in interpret mode,
    False the XLA form.  ``layer``: the caller's layer index, for the
    account's row only."""
    b, S, Dc = u.shape
    N = A.shape[1]
    Cn = min(int(chunk), S)
    n = -(-S // Cn)
    pad = n * Cn - S
    blocking, interpret = _kernel_blocking(interpret, Dc, N, Cn, u.dtype)
    first = _first_tokens(segment_ids, b, S)
    A = A.astype(jnp.float32)
    if pad:
        # tokens of step 0: they decay nothing and write nothing
        tail = lambda t, value=0: jnp.pad(
            t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2),
            constant_values=value)
        u, B, C, first = tail(u), tail(B), tail(C), tail(first)
        dt = tail(dt, _PAD_DT)
    row = {"batch": b, "positions": n * Cn, "channels": Dc, "state": N,
           "chunk": Cn, "path": "xla" if blocking is None else "kernel"}
    if layer is not None:
        row["layer"] = layer
    if blocking is not None:
        from deepspeed_tpu.ops.pallas.selective_scan import sscan_kernels
        row.update(channels_per_step=blocking.channels,
                   chunks_per_step=blocking.chunks)
        y = sscan_kernels(u, dt, A, B, C, D, dt_bias, first, blocking,
                          interpret)
    else:
        y = _chunked_xla(u, _step(dt, dt_bias), A, B, C, D, first, n, Cn)
    key = f"{b}x{n * Cn}x{Dc}x{N}"
    count_in_step(selective_scan_calls={
        key if layer is None else f"{key}@{layer:03d}": row})
    return y[:, :S]


def _chunked_xla(u, delta, A, B, C, D, first, n, Cn):
    """The chunked form in plain XLA: the fallback and, beside
    :func:`selective_scan_recurrent`, the kernels' oracle.  Arguments as
    :func:`selective_scan` prepared them (``n`` chunks of ``Cn`` tokens,
    ``delta`` the float32 step, ``first`` where a document starts);
    returns ``y`` [b, n * Cn, D] in ``u``'s dtype."""
    b, _, Dc = u.shape
    f32 = lambda t: t.astype(jnp.float32)
    chunks = lambda t: jnp.moveaxis(
        t.reshape((b, n, Cn) + t.shape[2:]), 1, 0)          # [n, b, Cn, ..]

    def combine(earlier, later):
        # h -> a h + x, twice
        (a1, x1), (a2, x2) = earlier, later
        return a1 * a2, a2 * x1 + x2

    @jax.checkpoint
    def one_chunk(h, xs):
        u_c, delta_c, B_c, C_c, first_c = xs
        decay = jnp.where(first_c[..., None, None], 0.0,
                          jnp.exp(delta_c[..., None] * A))   # [b, Cn, D, N]
        write = (delta_c * f32(u_c))[..., None] * f32(B_c)[:, :, None, :]
        kept, local = lax.associative_scan(combine, (decay, write), axis=1)
        states = local + kept * h[:, None]
        return states[:, -1], jnp.sum(
            states * f32(C_c)[:, :, None, :], axis=-1)       # [b, Cn, D]

    _, y = lax.scan(one_chunk, jnp.zeros((b, Dc, A.shape[1]), jnp.float32),
                    tuple(chunks(t) for t in (u, delta, B, C, first)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, n * Cn, Dc)
    if D is not None:
        y = y + f32(D) * f32(u)
    return y.astype(u.dtype)


def selective_scan_recurrent(u, dt, A, B, C, D=None, dt_bias=None,
                             segment_ids=None):
    """The same by the literal per-token recurrence (a ``lax.scan`` over
    tokens), in float32.  Same arguments and result."""
    b, S, Dc = u.shape
    f32 = lambda t: t.astype(jnp.float32)
    first = _first_tokens(segment_ids, b, S)
    delta = _step(dt, dt_bias)
    A = f32(A)

    def token(h, xs):
        u_t, delta_t, B_t, C_t, first_t = xs
        decay = jnp.where(first_t[:, None, None], 0.0,
                          jnp.exp(delta_t[..., None] * A))   # [b, D, N]
        h = decay * h + (delta_t * u_t)[..., None] * B_t[:, None, :]
        return h, jnp.sum(h * C_t[:, None, :], axis=-1)

    by_token = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = lax.scan(token, jnp.zeros((b, Dc, A.shape[1]), jnp.float32),
                    (by_token(f32(u)), by_token(delta), by_token(f32(B)),
                     by_token(f32(C)), by_token(first)))
    y = jnp.moveaxis(y, 0, 1)
    if D is not None:
        y = y + f32(D) * f32(u)
    return y.astype(u.dtype)
