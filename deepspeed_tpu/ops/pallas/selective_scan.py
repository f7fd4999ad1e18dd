"""The selective scan (Mamba-1) of ops/selective_scan.py as two Mosaic
kernels under one ``jax.custom_vjp``: ``ds_sscan_fwd`` and ``ds_sscan_bwd``.

The same recurrence and the same roundings as the XLA form (its docstring
is the contract: operands read in their own dtype, the step, the decay,
the state and every sum float32, ``y`` rounded once), lowered so that

* the state of a block of channels is ``[N, channels]`` float32 — states
  down sublanes, channels along lanes, every lane at work — and lives in
  VMEM scratch across the chunks of one sequence: the grid is (batch,
  chunk, block of channels), the last two walked in order, and a scratch
  ``[blocks, N, channels]`` holds every block's state;
* it is vector-unit work.  A token multiplies the state by ``exp(delta_t
  A)``, adds ``(delta_t u_t) B_t^T`` and sums ``h C_t`` down the sublanes:
  ``u_t`` and ``delta_t`` are rows that broadcast down sublanes for
  nothing; ``B_t`` and ``C_t`` have to lie down sublanes and be the same in
  every lane, and getting 32 numbers a token there is the one step that is
  not elementwise.  It is done once a chunk, for all its tokens and all
  blocks of channels, on the matrix unit, which has nothing else to do:
  the chunk's ``[B | C]^T`` rows (positions along lanes, as the caller
  packs them) are masked to one token a copy and multiplied by a matrix of
  ones — exact, one term a sum — into ``[tokens * 2 N, 128]``, from which
  a token reads its two tiles by an aligned slice.  Where a document
  starts travels the same way and becomes a step so large that the decay
  is exactly 0;
* the backward is written by hand.  The forward rule saves the state that
  enters each chunk (float32); the backward walks the chunks in reverse
  with ``dL/dh`` in VMEM scratch, re-walks its chunk forward keeping every
  token's state in VMEM, and then walks it back.  ``dB_t`` and ``dC_t``
  are sums over all channels: a token's products are folded to one lane
  tile, added up over the blocks of channels in scratch, and the last
  block sums the lanes on the matrix unit.  ``dA``, ``dD`` and
  ``d(dt_bias)`` are sums over tokens, kept in float32 in the (resident)
  output block; the batch is summed outside.

One grid step takes one chunk of ``CHUNK`` tokens and :func:`blocking`'s
channels.  The skip term ``D u`` is added before ``y`` is rounded.
"""
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import vmem
from deepspeed_tpu.ops.pallas.gated_delta_rule import _NT, _dot

LANES = 128
CHUNK = 128
GROUP = 8                 # tokens between two aligned reads of the rows
_f32 = jnp.float32
#: the step of a document's first token inside the decay's exponent:
#: ``exp(_RESET * A)`` is exactly 0 for any ``A < 0`` a float32 holds
_RESET = 1e30
#: rows of the packed vectors: dt_bias, D
VEC_ROWS = 8


class Blocking(NamedTuple):
    chunk: int           # tokens of one chunk
    chunks: int          # chunks one grid step walks
    channels: int        # channels one grid step takes
    vmem_bytes: int      # the buffers the backward call names


def supported(channels, state, chunk) -> bool:
    """Shapes the kernels take: channels of whole lane tiles, a state of
    whole sublane tiles of either dtype, a chunk of one lane tile."""
    return channels % LANES == 0 and state % 16 == 0 and chunk == CHUNK


def working_set(channels, state, itemsize) -> int:
    """Bytes of the double-buffered blocks and the scratch of a backward
    grid step (the larger set) that takes ``channels`` channels."""
    T, N = CHUNK, state
    tile = T * channels
    blocks = 3 * tile * itemsize + 2 * tile * itemsize       # u dt dy; du ddt
    blocks += N * channels * 4 * 2                           # A, s_in
    blocks += VEC_ROWS * channels * 4 + 8 * T * 2 * N * 4    # vec; dBC row
    scratch = (T + 1) * N * channels * 4                     # every state
    scratch += T * 2 * N * LANES * (itemsize + 4)            # tiles, partials
    scratch += 6 * tile * 4 + T * LANES * 4                  # float32 rows
    return 2 * blocks + scratch


def blocking(channels, state, chunk, itemsize) -> Blocking:
    """The channels one grid step takes: the most (of 512, 256, 128
    dividing ``channels``) — more lanes a token amortise its reads of
    ``B_t`` and ``C_t``; past 512 the state and its decay no longer stay in
    registers."""
    per = next(c for c in (512, 256, 128) if channels % c == 0)
    return Blocking(chunk, 1, per, working_set(per, state, itemsize))


def _compiler_params(b: Blocking):
    limit = vmem.limit_for(b.vmem_bytes)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        **({"vmem_limit_bytes": limit} if limit else {}))


# ------------------------------------------------------------ shared maths
def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _spread(rows_ref, x_ref, first_ref, N):
    """Once a chunk: every token's ``[B_t | C_t]`` down sublanes and the
    same in all lanes, ``x_ref`` [T * 2 N, 128]; and where a document
    starts, a column the same in all lanes, ``first_ref`` [T, 128]."""
    rows = rows_ref[0]                                       # [R, T]
    T = rows.shape[1]
    dt = rows.dtype
    ones = jnp.ones((T, LANES), dt)
    bc = rows[:2 * N].astype(_f32)
    token = lax.broadcasted_iota(jnp.int32, (T, 2 * N, T), 0)
    lane = lax.broadcasted_iota(jnp.int32, (T, 2 * N, T), 2)
    # copy t keeps lane t alone: times ones, one term a sum
    one_token = jnp.where(token == lane, bc[None], 0.0).astype(dt)
    x_ref[...] = _dot(one_token.reshape(T * 2 * N, T), ones).astype(
        x_ref.dtype)
    eye = (lax.broadcasted_iota(jnp.int32, (T, T), 0)
           == lax.broadcasted_iota(jnp.int32, (T, T), 1))
    start = jnp.where(eye, rows[2 * N:2 * N + 1].astype(_f32), 0.0)
    first_ref[...] = _dot(start.astype(dt), ones)


def _lanes(tile, channels):
    """A [., 128] tile, the same in all lanes, over ``channels`` lanes."""
    reps = channels // LANES
    return tile if reps == 1 else jnp.concatenate([tile] * reps, axis=1)


def _fold(x):
    """[N, channels] -> [N, 128]: the lane tiles added up."""
    out = x[:, :LANES]
    for k in range(1, x.shape[1] // LANES):
        out = out + x[:, k * LANES:(k + 1) * LANES]
    return out


def _tiles(x_ref, token, N, channels):
    """Token ``token``'s ``B_t`` and ``C_t`` as [N, channels] float32."""
    at = pl.multiple_of(token * 2 * N, 2 * N)
    return (_lanes(x_ref[pl.ds(at, N), :].astype(_f32), channels),
            _lanes(x_ref[pl.ds(at + N, N), :].astype(_f32), channels))


def _rows_of(values):
    """Eight [1, channels] rows -> [8, channels]."""
    sub = lax.broadcasted_iota(jnp.int32, (GROUP, values[0].shape[1]), 0)
    out = jnp.zeros(sub.shape, _f32)
    for r, v in enumerate(values):
        out = jnp.where(sub == r, v, out)
    return out


def _steps(u_ref, dt_ref, vec_ref, first_ref, channels):
    """The chunk's float32 tiles [T, channels]: ``u``, the raw step with
    its bias, ``delta``, and the step as the decay's exponent takes it
    (``_RESET`` where a document starts)."""
    u = u_ref[0].astype(_f32)
    raw = dt_ref[0].astype(_f32) + vec_ref[0:1, :]
    delta = _softplus(raw)
    starts = _lanes(first_ref[...], channels) > 0.5
    return u, raw, delta, jnp.where(starts, _RESET, delta)


# --------------------------------------------------------------- forward
def _fwd_kernel(u_ref, dt_ref, rows_ref, a_ref, vec_ref, y_ref, *rest, N,
                save):
    if save:
        sin_ref, h_ref, x_ref, first_ref, e_ref, w_ref, yf_ref = rest
    else:
        h_ref, x_ref, first_ref, e_ref, w_ref, yf_ref = rest
    j = pl.program_id(2)
    T, channels = u_ref.shape[1], u_ref.shape[2]

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[j] = jnp.zeros((N, channels), _f32)

    @pl.when(j == 0)
    def _():
        _spread(rows_ref, x_ref, first_ref, N)

    if save:
        sin_ref[0, 0] = h_ref[j]
    u, _, delta, exponent = _steps(u_ref, dt_ref, vec_ref, first_ref,
                                   channels)
    e_ref[...] = exponent
    w_ref[...] = delta * u
    A = a_ref[...]

    def group(g, h):
        base = pl.multiple_of(g * GROUP, GROUP)
        e8, w8 = e_ref[pl.ds(base, GROUP), :], w_ref[pl.ds(base, GROUP), :]
        ys = []
        for r in range(GROUP):
            Bt, Ct = _tiles(x_ref, base + r, N, channels)
            h = jnp.exp(e8[r:r + 1] * A) * h + w8[r:r + 1] * Bt
            ys.append(jnp.sum(h * Ct, axis=0, keepdims=True))
        yf_ref[pl.ds(base, GROUP), :] = _rows_of(ys)
        return h

    h_ref[j] = lax.fori_loop(0, T // GROUP, group, h_ref[j])
    y_ref[0] = (yf_ref[...] + vec_ref[1:2, :] * u).astype(y_ref.dtype)


def _in_specs(T, channels, R, N, chunk_of):
    tok = lambda i, c, j: (i, chunk_of(c), j)
    return [pl.BlockSpec((1, T, channels), tok),                  # u
            pl.BlockSpec((1, T, channels), tok),                  # dt
            pl.BlockSpec((1, R, T), lambda i, c, j: (i, 0, chunk_of(c))),
            pl.BlockSpec((N, channels), lambda i, c, j: (0, j)),  # A^T
            pl.BlockSpec((VEC_ROWS, channels), lambda i, c, j: (0, j))]


def _state_spec(N, channels, chunk_of):
    return pl.BlockSpec((1, 1, N, channels),
                        lambda i, c, j: (i, chunk_of(c), 0, j))


def _forward(u, dt, rows, At, vec, blocking: Blocking, save, interpret):
    b, Sp, Dc = u.shape
    T, channels, N = blocking.chunk, blocking.channels, At.shape[0]
    n, blocks = Sp // T, Dc // channels
    same = lambda c: c
    out_shape = [jax.ShapeDtypeStruct(u.shape, u.dtype)]
    out_specs = [pl.BlockSpec((1, T, channels), lambda i, c, j: (i, c, j))]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((b, n, N, Dc), _f32))
        out_specs.append(_state_spec(N, channels, same))
    tile = pltpu.VMEM((T, channels), _f32)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, N=N, save=save),
        grid=(b, n, blocks), name="ds_sscan_fwd", interpret=interpret,
        compiler_params=_compiler_params(blocking),
        in_specs=_in_specs(T, channels, rows.shape[1], N, same),
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((blocks, N, channels), _f32),
                        pltpu.VMEM((T * 2 * N, LANES), u.dtype),
                        pltpu.VMEM((T, LANES), _f32), tile, tile, tile],
    )(u, dt, rows, At, vec)
    return out if save else out[0]


# -------------------------------------------------------------- backward
def _bwd_kernel(u_ref, dt_ref, rows_ref, a_ref, vec_ref, dy_ref, sin_ref,
                du_ref, ddt_ref, dbc_ref, da_ref, dvec_ref,
                g_ref, x_ref, first_ref, hs_ref, p_ref, e_ref, w_ref,
                dw_ref, de_ref, *, N):
    c, j = pl.program_id(1), pl.program_id(2)
    T, channels = u_ref.shape[1], u_ref.shape[2]
    at = pl.ds(pl.multiple_of(j * channels, channels), channels)

    @pl.when(c == 0)                       # the sequence's last chunk
    def _():
        g_ref[j] = jnp.zeros((N, channels), _f32)
        da_ref[0, :, at] = jnp.zeros((N, channels), _f32)
        dvec_ref[0, :, at] = jnp.zeros((VEC_ROWS, channels), _f32)

    @pl.when(j == 0)
    def _():
        _spread(rows_ref, x_ref, first_ref, N)
        p_ref[...] = jnp.zeros_like(p_ref)

    u, raw, delta, exponent = _steps(u_ref, dt_ref, vec_ref, first_ref,
                                     channels)
    e_ref[...] = exponent
    w_ref[...] = delta * u
    A = a_ref[...]

    # the chunk forward again: slot t + 1 holds the state after token t
    hs_ref[pl.ds(0, N), :] = sin_ref[0, 0]

    def again(g, h):
        base = pl.multiple_of(g * GROUP, GROUP)
        e8, w8 = e_ref[pl.ds(base, GROUP), :], w_ref[pl.ds(base, GROUP), :]
        for r in range(GROUP):
            Bt, _ = _tiles(x_ref, base + r, N, channels)
            h = jnp.exp(e8[r:r + 1] * A) * h + w8[r:r + 1] * Bt
            hs_ref[pl.ds(pl.multiple_of((base + r + 1) * N, N), N), :] = h
        return h

    lax.fori_loop(0, T // GROUP, again, sin_ref[0, 0])

    def back(k, carry):
        G, dA = carry                      # e_{t+1} g_{t+1}; sum of x delta
        base = pl.multiple_of((T // GROUP - 1 - k) * GROUP, GROUP)
        rows8 = pl.ds(base, GROUP)
        e8, w8 = e_ref[rows8, :], w_ref[rows8, :]
        dy8 = dy_ref[0, rows8, :].astype(_f32)
        dws, des = [None] * GROUP, [None] * GROUP
        for r in reversed(range(GROUP)):
            t = base + r
            Bt, Ct = _tiles(x_ref, t, N, channels)
            before = hs_ref[pl.ds(pl.multiple_of(t * N, N), N), :]
            after = hs_ref[pl.ds(pl.multiple_of((t + 1) * N, N), N), :]
            dy = dy8[r:r + 1]
            g = G + Ct * dy
            dws[r] = jnp.sum(g * Bt, axis=0, keepdims=True)
            decay = jnp.exp(e8[r:r + 1] * A)
            x = g * before * decay
            des[r] = jnp.sum(x * A, axis=0, keepdims=True)
            dA = dA + x * e8[r:r + 1]
            G = decay * g
            slot = pl.multiple_of(t * 2 * N, 2 * N)
            p_ref[pl.ds(slot, N), :] += _fold(g * w8[r:r + 1])
            p_ref[pl.ds(slot + N, N), :] += _fold(after * dy)
        dw_ref[rows8, :] = _rows_of(dws)
        de_ref[rows8, :] = _rows_of(des)
        return G, dA

    G, dA = lax.fori_loop(0, T // GROUP, back,
                          (g_ref[j], jnp.zeros((N, channels), _f32)))
    g_ref[j] = G
    da_ref[0, :, at] += dA

    dy = dy_ref[0].astype(_f32)
    dw = dw_ref[...]
    d_delta = dw * u + de_ref[...]
    d_raw = d_delta * (1.0 / (1.0 + jnp.exp(-raw)))
    du_ref[0] = (dw * delta + vec_ref[1:2, :] * dy).astype(du_ref.dtype)
    ddt_ref[0] = d_raw.astype(ddt_ref.dtype)
    dvec_ref[0, 0:1, at] += jnp.sum(d_raw, axis=0, keepdims=True)
    dvec_ref[0, 1:2, at] += jnp.sum(dy * u, axis=0, keepdims=True)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        # the lanes summed: [8, T * 2 N], every row the same
        dbc_ref[0, 0] = _dot(jnp.ones((8, LANES), _f32), p_ref[...], _NT)


def _backward(u, dt, rows, At, vec, dy, s_in, blocking: Blocking,
              interpret):
    b, Sp, Dc = u.shape
    T, channels, N = blocking.chunk, blocking.channels, At.shape[0]
    n, blocks = Sp // T, Dc // channels
    rev = lambda c: n - 1 - c
    tok = lambda i, c, j: (i, rev(c), j)
    tile = pltpu.VMEM((T, channels), _f32)
    whole = lambda rows_: pl.BlockSpec((1, rows_, Dc),
                                       lambda i, c, j: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, N=N),
        grid=(b, n, blocks), name="ds_sscan_bwd", interpret=interpret,
        compiler_params=_compiler_params(blocking),
        in_specs=_in_specs(T, channels, rows.shape[1], N, rev) + [
            pl.BlockSpec((1, T, channels), tok),                  # dy
            _state_spec(N, channels, rev)],
        out_specs=[
            pl.BlockSpec((1, T, channels), tok),                  # du
            pl.BlockSpec((1, T, channels), tok),                  # d(dt)
            pl.BlockSpec((1, 1, 8, T * 2 * N),
                         lambda i, c, j: (i, rev(c), 0, 0)),
            whole(N), whole(VEC_ROWS)],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct(dt.shape, dt.dtype),
                   jax.ShapeDtypeStruct((b, n, 8, T * 2 * N), _f32),
                   jax.ShapeDtypeStruct((b, N, Dc), _f32),
                   jax.ShapeDtypeStruct((b, VEC_ROWS, Dc), _f32)],
        scratch_shapes=[pltpu.VMEM((blocks, N, channels), _f32),
                        pltpu.VMEM((T * 2 * N, LANES), u.dtype),
                        pltpu.VMEM((T, LANES), _f32),
                        pltpu.VMEM(((T + 1) * N, channels), _f32),
                        pltpu.VMEM((T * 2 * N, LANES), _f32),
                        tile, tile, tile, tile],
    )(u, dt, rows, At, vec, dy, s_in)


# ------------------------------------------------- the differentiable op
def pack_rows(B, C, first):
    """[b, 2 N + 8, Sp] in ``B``'s dtype, positions along lanes: ``B^T``,
    ``C^T``, then where a document starts (1.0 | 0.0), eight times."""
    b, Sp, _ = B.shape
    starts = jnp.broadcast_to(first.astype(B.dtype)[:, None, :], (b, 8, Sp))
    return jnp.concatenate(
        [jnp.swapaxes(B, 1, 2), jnp.swapaxes(C.astype(B.dtype), 1, 2),
         starts], axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _sscan(u, dt, rows, At, vec, blocking, interpret):
    return _forward(u, dt, rows, At, vec, blocking, False, interpret)


def _sscan_fwd(u, dt, rows, At, vec, blocking, interpret):
    y, s_in = _forward(u, dt, rows, At, vec, blocking, True, interpret)
    return y, (u, dt, rows, At, vec, s_in)


def _sscan_bwd(blocking, interpret, res, dy):
    u, dt, rows, At, vec, s_in = res
    du, ddt, dbc, dA, dvec = _backward(u, dt, rows, At, vec, dy, s_in,
                                       blocking, interpret)
    b, Sp, _ = u.shape
    N = At.shape[0]
    # [b, n, 8, T * 2 N], every row the same: a token's [dB_t | dC_t]
    dbc = dbc[:, :, 0].reshape(b, Sp, 2 * N)
    d_rows = jnp.concatenate(
        [jnp.swapaxes(dbc, 1, 2).astype(rows.dtype),
         jnp.zeros((b, rows.shape[1] - 2 * N, Sp), rows.dtype)], axis=1)
    return (du, ddt, d_rows, jnp.sum(dA, axis=0).astype(At.dtype),
            jnp.sum(dvec, axis=0).astype(vec.dtype))


_sscan.defvjp(_sscan_fwd, _sscan_bwd)


def sscan_kernels(u, dt, A, B, C, D, dt_bias, first, blocking: Blocking,
                  interpret=False):
    """``y`` [b, Sp, D] — the arguments as ops/selective_scan.py prepared
    them: ``dt`` the raw step, ``A`` [D, N] float32, ``D`` and ``dt_bias``
    [D] or None, ``first`` [b, Sp] bool, Sp a multiple of the chunk."""
    Dc = u.shape[2]
    zeros = jnp.zeros((Dc,), _f32)
    vec = jnp.concatenate([
        jnp.stack([zeros if dt_bias is None else dt_bias.astype(_f32),
                   zeros if D is None else D.astype(_f32)]),
        jnp.zeros((VEC_ROWS - 2, Dc), _f32)])
    return _sscan(u, dt.astype(u.dtype), pack_rows(B.astype(u.dtype), C,
                                                   first),
                  jnp.swapaxes(A, 0, 1), vec, blocking, bool(interpret))
