"""The chunked state-space scan (SSD) of ops/state_space.py as two Mosaic
kernels under one ``jax.custom_vjp``: ``ds_ssd_fwd`` and ``ds_ssd_bwd``.

Same algorithm and the same roundings as the XLA chunked form (its
docstring is the contract: operands of every product in ``x``'s dtype,
float32 accumulation, float32 decays and state; a document's reset is a
mask, anywhere in a chunk), lowered so that

* every operand has **positions along lanes**: ``x`` and ``y`` are read
  and written as ``[b, H P, S]``, ``B`` and ``C`` as ``[b, G N, S]``, the
  per-token scalars as rows ``[b, G W, S]``.  That is the layout XLA gives
  the layer's arrays by itself where the projection's width (z | x B C |
  dt) is not whole lane tiles, so the transposes around the call are
  relabelings; a head is ``P`` sublanes of a group's ``r P``, a per-token
  scalar of a head is a row that broadcasts over them for nothing, and
  the sums over a head's channels run down sublanes;
* the carried state of a group's ``r = H / G`` heads, ``[r P, N]`` float32,
  lives in VMEM scratch across the chunks of one sequence: the grid is
  (batch, group, block of chunks) with the last axis sequential;
* the masked decay ``exp(G_i - G_j)`` of a head, ``i >= j`` of one
  document, is built in registers (transposed: j down sublanes, i along
  lanes) from the scalars' rows and, for ``G_j``, their columns (one
  float32 transpose of a register tile a chunk) and multiplied into the
  group's ``(C B^T)^T``: no [.., C, C] array and no per-chunk state is
  written to HBM.  The difference is taken only where it is <= 0 (no
  factoring into ``exp(G_i) exp(-G_j)``: ``dt A`` reaches -205 a chunk);
* the chunk's contribution to the state does not need the state, so the
  walk down a block's chunks is a chain of multiply-adds on the state
  alone and the scheduler interleaves everything else (the loop over a
  block's chunks is unrolled);
* the backward is written by hand.  The forward rule saves the state that
  enters each *block* of chunks (float32); the backward walks the blocks
  in reverse with ``dH`` in VMEM scratch, first re-walks its block forward
  to have each chunk's incoming state again, and recomputes the
  chunk-local products from ``x``, ``B``, ``C`` and the scalars.  ``dB``
  and ``dC`` are sums over the group's heads, formed inside the grid step.
  The gradients of the packed scalars leave the kernel as rows and
  autodiff takes them through :func:`pack_scalars` (the reverse cumulative
  sum into ``d(dt)`` and ``dA``).

One grid step takes one group and :func:`chunks_per_step` chunks — a rule
of shapes, dtypes and ``vmem.budget()``.  The skip term ``D x`` is added
before ``y`` is rounded, as the XLA form adds it.
"""
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import vmem
from deepspeed_tpu.ops.pallas.gated_delta_rule import _NT, _TN, _dot

LANES = 128
_f32 = jnp.float32
#: rows of the packed scalars a head has: G, dt, dt * to_end, from_state
PER_HEAD = 4


class Blocking(NamedTuple):
    chunk: int           # C, tokens of one chunk
    chunks: int          # chunks one grid step walks
    heads: int           # heads one grid step takes (r = H / G)
    vmem_bytes: int      # the buffers the backward call names


def supported(P, N, r, chunk) -> bool:
    """Shapes the kernels take: a state size that is whole lane tiles, a
    chunk that is one, heads that are whole sublane tiles of either dtype,
    no more heads a group than a register tile has lanes for their
    columns."""
    return (N % LANES == 0 and chunk == LANES and P % 16 == 0
            and r <= LANES)


def _ceil8(rows):
    return -(-rows // 8) * 8


def scalar_rows(r):
    """Rows of a group's packed scalars, a multiple of a float32 tile's 8:
    PER_HEAD a head, then where a position's document starts."""
    return _ceil8(PER_HEAD * r + 1)


def working_set(nc, C, r, P, N, itemsize) -> int:
    """Bytes of the double-buffered blocks and the scratch of a grid step
    that walks ``nc`` chunks — the backward's, the larger set."""
    T, width = nc * C, r * P
    tokens = T * (2 * width + 2 * N) * itemsize               # x dy B C
    outs = T * (width + 2 * N) * itemsize                     # dx dB dC
    small = 2 * T * scalar_rows(r) * 4 + width * LANES * 4    # rows, D
    state = N * width * 4
    return (2 * (tokens + outs + small + state)
            + (nc + 2) * state               # dH, H, the block's states
            + 6 * C * width * 4)             # a chunk's temporaries


def chunks_per_step(n, C, r, P, N, itemsize) -> Blocking:
    """How many chunks one grid step walks: the most (of 8, 4, 2, 1
    dividing ``n``) whose :func:`working_set` fits what a call is granted
    unasked; where even one chunk passes that, one chunk and
    ``vmem.limit_for``'s limit."""
    for nc in (8, 4, 2, 1):
        need = working_set(nc, C, r, P, N, itemsize)
        if n % nc == 0 and need <= vmem.UNASKED:
            return Blocking(C, nc, r, need)
    return Blocking(C, 1, r, working_set(1, C, r, P, N, itemsize))


def _compiler_params(blocking: Blocking):
    limit = vmem.limit_for(blocking.vmem_bytes)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        **({"vmem_limit_bytes": limit} if limit else {}))


# ------------------------------------------------------------ chunk maths
def _positions(C):
    return (lax.broadcasted_iota(jnp.int32, (C, C), 0),
            lax.broadcasted_iota(jnp.int32, (C, C), 1))


class _Chunk:
    """One chunk's scalars: ``row(q, h)`` [1, C] is G (q = 0), dt (1),
    dt * to_end (2) or from_state (3) of head ``h`` along lanes; with
    ``position`` also what the decay matrices are made of."""

    def __init__(self, rows, r, position=None):
        self.rows, self.r = rows, r
        if position is None:
            return
        jj, ii = position                  # j down sublanes, i along lanes
        start = rows[PER_HEAD * r:PER_HEAD * r + 1, :]
        # j is a position of i's document, at or before i (documents are
        # contiguous: from the document's first position to i)
        self.visible = (jj <= ii) & (jj.astype(_f32) >= start)
        # G of head h down sublanes in lane h: one transpose of a tile
        C = rows.shape[1]
        self.G_col = jnp.concatenate(
            [rows[:_ceil8(r)], jnp.zeros((LANES - _ceil8(r), C), _f32)],
            axis=0).T

    def row(self, q, h):
        k = q * self.r + h
        return self.rows[k:k + 1, :]

    def decay(self, h):
        """[C (j), C (i)]: exp(G_i - G_j) where visible, else 0; the
        difference is taken only where it is <= 0."""
        d = self.row(0, h) - self.G_col[:, h:h + 1]
        return jnp.where(self.visible, jnp.exp(jnp.minimum(d, 0.0)), 0.0)


def kept(scalars, G, r, C, N):
    """[b, G, n, r8, N]: what each head's state keeps of itself over each
    chunk — from_state at the chunk's last position — along the state's
    lanes, as the kernels multiply it into the state (Mosaic broadcasts
    over lanes or over sublanes, not over both at once; plain XLA here)."""
    b, rows, Sp = scalars.shape
    last = scalars.reshape(b, G, rows // G, Sp // C, C)[
        :, :, 3 * r:PER_HEAD * r, :, -1]
    last = jnp.pad(jnp.swapaxes(last, 2, 3),                # [b, G, n, r8]
                   ((0, 0), (0, 0), (0, 0), (0, _ceil8(r) - r)))
    return jnp.broadcast_to(last[..., None], last.shape + (N,))


def _scaled(row, xf, dt):
    return (row * xf).astype(dt)


def _heads(r, P):
    return [slice(h * P, (h + 1) * P) for h in range(r)]


# --------------------------------------------------------------- forward
def _fwd_kernel(x_ref, b_ref, c_ref, sc_ref, k_ref, d_ref, o_ref, *rest, C,
                nc, r, P, save):
    if save:
        sin_ref, s_ref = rest
    else:
        s_ref, = rest
    dt = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    if save:
        sin_ref[0, 0, 0] = s_ref[...]
    position = _positions(C)

    for c in range(nc):                    # unrolled: see the docstring
        at = slice(c * C, (c + 1) * C)
        ch = _Chunk(sc_ref[0, :, at], r, position)
        Bt, Ct = b_ref[0, :, at], c_ref[0, :, at]            # [N, C]
        cb = _dot(Bt, Ct, _TN)                               # [C (j), C (i)]
        state = s_ref[...]
        read = _dot(state.astype(dt), Ct)                    # [r P, C]
        to_state = []
        for h, rows in enumerate(_heads(r, P)):
            xf = x_ref[0, rows, at].astype(_f32)             # [P, C]
            M = (ch.decay(h) * cb).astype(dt)
            y = ch.row(3, h) * read[rows] \
                + _dot(_scaled(ch.row(1, h), xf, dt), M)
            o_ref[0, rows, at] = (y + d_ref[0, rows] * xf).astype(dt)
            to_state.append(_scaled(ch.row(2, h), xf, dt))
        update = _dot(jnp.concatenate(to_state, axis=0), Bt, _NT)  # [r P, N]
        for h, rows in enumerate(_heads(r, P)):
            s_ref[rows] = k_ref[0, 0, c, h:h + 1] * state[rows] \
                + update[rows]


def _token_specs(nc, C, width, N, W, index):
    """x as [b, H P, S], B and C as [b, G N, S], the packed scalars as
    [b, G W, S]: a block of ``nc`` chunks of one group."""
    return [pl.BlockSpec((1, width, nc * C), index),
            pl.BlockSpec((1, N, nc * C), index),
            pl.BlockSpec((1, N, nc * C), index),
            pl.BlockSpec((1, W, nc * C), index)]


def _kept_spec(nc, r, N, index):
    return pl.BlockSpec((1, 1, nc, _ceil8(r), N),
                        lambda i, g, j: index(i, g, j) + (0, 0))


def _skip_spec(width):
    """D over each head's channels and over a lane tile, [G, r P, 128]"""
    return pl.BlockSpec((1, width, LANES), lambda i, g, j: (g, 0, 0))


def _sizes(x, B, skip, blocking):
    G = skip.shape[0]
    return G, x.shape[1] // G, B.shape[1] // G, x.shape[2] // blocking.chunk


def _forward(x, B, C_, scalars, skip, blocking, save, interpret):
    C, nc, r, _ = blocking
    G, width, N, n = _sizes(x, B, skip, blocking)
    tok = lambda i, g, j: (i, g, j)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    out_specs = [pl.BlockSpec((1, width, nc * C), tok)]
    if save:
        out_shape.append(jax.ShapeDtypeStruct(
            (x.shape[0], G, n // nc, width, N), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, 1, width, N),
                                      lambda i, g, j: (i, g, j, 0, 0)))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, C=C, nc=nc, r=r, P=width // r,
                          save=save),
        grid=(x.shape[0], G, n // nc), name="ds_ssd_fwd",
        interpret=interpret, compiler_params=_compiler_params(blocking),
        in_specs=_token_specs(nc, C, width, N, scalars.shape[1] // G, tok)
        + [_kept_spec(nc, r, N, tok), _skip_spec(width)],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((width, N), jnp.float32)],
    )(x, B, C_, scalars, kept(scalars, G, r, C, N), skip)
    return out if save else out[0]


# -------------------------------------------------------------- backward
def _down(x):
    """The sum down sublanes, [1, C]."""
    return jnp.sum(x, axis=0, keepdims=True)


def _bwd_kernel(x_ref, b_ref, c_ref, sc_ref, k_ref, d_ref, dy_ref, sin_ref,
                dx_ref, db_ref, dc_ref, dsc_ref, dd_ref, dh_ref, h_ref,
                st_ref, *, C, nc, r, P):
    dt = x_ref.dtype
    W = sc_ref.shape[1]
    r8 = _ceil8(r)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    position = _positions(C)
    heads = _heads(r, P)
    lane = lax.broadcasted_iota(jnp.int32, (1, C), 1)
    lane_c = lax.broadcasted_iota(jnp.int32, (C, LANES), 1)

    # the block's states again: the one entering each chunk.  dx's block
    # holds dt * to_end * x of a chunk until that chunk's dx is written
    h_ref[...] = sin_ref[0, 0, 0]
    for c in range(nc):
        at = slice(c * C, (c + 1) * C)
        ch = _Chunk(sc_ref[0, :, at], r)
        st_ref[c] = h_ref[...]
        for h, rows in enumerate(heads):
            dx_ref[0, rows, at] = _scaled(
                ch.row(2, h), x_ref[0, rows, at].astype(_f32), dt)
        if c < nc - 1:
            update = _dot(dx_ref[0, :, at], b_ref[0, :, at], _NT)
            for h, rows in enumerate(heads):
                h_ref[rows] = k_ref[0, 0, c, h:h + 1] * h_ref[rows] \
                    + update[rows]

    for c in reversed(range(nc)):
        at = slice(c * C, (c + 1) * C)
        ch = _Chunk(sc_ref[0, :, at], r, position)
        Bt, Ct = b_ref[0, :, at], c_ref[0, :, at]
        cb = _dot(Bt, Ct, _TN)                               # [C (j), C (i)]
        state = st_ref[c]
        held = state.astype(dt)
        read = _dot(held, Ct)                                # [r P, C]
        d_out = dh_ref[...]                  # of the state the chunk leaves
        d_held = d_out.astype(dt)
        dB = _dot(d_held, dx_ref[0, :, at], _TN)             # [N, C]
        d_xw = _dot(d_held, Bt)                              # [r P, C]
        d_cb = jnp.zeros((C, C), _f32)
        d_read = []
        dG_col = jnp.zeros((C, LANES), _f32)
        for h, rows in enumerate(heads):
            xf = x_ref[0, rows, at].astype(_f32)
            dy = dy_ref[0, rows, at]
            dyf = dy.astype(_f32)
            # y = from_state * (held C) + xdt M + D x
            d_read.append(_scaled(ch.row(3, h), dyf, dt))
            decay = ch.decay(h)
            M = (decay * cb).astype(dt)
            xdt = _scaled(ch.row(1, h), xf, dt)
            dM = _dot(xdt, dy, _TN)                          # [C (j), C (i)]
            d_xdt = _dot(dy, M, _NT)                         # [P, C (j)]
            d_cb += dM * decay
            d_decay = dM * cb * decay
            # G enters the decay with + at i (a row, below) and - at j
            dG_col = jnp.where(lane_c == h,
                               jnp.sum(d_decay, axis=1, keepdims=True),
                               dG_col)
            # the state leaves as keep * state + xw B^T, keep = the last
            # from_state
            d_keep = jnp.sum(_down(d_out[rows] * state[rows]), axis=1,
                             keepdims=True)                  # [1, 1]
            dsc_ref[0, h:h + 1, at] = _down(d_decay)
            dsc_ref[0, r + h:r + h + 1, at] = _down(d_xdt * xf)
            dsc_ref[0, 2 * r + h:2 * r + h + 1, at] = _down(d_xw[rows] * xf)
            dsc_ref[0, 3 * r + h:3 * r + h + 1, at] = \
                _down(dyf * read[rows]) + jnp.where(lane == C - 1, d_keep,
                                                    0.0)
            dx_ref[0, rows, at] = (ch.row(1, h) * d_xdt
                                   + ch.row(2, h) * d_xw[rows]
                                   + d_ref[0, rows] * dyf).astype(dt)
            dd_ref[0, 0, h:h + 1, :] += _down(dyf * xf)
            dh_ref[rows] = k_ref[0, 0, c, h:h + 1] * d_out[rows]
        d_read = jnp.concatenate(d_read, axis=0)             # [r P, C]
        d_cb = d_cb.astype(dt)
        db_ref[0, :, at] = (dB + _dot(Ct, d_cb, _NT)).astype(dt)
        dc_ref[0, :, at] = (_dot(held, d_read, _TN)
                            + _dot(Bt, d_cb)).astype(dt)
        dh_ref[...] += _dot(d_read, Ct, _NT)
        # the decay's part of dG by its columns, turned along lanes (lanes
        # past the heads hold zeros)
        dsc_ref[0, :r8, at] = dsc_ref[0, :r8, at] - dG_col.T[:r8]
        if W > PER_HEAD * r:
            dsc_ref[0, PER_HEAD * r:, at] = jnp.zeros(
                (W - PER_HEAD * r, C), _f32)


def _backward(x, B, C_, scalars, skip, dy, s_in, blocking, interpret):
    C, nc, r, _ = blocking
    G, width, N, n = _sizes(x, B, skip, blocking)
    W = scalars.shape[1] // G
    nb = n // nc
    tok = lambda i, g, j: (i, g, nb - 1 - j)
    state = pltpu.VMEM((width, N), jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, C=C, nc=nc, r=r, P=width // r),
        grid=(x.shape[0], G, nb), name="ds_ssd_bwd", interpret=interpret,
        compiler_params=_compiler_params(blocking),
        in_specs=_token_specs(nc, C, width, N, W, tok) + [
            _kept_spec(nc, r, N, tok), _skip_spec(width),
            pl.BlockSpec((1, width, nc * C), tok),
            pl.BlockSpec((1, 1, 1, width, N),
                         lambda i, g, j: (i, g, nb - 1 - j, 0, 0))],
        out_specs=_token_specs(nc, C, width, N, W, tok) + [
            # a group's x dy by head and position within a chunk, summed
            # over the sequence's chunks in place
            pl.BlockSpec((1, 1, _ceil8(r), C), lambda i, g, j: (i, g, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(B.shape, B.dtype),
                   jax.ShapeDtypeStruct(C_.shape, C_.dtype),
                   jax.ShapeDtypeStruct(scalars.shape, jnp.float32),
                   jax.ShapeDtypeStruct((x.shape[0], G, _ceil8(r), C),
                                        jnp.float32)],
        scratch_shapes=[state, state,
                        pltpu.VMEM((nc, width, N), jnp.float32)],
    )(x, B, C_, scalars, kept(scalars, G, r, C, N), skip, dy, s_in)


# ------------------------------------------------- the differentiable op
def pack_scalars(dt, A, seg, C, G):
    """Per-token scalars as the kernels read them, positions along lanes:
    [b, G W, Sp] float32 (W = :func:`scalar_rows`) — per head of a group
    ``G`` (the in-chunk running sum of ``dt A``), ``dt``, ``dt`` times the
    factor from a position to its chunk's end, the factor from the incoming
    state to a position (the two zero outside the document of the chunk's
    last token / the one the previous chunk ended in); then the first
    position in the chunk of each position's document; then zeros.  Plain
    XLA: autodiff takes the kernels' gradient of this array back to ``dt``
    and ``A``."""
    b, Sp, H = dt.shape
    n, r = Sp // C, H // G
    dth = jnp.swapaxes(dt, 1, 2).reshape(b, H, n, C)
    Gc = jnp.cumsum(dth * A[:, None, None], axis=-1)
    sc = seg.reshape(b, n, C)
    # the document the previous chunk ended in (chunk 0: no state yet)
    prev = jnp.concatenate([sc[:, :1, 0], sc[:, :-1, -1]], axis=1)
    heads = lambda m: m[:, None]
    # both exponents are <= 0
    from_state = jnp.where(heads(sc == prev[..., None]), jnp.exp(Gc), 0.0)
    to_end = jnp.where(heads(sc == sc[..., -1:]),
                       jnp.exp(Gc[..., -1:] - Gc), 0.0)
    position = jnp.arange(C, dtype=jnp.int32)
    starts = jnp.concatenate([jnp.ones((b, n, 1), bool),
                              sc[..., 1:] != sc[..., :-1]], axis=-1)
    start = lax.cummax(jnp.where(starts, position, 0), axis=2)
    by_group = lambda a: a.reshape(b, G, r, Sp)
    W = scalar_rows(r)
    return jnp.concatenate(
        [by_group(a) for a in (Gc, dth, to_end * dth, from_state)]
        + [jnp.broadcast_to(start.reshape(b, 1, 1, Sp).astype(jnp.float32),
                            (b, G, 1, Sp)),
           jnp.zeros((b, G, W - PER_HEAD * r - 1, Sp), jnp.float32)],
        axis=2).reshape(b, G * W, Sp)


def _along_lanes(*arrays):
    """[b, Sp, heads, width] -> [b, heads * width, Sp]"""
    b, Sp = arrays[0].shape[:2]
    return tuple(jnp.swapaxes(a.reshape(b, Sp, -1), 1, 2) for a in arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, B, C_, scalars, skip, blocking, interpret):
    return _forward(x, B, C_, scalars, skip, blocking, False, interpret)


def _ssd_fwd(x, B, C_, scalars, skip, blocking, interpret):
    y, s_in = _forward(x, B, C_, scalars, skip, blocking, True, interpret)
    return y, (x, B, C_, scalars, skip, s_in)


def _ssd_bwd(blocking, interpret, res, dy):
    x, B, C_, scalars, skip, s_in = res
    dx, dB, dC, d_scalars, dD = _backward(x, B, C_, scalars, skip, dy, s_in,
                                          blocking, interpret)
    # [b, G, r, C] summed to a head's; the whole of it at the head's first
    # entry of ``skip`` (the broadcast that made ``skip`` sums it back)
    r, P = blocking.heads, skip.shape[1] // blocking.heads
    d_head = jnp.sum(dD[:, :, :r], axis=(0, 3))               # [G, r]
    return (dx, dB, dC, d_scalars,
            jnp.zeros_like(skip).at[:, ::P, 0].set(d_head))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_kernels(x, dt, A, B, C_, D, seg, blocking: Blocking,
                interpret=False):
    """``y`` [b, Sp, H, P] — the arguments as ops/state_space.py prepared
    them: ``dt`` and ``A`` float32, ``B`` and ``C_`` in ``x``'s dtype, ``D``
    [H] or None, ``seg`` int32, Sp a multiple of the chunk."""
    b, Sp, H, P = x.shape
    G = B.shape[2]
    scalars = pack_scalars(dt, A, seg, blocking.chunk, G)
    D = jnp.zeros((H,), jnp.float32) if D is None else D
    skip = jnp.broadcast_to(
        jnp.repeat(D.astype(jnp.float32), P).reshape(G, H // G * P, 1),
        (G, H // G * P, LANES))
    y = _ssd(*_along_lanes(x, B, C_), scalars, skip, blocking,
             bool(interpret))
    return jnp.swapaxes(y, 1, 2).reshape(x.shape)
