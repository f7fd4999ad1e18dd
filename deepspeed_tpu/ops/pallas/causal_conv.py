"""The short depthwise causal convolution of ops/linear_attention.py
``causal_conv`` — with its document reset, its bias and the ``silu`` after
it — as two Mosaic kernels under one ``jax.custom_vjp``: ``ds_conv_fwd``
and ``ds_conv_bwd``.

    u_t = b + sum_j w[K-1-j] * x_{t-j} * [t-j is of t's document]
    y_t = silu(u_t)                       (or u_t: ``activation`` None)

Channels are independent, so a grid step (batch, slab of channels) holds
**every position of its slab** in VMEM: each row of ``x`` is read from HBM
once and each row of ``y`` written once; no shifted copy, no
pre-activation and no mask of the array's size ever exists in HBM.  Inside
a grid step a loop walks the slab a tile of positions at a time; the
``K - 1`` taps back are rotations (``pltpu.roll``) of the tile with the
last rows of the tile before it — kept in a small VMEM scratch, zeros
before the sequence — in front.  Everything is float32 in registers; the
one rounding is at the write.

**Which taps a position may read** is one small array made by XLA from
``segment_ids`` (:func:`valid_taps`): how many positions back are still
of the position's own document, at most ``K - 1`` — 0 at a document's and
at the sequence's first position.  Tap ``j`` back counts where that
number is at least ``j``.  Documents are contiguous runs of one id, as
everywhere in ops/.

**The backward is written by hand** and saves nothing but its arguments.
One grid step makes two passes over its slab: the first recomputes ``u``,
takes ``s = dy * silu'(u)`` into a float32 scratch and sums the slab's
``dw[j] = sum_t s_t x_{t-j} [..]`` and ``db = sum_t s_t`` in float32; the
second walks the tiles last to first and writes ``dx_t = sum_j w[K-1-j]
s_{t+j} [t+j may read j back]`` — ``s`` masked where it stands, then the
same rotations the other way.  The
partial ``dw`` and ``db`` leave as ``[batch, 8, C]`` float32 rows (taps,
then the bias) that XLA sums over the batch.

**Two orientations of the one algorithm** (``positions``): ``"sublanes"``
takes slabs ``[S, 128 k]`` of ``x`` [b, S, C] — positions down sublanes,
the weight a row; ``"lanes"`` takes slabs ``[128 k, S]`` of ``x``
[b, C, S] — positions along lanes, the weight a column, the taps' counts
a row.  The caller says which: the one whose array is already laid out
that way, so that no transpose stands beside the call.  For the same
reason ``x`` may be wider than the weights: the convolution then takes the
channels from ``first`` on by its blocks' index, and no slice of the
projection it reads from is written out.
"""
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import vmem

LANES = 128
SUBLANES = 8
ROWS = 16               # channels of one unit with positions along lanes
TAP_ROWS = 8            # rows of the weights' block and of the partial dw
ORIENTATIONS = ("sublanes", "lanes")
_f32 = jnp.float32


class Blocking(NamedTuple):
    positions: str       # which axis of a slab holds positions
    slab: int            # channels one grid step takes
    tile: int            # positions one step of the inner loop takes
    vmem_bytes: int      # the buffers the backward call names


def supported(S, C, K, positions, first=0) -> bool:
    """Shapes the kernels take: channels in whole lane tiles from a first
    channel that starts one, positions in whole tiles of the axis that
    holds them (16 sublanes: a bfloat16 tile; 128 lanes), taps and bias
    inside the weights' eight rows."""
    return (positions in ORIENTATIONS and C % LANES == 0
            and first % LANES == 0
            and S % (ROWS if positions == "sublanes" else LANES) == 0
            and 2 <= K < TAP_ROWS)


def _taps_bytes(S, itemsize, positions):
    """One buffer of :func:`valid_taps`' array as the kernels take it."""
    return S * LANES * itemsize if positions == "sublanes" else ROWS * S * 4


def working_set(S, slab, itemsize, positions) -> int:
    """Bytes of the double-buffered blocks and the scratch of a grid step —
    the backward's, the larger set: x, dy and dx, ``s`` in float32, the
    taps' counts, the weights and the partial sums' rows, and the small
    scratch (partial sums, halos; along lanes also the weights spread over
    a lane tile)."""
    block = S * slab * itemsize
    rows = 2 * TAP_ROWS * slab * 4
    small = TAP_ROWS * slab * 4 * (3 * LANES if positions == "lanes"
                                   else 2 * SUBLANES)
    return (2 * 3 * block + S * slab * 4
            + 2 * _taps_bytes(S, itemsize, positions) + 2 * rows + small)


def slab_width(S, C, itemsize, positions, first=0) -> Blocking:
    """The channels one grid step takes — the wider of 256 and 128 that
    divides ``C`` and the first channel and whose :func:`working_set` fits
    ``vmem.budget()`` (128 where neither does: the caller sees by
    ``vmem_bytes`` that the call does not fit) — and the positions one
    step of the inner loop takes: 128 down sublanes, up to 1024 along
    lanes (the sweep on the chip is in PERF §6, PR 37: a wider slab shares
    a tile's masks among more groups, a longer tile along lanes wastes
    less of each rotation on the halo)."""
    tile = next(t for t in ((128, 64, 32, 16) if positions == "sublanes"
                            else (1024, 512, 256, 128)) if S % t == 0)
    for slab in (256, LANES):
        need = working_set(S, slab, itemsize, positions)
        if C % slab == 0 and first % slab == 0 and need <= vmem.budget():
            return Blocking(positions, slab, tile, need)
    return Blocking(positions, LANES, tile,
                    working_set(S, LANES, itemsize, positions))


def _compiler_params(blocking: Blocking):
    limit = vmem.limit_for(blocking.vmem_bytes)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        **({"vmem_limit_bytes": limit} if limit else {}))


def valid_taps(segment_ids, batch, S, K):
    """[batch, S] int32: how many positions back of each position are of
    its own document, at most ``K - 1`` (0 at a document's first position
    and at the sequence's)."""
    taps = jnp.minimum(jnp.arange(S, dtype=jnp.int32), K - 1)
    taps = jnp.broadcast_to(taps, (batch, S))
    if segment_ids is None:
        return taps
    run = jnp.ones((batch, S), bool)
    count = jnp.zeros((batch, S), jnp.int32)
    for back in range(1, K):
        before = jnp.pad(segment_ids, ((0, 0), (back, 0)),
                         constant_values=-1)[:, :S]
        run = run & (before == segment_ids)
        count = count + run
    return jnp.minimum(count, taps)


# ------------------------------------------------------- walking a slab
UNROLL = 4              # channel groups written out in one step of the loop


class _Slab:
    """How a grid step's slab is walked: which axis holds positions, the
    units (a tile of positions x one group of channels: 128 lanes, or 16
    sublanes) and what of a unit the next one needs in front of it (the
    halo: one register tile, kept in VMEM scratch between tiles).  Tiles
    are a loop; a tile's groups are a loop of ``UNROLL`` groups written
    out — independent chains for the scheduler to interleave, and a
    kernel's text a quarter of what sixteen groups written out are (PERF
    §6, PR 37: what the text costs a start, what the loop costs a call)."""

    def __init__(self, blocking: Blocking, S):
        self.ax = 0 if blocking.positions == "sublanes" else 1
        self.T, self.n = blocking.tile, S // blocking.tile
        self.halo = SUBLANES if self.ax == 0 else LANES
        self.width = LANES if self.ax == 0 else ROWS
        groups = blocking.slab // self.width
        self.unroll = next(u for u in (UNROLL, 2, 1) if groups % u == 0)
        self.steps = groups // self.unroll

    def each_group(self, unit):
        """``unit(group)`` for every channel group of the slab."""
        def step(q, _):
            for u in range(self.unroll):
                start = (q * self.unroll + u) * self.width
                unit(pl.ds(start if isinstance(start, int)
                           else pl.multiple_of(start, self.width),
                           self.width))
            return 0
        if self.steps == 1:
            step(0, 0)
        else:
            lax.fori_loop(0, self.steps, step, 0)

    def at(self, i, group=slice(None)):
        """Index of tile ``i``'s unit of a channel group in a slab."""
        pos = pl.ds(pl.multiple_of(i * self.T, self.T), self.T)
        return (pos, group) if self.ax == 0 else (group, pos)

    def of(self, group):
        """Index of a channel group in an array with one register tile of
        positions (a halo, a partial sum)."""
        return (slice(None), group) if self.ax == 0 else (group, slice(None))

    def _cut(self, a, lo, hi):
        return a[lo:hi] if self.ax == 0 else a[:, lo:hi]

    def last(self, tile):
        return self._cut(tile, self.T - self.halo, self.T)

    def first(self, tile):
        return self._cut(tile, 0, self.halo)

    def back(self, before, tile, j):
        """The values ``j`` positions back of each of the tile's."""
        window = jnp.concatenate([before, tile], axis=self.ax)
        return self._cut(pltpu.roll(window, j, self.ax), self.halo,
                         self.halo + self.T)

    def ahead(self, tile, after, j):
        """The values ``j`` positions ahead of each of the tile's."""
        window = jnp.concatenate([tile, after], axis=self.ax)
        return self._cut(pltpu.roll(window, self.T + self.halo - j, self.ax),
                         0, self.T)

    def summed(self, prod):
        """A unit's products summed over positions down to one register
        tile a channel group (the rest of the sum waits for the end)."""
        step = self.halo
        parts = [self._cut(prod, p, p + step) for p in range(0, self.T, step)]
        return functools.reduce(jnp.add, parts)


class _Weights:
    """Taps and bias of a slab as the units multiply them: with positions
    down sublanes a row of the block [8, slab] (it broadcasts down
    sublanes for nothing); with positions along lanes a column of the
    block [slab, 8], spread over a lane tile once a grid step (scratch
    ``wide`` [8, slab, 128]) and then read as it is."""

    def __init__(self, slab: _Slab, w_ref, wide_ref, K):
        self.slab, self.w_ref, self.wide = slab, w_ref, wide_ref
        if slab.ax == 1:
            for j in range(K + 1):
                wide_ref[j] = jnp.broadcast_to(
                    w_ref[:, j:j + 1], wide_ref.shape[1:])

    def __call__(self, j, group):
        """Row ``j`` (tap ``j``; ``K``: the bias) for a channel group."""
        if self.slab.ax == 0:
            return self.w_ref[j:j + 1, group]
        tile = self.wide[j, group, :]
        return jnp.concatenate([tile] * (self.slab.T // LANES), axis=1)


def _masked_taps(slab: _Slab, before, x, valid):
    """[x_t, x_{t-1} [..], ..., x_{t-(K-1)} [..]] of one unit."""
    return [x] + [jnp.where(ok, slab.back(before, x, j + 1), 0.0)
                  for j, ok in enumerate(valid)]


def _silu(u):
    """u sigmoid(u) with the sigmoid as a hyperbolic tangent: one
    transcendental and no division."""
    half = 0.5 * u
    return half + half * jnp.tanh(half)


def _silu_slope(u):
    """d silu / du = sig (1 + u (1 - sig))"""
    sig = 0.5 * jnp.tanh(0.5 * u) + 0.5
    return sig * (1.0 + u * (1.0 - sig))


def _pre_activation(taps, weights, group, K):
    u = weights(K, group) + weights(K - 1, group) * taps[0]
    for j in range(1, K):
        u = u + weights(K - 1 - j, group) * taps[j]
    return u


# --------------------------------------------------------------- forward
def _fwd_kernel(x_ref, taps_ref, w_ref, o_ref, halo_ref, *scratch, blocking,
                K, silu):
    slab = _Slab(blocking, x_ref.shape[1 + (blocking.positions == "lanes")])
    weights = _Weights(slab, w_ref, scratch[0] if scratch else None, K)
    halo_ref[0] = jnp.zeros_like(halo_ref[0])      # nothing before the start

    def tile(i, _):
        count = taps_ref[(0,) + slab.at(i)].astype(_f32)
        valid = [count >= j for j in range(1, K)]

        def unit(group):
            x = x_ref[(0,) + slab.at(i, group)].astype(_f32)
            before = halo_ref[(0,) + slab.of(group)]
            u = _pre_activation(_masked_taps(slab, before, x, valid),
                                weights, group, K)
            y = _silu(u) if silu else u
            o_ref[(0,) + slab.at(i, group)] = y.astype(o_ref.dtype)
            halo_ref[(0,) + slab.of(group)] = slab.last(x)

        slab.each_group(unit)
        return 0

    lax.fori_loop(0, slab.n, tile, 0)


def _specs(blocking: Blocking, S, first):
    """(block of x from its first channel on, block of y / dy / dx, of the
    taps' counts, of the weights, of the partial sums; scratch: the halos
    and, with positions along lanes, the weights spread over lanes)."""
    slab = blocking.slab
    skip = first // slab
    if blocking.positions == "sublanes":
        return (pl.BlockSpec((1, S, slab), lambda i, c: (i, 0, c + skip)),
                pl.BlockSpec((1, S, slab), lambda i, c: (i, 0, c)),
                pl.BlockSpec((1, S, LANES), lambda i, c: (i, 0, 0)),
                pl.BlockSpec((TAP_ROWS, slab), lambda i, c: (0, c)),
                pl.BlockSpec((1, TAP_ROWS, slab), lambda i, c: (i, 0, c)),
                [pltpu.VMEM((TAP_ROWS, SUBLANES, slab), _f32)])
    return (pl.BlockSpec((1, slab, S), lambda i, c: (i, c + skip, 0)),
            pl.BlockSpec((1, slab, S), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, ROWS, S), lambda i, c: (i, 0, 0)),
            pl.BlockSpec((slab, TAP_ROWS), lambda i, c: (c, 0)),
            pl.BlockSpec((1, TAP_ROWS, slab), lambda i, c: (i, 0, c)),
            [pltpu.VMEM((TAP_ROWS, slab, LANES), _f32)] * 2)


def _out_shape(x, w, blocking: Blocking):
    """(shape of y — ``x``'s with the weights' channels —, positions,
    channels) for a slab-major ``x``."""
    if blocking.positions == "sublanes":
        b, S, _ = x.shape
        return (b, S, w.shape[1]), S, w.shape[1]
    b, _, S = x.shape
    return (b, w.shape[0], S), S, w.shape[0]


_STATIC = ("blocking", "K", "silu", "first", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _forward(x, taps, w, blocking, K, silu, first, interpret):
    shape, S, C = _out_shape(x, w, blocking)
    x_spec, y_spec, taps_spec, w_spec, _, scratch = _specs(blocking, S, first)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, blocking=blocking, K=K, silu=silu),
        grid=(shape[0], C // blocking.slab), name="ds_conv_fwd",
        interpret=interpret, compiler_params=_compiler_params(blocking),
        in_specs=[x_spec, taps_spec, w_spec], out_specs=y_spec,
        out_shape=jax.ShapeDtypeStruct(shape, x.dtype),
        scratch_shapes=scratch)(x, taps, w)


# -------------------------------------------------------------- backward
def _bwd_kernel(x_ref, taps_ref, w_ref, dy_ref, dx_ref, dw_ref, s_ref,
                acc_ref, halo_ref, *scratch, blocking, K, silu):
    slab = _Slab(blocking, x_ref.shape[1 + (blocking.positions == "lanes")])
    weights = _Weights(slab, w_ref, scratch[0] if scratch else None, K)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    halo_ref[...] = jnp.zeros_like(halo_ref)

    def forward_tile(i, _):
        """s = dy silu'(u) of the tile into scratch, its part of dw, db."""
        count = taps_ref[(0,) + slab.at(i)].astype(_f32)
        valid = [count >= j for j in range(1, K)]

        def unit(group):
            x = x_ref[(0,) + slab.at(i, group)].astype(_f32)
            taps = _masked_taps(slab, halo_ref[(0,) + slab.of(group)], x,
                                valid)
            s = dy_ref[(0,) + slab.at(i, group)].astype(_f32)
            if silu:
                s = s * _silu_slope(_pre_activation(taps, weights, group, K))
            s_ref[slab.at(i, group)] = s
            for j in range(K):
                acc_ref[(K - 1 - j,) + slab.of(group)] += \
                    slab.summed(s * taps[j])
            acc_ref[(K,) + slab.of(group)] += slab.summed(s)
            halo_ref[(0,) + slab.of(group)] = slab.last(x)

        slab.each_group(unit)
        return 0

    lax.fori_loop(0, slab.n, forward_tile, 0)
    halo_ref[0] = jnp.zeros_like(halo_ref[0])      # nothing after the end

    def backward_tile(k, _):
        """dx of the tile from s of the tile and of the one after it: s is
        masked where it stands (position t + j may read j back), then
        brought j positions forward."""
        i = slab.n - 1 - k
        count = taps_ref[(0,) + slab.at(i)].astype(_f32)
        valid = [count >= j for j in range(1, K)]

        def unit(group):
            s = s_ref[slab.at(i, group)]
            dx = weights(K - 1, group) * s
            for j, ok in enumerate(valid, start=1):
                sj = jnp.where(ok, s, 0.0)
                dx = dx + weights(K - 1 - j, group) * slab.ahead(
                    sj, halo_ref[(j - 1,) + slab.of(group)], j)
                halo_ref[(j - 1,) + slab.of(group)] = slab.first(sj)
            dx_ref[(0,) + slab.at(i, group)] = dx.astype(dx_ref.dtype)

        slab.each_group(unit)
        return 0

    lax.fori_loop(0, slab.n, backward_tile, 0)

    # what is left of the sums over positions, a row a tap
    rows = []
    for j in range(K + 1):
        part = acc_ref[j]
        rows.append(jnp.sum(part if slab.ax == 0 else part.T, axis=0,
                            keepdims=True))
    rows.append(jnp.zeros((TAP_ROWS - K - 1, blocking.slab), _f32))
    dw_ref[0] = jnp.concatenate(rows, axis=0)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _backward(x, taps, w, dy, blocking, K, silu, first, interpret):
    shape, S, C = _out_shape(x, w, blocking)
    x_spec, y_spec, taps_spec, w_spec, dw_spec, scratch = _specs(
        blocking, S, first)
    slab = blocking.slab
    lanes = blocking.positions == "lanes"
    return pl.pallas_call(
        functools.partial(_bwd_kernel, blocking=blocking, K=K, silu=silu),
        grid=(shape[0], C // slab), name="ds_conv_bwd", interpret=interpret,
        compiler_params=_compiler_params(blocking),
        in_specs=[x_spec, taps_spec, w_spec, y_spec],
        out_specs=[y_spec, dw_spec],
        out_shape=[jax.ShapeDtypeStruct(shape, x.dtype),
                   jax.ShapeDtypeStruct((shape[0], TAP_ROWS, C), _f32)],
        scratch_shapes=[
            pltpu.VMEM((slab, S) if lanes else (S, slab), _f32),   # s
            scratch[0]] + scratch,              # partial sums, as the halos
    )(x, taps, w, dy)


# ------------------------------------------------- the differentiable op
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _conv(x, taps, w, blocking, K, silu, first, interpret):
    return _forward(x, taps, w, blocking, K, silu, first, interpret)


def _conv_fwd(x, taps, w, blocking, K, silu, first, interpret):
    return _forward(x, taps, w, blocking, K, silu, first, interpret), \
        (x, taps, w)


def _conv_bwd(blocking, K, silu, first, interpret, res, dy):
    x, taps, w = res
    dx, dw = _backward(x, taps, w, dy, blocking, K, silu, first, interpret)
    dw = jnp.sum(dw, axis=0)                                 # [8, C]
    lanes = blocking.positions == "lanes"
    axis = 1 if lanes else 2
    rest = x.shape[axis] - first - dx.shape[axis]
    if first or rest:
        # the channels of x the convolution did not read
        dx = jnp.pad(dx, [(0, 0)] * axis + [(first, rest)]
                     + [(0, 0)] * (2 - axis))
    return dx, jnp.zeros_like(taps), dw.T if lanes else dw


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv_kernels(x, w, segment_ids, bias, activation: Optional[str],
                        blocking: Blocking, first=0, interpret=False):
    """``y`` [B, S, C] in ``x``'s dtype — ``x`` [B, S, Cx] of which the
    convolution takes the channels ``first`` to ``first + C``, ``w``
    [K, C], ``segment_ids`` [B, S] int or None, ``bias`` [C] or None,
    ``activation`` None or ``"silu"``; ``blocking`` from
    :func:`slab_width`.  Differentiable in ``x``, ``w`` and ``bias``."""
    B, S, _ = x.shape
    K, C = w.shape
    rows = jnp.concatenate(
        [w.astype(_f32),
         (jnp.zeros((C,), _f32) if bias is None
          else bias.astype(_f32))[None],
         jnp.zeros((TAP_ROWS - K - 1, C), _f32)], axis=0)    # [8, C]
    taps = valid_taps(segment_ids, B, S, K)
    silu = activation == "silu"
    if blocking.positions == "sublanes":
        taps = jnp.broadcast_to(taps[..., None].astype(x.dtype),
                                (B, S, LANES))
        return _conv(x, taps, rows, blocking, K, silu, first,
                     bool(interpret))
    taps = jnp.broadcast_to(taps[:, None].astype(_f32), (B, ROWS, S))
    y = _conv(jnp.swapaxes(x, 1, 2), taps, rows.T, blocking, K, silu, first,
              bool(interpret))
    return jnp.swapaxes(y, 1, 2)
