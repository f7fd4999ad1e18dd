"""The chunked gated delta rule with a decay a key channel (Kimi Delta
Attention; ops/linear_attention.py's module docstring has the equations)
as two Mosaic kernels under one ``jax.custom_vjp``: ``ds_kda_fwd`` and
``ds_kda_bwd``.

Same algorithm and the same roundings as ``_chunked_xla_channel`` (operands
of every matrix product in ``v``'s dtype, float32 accumulation; ``G``, every
decay, the inverse of ``I + L`` and the carried state float32), and the
frame of ops/pallas/gated_delta_rule.py, whose helpers these kernels share
(the inverse by halves, ``_dot``, the l2-norm on the tiles and its backward,
the token blocks, the blocking's shape): grid (batch, head, block of
chunks), the last axis sequential, the state of a head in VMEM scratch
across the chunks of a sequence, the backward by hand over the saved
incoming states and ``T``.  What a decay a channel changes:

* ``g`` [C, dk] float32 is a tile beside ``k``.  ``G``, its cumulative
  sum down the chunk, is a product with a triangle of ones (exact: ``g`` in
  three bfloat16 pieces, :func:`_dot_exact`), and ``dg`` the transposed
  product of ``dG``: neither is an array in HBM.
* ``A[i, j] = sum_c a_ic k_jc exp(G_ic - G_jc)`` (``a`` = ``k`` and ``a`` =
  ``q``) has the decay inside the contraction.  Below the sub-blocks of
  ``SUB_BLOCK`` positions it is made element by element in float32
  registers — by offset ``d = i - j``: ``k`` and ``G`` turned ``d`` rows
  down the sublanes, one exponential and two sums over lanes an offset;
  above them by halves: for blocks of ``2s`` rows (``s`` = C/2 .. the
  sub-block) rows of the later half and keys of the earlier one are both
  taken against ``G`` at the later half's first row — ``exp(-|G -
  G_ref|)``, one product a level.  **No exponential of a positive number**,
  whatever the inputs.
* ``(I + L)^-1`` is gated_delta_rule's ``_inverse``, for two chunks side by
  side along lanes (one [C, C] tile half-fills a register and a pass of
  the matrix unit, and the inverse's thirty dependent products were half
  of the forward: PERF.md section 6, PR 61).
* The state is held transposed, ``[dv, dk]``: what it keeps of itself over
  a chunk is a factor a key channel, ``exp(G_C)``, a row along lanes.
* ``A`` reads ``G`` only through ``exp(G_i - G_j)`` beside ``a_i k_j``, so
  ``dG = q . dq + k . (dk as a row - dk as a key)`` over every use of a
  decay (Yang et al. 2023, GLA, arXiv:2312.06635 section 4.3), plus what
  the chunk's last row gathers: no gradient of a reference row is kept.

One grid step takes one head (a decay a channel shares nothing between
value heads: ``rep`` is 1) and :func:`chunks_per_step` chunks.
"""
import functools
import types

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.linear_attention import SUB_BLOCK
from deepspeed_tpu.ops.pallas import gated_delta_rule as gdr, vmem
from deepspeed_tpu.ops.pallas.gated_delta_rule import (
    Blocking, _NN, _NT, _TN, _by_chunk, _compiler_params, _dot, _f32,
    _identity, _inverse, _inverse_product, _level_masks, _normalized,
    _place, _positions, _unnormalized)

_ROWS = 8           # the per-token scalars' rows: beta, document, two masks


def supported(dk, dv, chunk, rep) -> bool:
    """Shapes the kernels take: the scalar rule's kernels' (lane-wide
    heads, a chunk that halves down to one token and fills a bf16 tile:
    whole sub-blocks, then), one value head a key head."""
    return gdr.supported(dk, dv, chunk, rep) and rep == 1


def chunks_per_step(n, C, rep, dk, dv, itemsize) -> Blocking:
    """How many chunks one grid step walks: the most (of 8, 4, 2, 1
    dividing ``n``) whose double-buffered blocks — the backward's, the
    larger set — and a chunk's float32 working set fit what a call is
    granted unasked (gated_delta_rule.chunks_per_step's rule)."""
    def need(nc):
        T = nc * C
        rows = T * (2 * dk + 2 * dv) * itemsize + T * dk * 4     # q k v do g
        outs = T * (2 * dk + dv) * itemsize + T * dk * 4         # dq dk dv dg
        small = nc * 2 * _ROWS * 128 * 4
        saved = nc * (dk * dv * 4 + C * 128 * itemsize)
        working = 24 * C * max(dk, dv) * 4
        return 2 * (rows + outs + small + saved) + working + 3 * dk * dv * 4
    for nc in (8, 4, 2, 1):
        if n % nc == 0 and need(nc) <= vmem.UNASKED:
            return Blocking(C, nc, 1, need(nc))
    return Blocking(C, 1, 1, need(1))


# ------------------------------------------------------------ chunk maths
def _pieces(x):
    """float32 ``x`` as three bfloat16 pieces whose sum it is (3 x 8 bits
    of mantissa)."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(_f32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(_f32)).astype(jnp.bfloat16)


def _dot_exact(a, b, contract=_NN):
    """``a @ b`` for a bfloat16 matrix of zeros and ones and a float32 one
    (either side): the float32 operand piece by piece, every product exact
    and summed in float32 — a full-precision product's result in half its
    passes, which split both operands."""
    if a.dtype == jnp.bfloat16:
        hi, mid, lo = (_dot(a, x, contract) for x in _pieces(b))
    else:
        hi, mid, lo = (_dot(x, b, contract) for x in _pieces(a))
    return hi + (mid + lo)


def _rsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _turned(x, by):
    """``x`` [C, .] turned ``by`` rows down: row r holds x[r - by]."""
    return pltpu.roll(x, by % x.shape[0], 0)


def _against_reference(G, s):
    """``exp(-|G - G_ref|)`` [C, dk] for the level of blocks of ``2s`` rows:
    ``G_ref`` is ``G`` at the first row of a block's later half."""
    C, dk = G.shape
    refs = [jnp.broadcast_to(G[b + s:b + s + 1, :], (2 * s, dk))
            for b in range(0, C, 2 * s)]
    ref = refs[0] if len(refs) == 1 else jnp.concatenate(refs, axis=0)
    return jnp.exp(-jnp.abs(G - ref))


def _levels(C):
    """(s, index of its mask in ``_level_masks``) of the levels made by
    products: s = C/2 down to the sub-block."""
    out, s = [], C // 2
    while s >= SUB_BLOCK:
        out.append((s, s.bit_length() - 1))
        s //= 2
    return out


class _Frame:
    """What every chunk of a call shares: positions and masks of a [C, C]
    tile."""

    def __init__(self, C, dt):
        ii, jj, _ = _positions(C)
        self.C, self.dt = C, dt
        self.masks = _level_masks(ii, jj, C)
        # the same for k's rows above q's, [2C, C]
        i2 = lax.broadcasted_iota(jnp.int32, (2 * C, C), 0) & (C - 1)
        j2 = lax.broadcasted_iota(jnp.int32, (2 * C, C), 1)
        self.masks2 = _level_masks(i2, j2, C)
        self.strict = ii > jj
        self.lower = ii >= jj
        self.eye = (ii == jj).astype(_f32)
        # zeros and ones, for _dot_exact: the triangle of a cumulative sum,
        # the identities that turn rows into columns and back
        self.tri = (ii >= jj).astype(jnp.bfloat16)
        self.eye_rows = _identity(_ROWS).astype(jnp.bfloat16)
        self.eye_c = self.eye.astype(jnp.bfloat16)
        self.mm = _inverse_product(dt, None, 1)
        # two chunks' L side by side along lanes: one inverse for both
        i2, j2, chunk = _positions(C, 2)
        self.pair = ((i2 == j2).astype(_f32), _level_masks(i2, j2, C),
                     _inverse_product(dt, chunk, 2))
        sub = SUB_BLOCK.bit_length() - 1
        together = (ii >> sub) == (jj >> sub)
        #: offset d: "j is d positions before i in i's sub-block"
        self.offsets = [together & (ii - jj == d) for d in range(SUB_BLOCK)]


def _local(fr, q, k, v, g, sc, scales):
    """The chunk-local values of one chunk of one head, up to ``L``:
    ``q``, ``k``, ``v`` [C, .] as the call holds them, ``g`` [C, dk]
    float32, ``sc`` [_ROWS, C] the per-token scalars along lanes."""
    C, dt = fr.C, fr.dt
    lo = types.SimpleNamespace()      # the chunk's values, by name
    qn, lo.rq = _normalized(q, scales and scales[0], dt)
    kn, lo.rk = _normalized(k, scales and scales[1], dt)
    lo.qf, lo.kf, lo.vf = qn.astype(_f32), kn.astype(_f32), v.astype(_f32)
    col = _dot_exact(sc, fr.eye_rows, _TN)                   # [C, _ROWS]
    lo.b = col[:, 0:1]
    same = col[:, 1:2] == sc[1:2, :]                         # [C, C]
    lo.visible = same & fr.lower
    lo.below = same & fr.strict
    lo.G = G = _dot_exact(fr.tri, g)
    lo.F = col[:, 2:3] * jnp.exp(G)                          # from the state
    last = jnp.broadcast_to(G[C - 1:C, :], G.shape)
    lo.E = col[:, 3:4] * jnp.exp(-jnp.abs(last - G))         # to the end
    lo.keep = lo.F[C - 1:C, :]                               # [1, dk]

    # A for a = k (rows 0 .. C) and a = q (rows C .. 2C), level by level
    A = jnp.zeros((2 * C, C), _f32)
    for s, at in _levels(C):
        e = _against_reference(G, s)
        x = (lo.kf * e).astype(dt)
        both = jnp.concatenate([x, (lo.qf * e).astype(dt)], axis=0)
        A = jnp.where(fr.masks2[at], _dot(both, x, _NT), A)
    kk, qk = A[:C], A[C:]
    for d, place in enumerate(fr.offsets):
        if d == 0:
            qk = jnp.where(place, _rsum(lo.qf * lo.kf), qk)
            continue
        p = _turned(lo.kf, d) * jnp.exp(-jnp.abs(G - _turned(G, d)))
        kk = jnp.where(place, _rsum(lo.kf * p), kk)
        qk = jnp.where(place, _rsum(lo.qf * p), qk)
    lo.kk = jnp.where(lo.below, kk, 0.0)
    lo.attn = jnp.where(lo.visible, qk, 0.0).astype(dt)
    lo.kb = (lo.b * lo.F * lo.kf).astype(dt)
    lo.vb = (lo.b * lo.vf).astype(dt)
    lo.qF = (lo.F * lo.qf).astype(dt)
    lo.kE = (lo.E * lo.kf).astype(dt)
    return lo


def _inverses(fr, los):
    """``T = (I + L)^-1`` of each chunk of ``los``, once, in float32 and
    rounded as ``W`` and ``U`` read it — two chunks' side by side along
    lanes (gated_delta_rule.heads_side_by_side's reason: one [C, C] tile
    half-fills every register and every pass of the matrix unit)."""
    C, dt = fr.C, fr.dt
    Ls = [lo.b * lo.kk for lo in los]
    out = []
    for i in range(0, len(Ls) - len(Ls) % 2 if 2 * C <= 128 else 0, 2):
        X = _inverse(jnp.concatenate(Ls[i:i + 2], axis=1), *fr.pair
                     ).astype(dt)
        out += [X[:, :C], X[:, C:]]
    out += [_inverse(L, fr.eye, fr.masks, fr.mm).astype(dt)
            for L in Ls[len(out):]]
    return out


def _with_inverse(lo, T):
    """W and U, products with ``T``."""
    lo.T = T
    lo.W = _dot(T, lo.kb).astype(T.dtype)
    lo.U = _dot(T, lo.vb)
    return lo


# --------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, sc_ref, o_ref, *rest,
                C, nc, save, scales):
    if save:
        sin_ref, t_ref, *rest = rest
    (st_ref,) = rest
    dt = v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    fr = _Frame(C, dt)

    def walk(c, lo):
        St = st_ref[...]                                     # [dv, dk]
        H = St.astype(dt)
        v_new = (lo.U - _dot(lo.W, H, _NT)).astype(dt)
        o = _dot(lo.qF, H, _NT) + _dot(lo.attn, v_new)
        o_ref[0, c] = o.astype(dt)
        if save:
            sin_ref[0, 0, c] = St
            t_ref[0, 0, c] = lo.T
        st_ref[...] = St * lo.keep + _dot(v_new, lo.kE, _TN)

    # unrolled, two chunks at a time: what needs no state for both (the
    # inverse for both at once), then the state's pass down them; the next
    # pair's products that do not wait for the state fill this one's gaps
    for first in range(0, nc, 2):
        pair = range(first, min(first + 2, nc))
        los = [_local(fr, q_ref[0, c], k_ref[0, c], v_ref[0, c], g_ref[0, c],
                      sc_ref[0, 0, c], scales) for c in pair]
        for c, lo, T in zip(pair, los, _inverses(fr, los)):
            walk(c, _with_inverse(lo, T))


def _token_specs(nc, C, dk, dv, index):
    """q, k, v, g as [B, n, C, heads * width]: a block of ``nc`` chunks of
    one head."""
    return [pl.BlockSpec((1, nc, C, dk), index),
            pl.BlockSpec((1, nc, C, dk), index),
            pl.BlockSpec((1, nc, C, dv), index),
            pl.BlockSpec((1, nc, C, dk), index)]


def _forward(q, k, v, g, scalars, blocking, scales, save, interpret):
    B, n, C, _ = q.shape
    nc = blocking.chunks
    H = scalars.shape[1]
    dk, dv = q.shape[3] // H, v.shape[3] // H
    tok = lambda b, h, i: (b, i, 0, h)
    per = lambda b, h, i: (b, h, i, 0, 0)
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    out_specs = [pl.BlockSpec((1, nc, C, dv), tok)]
    if save:
        out_shape += [
            jax.ShapeDtypeStruct((B, H, n, dv, dk), jnp.float32),
            jax.ShapeDtypeStruct((B, H, n, C, C), v.dtype)]
        out_specs += [pl.BlockSpec((1, 1, nc, dv, dk), per),
                      pl.BlockSpec((1, 1, nc, C, C), per)]
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, C=C, nc=nc, save=save, scales=scales),
        grid=(B, H, n // nc), name="ds_kda_fwd", interpret=interpret,
        compiler_params=_compiler_params(blocking),
        in_specs=_token_specs(nc, C, dk, dv, tok)
        + [pl.BlockSpec((1, 1, nc, _ROWS, C), per)],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
    )(q, k, v, g, scalars)
    return out if save else out[0]


# -------------------------------------------------------------- backward
def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, do_ref, sc_ref, sin_ref, t_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dsc_ref, ds_ref,
                *, C, nc, dk, scales):
    dt = v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    fr = _Frame(C, dt)
    csum = lambda x: jnp.sum(x, axis=0, keepdims=True)
    last = lax.broadcasted_iota(jnp.int32, (C, dk), 0) == C - 1

    def chunk(step, _):
        c = nc - 1 - step
        lo = _with_inverse(
            _local(fr, q_ref[0, c], k_ref[0, c], v_ref[0, c], g_ref[0, c],
                   sc_ref[0, 0, c], scales), t_ref[0, 0, c])
        qf, kf, G, b, T = lo.qf, lo.kf, lo.G, lo.b, lo.T
        St = sin_ref[0, 0, c]
        H = St.astype(dt)
        v_new = (lo.U - _dot(lo.W, H, _NT)).astype(dt)
        do = do_ref[0, c]
        dSp = ds_ref[...]
        dSb = dSp.astype(dt)
        # o = qF H^T + attn v_new;  S' = keep S + v_new^T kE
        d_qF = _dot(do, H)
        dH = _dot(do, lo.qF, _TN)
        d_attn = _dot(do, v_new, _NT)
        dvn = (_dot(lo.attn, do, _TN) + _dot(lo.kE, dSb, _NT)).astype(dt)
        d_kE = _dot(v_new, dSb)
        d_keep = csum(dSp * St)
        # v_new = U - W H^T
        dW = (-_dot(dvn, H)).astype(dt)
        dH -= _dot(dvn, lo.W, _TN)
        ds_ref[...] = dSp * lo.keep + dH
        # W = T kb, U = T vb
        dT = _dot(dW, lo.kb, _NT) + _dot(dvn, lo.vb, _NT)
        d_kb = _dot(T, dW, _TN)
        d_vb = _dot(T, dvn, _TN)
        dv_ref[0, c] = (b * d_vb).astype(dt)
        d_beta = _rsum(d_kb * lo.F * kf) + _rsum(d_vb * lo.vf)
        # gradients of the normalised q and k: q's as a row, k's as a row
        # (beside exp(G_i - ..)) and as a key (beside exp(.. - G_j))
        dq = lo.F * d_qF
        dk_row = (b * lo.F) * d_kb
        dk_key = lo.E * d_kE
        # what the chunk's last row of G gathers: the factors to the end
        # and what the state keeps
        d_last = csum(kf * dk_key) + d_keep * lo.keep
        # T = (I + L)^-1, L = beta kk below the diagonal
        dL = -_dot(_dot(T, dT.astype(dt), _TN).astype(dt), T, _NT)
        dL = jnp.where(fr.strict, dL, 0.0)
        d_beta += _rsum(dL * lo.kk)
        d_kk = jnp.where(lo.below, dL * b, 0.0)
        d_qk = jnp.where(lo.visible, d_attn, 0.0)
        # A, level by level and offset by offset
        both = jnp.concatenate([d_kk, d_qk], axis=0)         # [2C, C]
        for s, at in _levels(C):
            e = _against_reference(G, s)
            x = (kf * e).astype(dt)
            xq = jnp.concatenate([x, (qf * e).astype(dt)], axis=0)
            dP = jnp.where(fr.masks2[at], both, 0.0).astype(dt)
            rows = _dot(dP, x)                               # [2C, dk]
            dk_row += rows[:C] * e
            dq += rows[C:] * e
            dk_key += _dot(dP, xq, _TN) * e
        for d, place in enumerate(fr.offsets):
            c_q = _rsum(jnp.where(place, d_qk, 0.0))
            if d == 0:
                dq += c_q * kf
                dk_key += c_q * qf
                continue
            c_k = _rsum(jnp.where(place, d_kk, 0.0))
            e = jnp.exp(-jnp.abs(G - _turned(G, d)))
            p = _turned(kf, d) * e
            dk_row += c_k * p
            dq += c_q * p
            dk_key += _turned((c_k * kf + c_q * qf) * e, -d)
        dG = qf * dq + kf * (dk_row - dk_key) + jnp.where(
            last, jnp.broadcast_to(d_last, G.shape), 0.0)
        dg_ref[0, c] = _dot_exact(fr.tri, dG, _TN)
        dq_ref[0, c] = _unnormalized(dq, q_ref[0, c], lo.rq,
                                     scales and scales[0], dt)
        dk_ref[0, c] = _unnormalized(dk_row + dk_key, k_ref[0, c], lo.rk,
                                     scales and scales[1], dt)
        # the column dbeta, turned along lanes: row 0 of the block
        dsc_ref[0, 0, c] = _dot_exact(_place(_ROWS, [d_beta], C), fr.eye_c,
                                      _TN)
        return 0

    lax.fori_loop(0, nc, chunk, 0, unroll=True)


def _backward(q, k, v, g, do, scalars, s_in, t, blocking, scales, interpret):
    B, n, C, _ = q.shape
    nc = blocking.chunks
    H = scalars.shape[1]
    dk, dv = q.shape[3] // H, v.shape[3] // H
    nb = n // nc
    tok = lambda b, h, i: (b, nb - 1 - i, 0, h)
    per = lambda b, h, i: (b, h, nb - 1 - i, 0, 0)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, C=C, nc=nc, dk=dk, scales=scales),
        grid=(B, H, nb), name="ds_kda_bwd", interpret=interpret,
        compiler_params=_compiler_params(blocking),
        in_specs=_token_specs(nc, C, dk, dv, tok) + [
            pl.BlockSpec((1, nc, C, dv), tok),
            pl.BlockSpec((1, 1, nc, _ROWS, C), per),
            pl.BlockSpec((1, 1, nc, dv, dk), per),
            pl.BlockSpec((1, 1, nc, C, C), per)],
        out_specs=_token_specs(nc, C, dk, dv, tok) + [
            pl.BlockSpec((1, 1, nc, _ROWS, C), per)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, jnp.float32),
                   jax.ShapeDtypeStruct(scalars.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
    )(q, k, v, g, do, scalars, s_in, t)


# ------------------------------------------------- the differentiable op
def _scalars(beta, seg, C):
    """Per-token scalars as the kernels read them, positions along lanes:
    [B, H, n, _ROWS, C] — beta, the document id, the masks "the document of
    the state that comes in" / "of the chunk's last token", then zeros.
    The kernels turn the columns they need down sublanes."""
    B, Sp, H = beta.shape
    n = Sp // C
    sc = seg.reshape(B, n, C)
    # the document the previous chunk ended in (chunk 0: no state yet)
    prev = jnp.concatenate([sc[:, :1, 0], sc[:, :-1, -1]], axis=1)
    per_head = lambda m: jnp.broadcast_to(
        m.astype(jnp.float32)[:, None], (B, H, n, C))
    rows = [jnp.transpose(beta, (0, 2, 1)).reshape(B, H, n, C),
            per_head(sc), per_head(sc == prev[..., None]),
            per_head(sc == sc[..., -1:])]
    rows += [jnp.zeros((B, H, n, C), jnp.float32)] * (_ROWS - len(rows))
    return jnp.stack(rows, axis=3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _kda(q, k, v, g, beta, seg, blocking, scales, interpret):
    C = blocking.chunk
    return _forward(*_by_chunk(C, q, k, v, g), _scalars(beta, seg, C),
                    blocking, scales, False, interpret)


def _kda_fwd(q, k, v, g, beta, seg, blocking, scales, interpret):
    C = blocking.chunk
    scalars = _scalars(beta, seg, C)
    o, s_in, t = _forward(*_by_chunk(C, q, k, v, g), scalars, blocking,
                          scales, True, interpret)
    return o, (q, k, v, g, scalars, s_in, t)


def _kda_bwd(blocking, scales, interpret, res, do):
    q, k, v, g, scalars, s_in, t = res
    B, Sp, H, _ = q.shape
    dq, dk, dv, dg, dsc = _backward(
        *_by_chunk(blocking.chunk, q, k, v, g, do.reshape(v.shape)), scalars,
        s_in, t, blocking, scales, interpret)
    # [B, H, n, C] -> [B, Sp, H]
    dbeta = jnp.transpose(dsc[:, :, :, 0].reshape(B, H, Sp), (0, 2, 1))
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape), dbeta, None)


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda_kernels(q, k, v, g, beta, seg, blocking: Blocking, scales=None,
                interpret=False):
    """``o`` [B, Sp, H, dv] — the arguments as ops/linear_attention.py
    prepared them: ``g`` [B, Sp, H, dk] and beta float32, ``seg`` int32, Sp
    a multiple of the chunk; q and k in ``v``'s dtype, or, with ``scales``
    = (q's, k's), as the layer made them: the kernels l2-normalise and
    scale them."""
    o = _kda(q, k, v, g, beta, seg, blocking,
             scales and tuple(float(s) for s in scales), bool(interpret))
    return o.reshape(v.shape)
