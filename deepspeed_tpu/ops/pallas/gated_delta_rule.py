"""The chunked gated delta rule of ops/linear_attention.py as two Mosaic
kernels under one ``jax.custom_vjp``: ``ds_gdr_fwd`` and ``ds_gdr_bwd``.

Same algorithm and the same roundings as the XLA chunked form (its
docstring is the contract: operands of every product in ``v``'s dtype,
float32 accumulation, float32 decays, inverse and state), lowered so that

* the carried state ``S`` [dk, dv] float32 of each value head lives in
  VMEM scratch across the chunks of one sequence: the grid is (batch, key
  head, block of chunks) with the last axis sequential.  One grid step
  first does what needs no state for all its chunks at once — independent
  chains of small products that the scheduler interleaves — and then
  walks the state down them.  The backward walks the blocks in reverse
  and carries ``dS`` the same way;
* ``(I + L)^-1`` of each chunk is taken in the kernel, in float32, by
  block forward substitution: the inverses of the diagonal blocks of size
  ``s`` give those of size ``2s`` as ``X - X N X`` (``N`` the part of ``L``
  that joins the two halves), from ``s = 1`` to the chunk — five levels
  and ten products for a chunk of 64, no triangular solve.  The [C, C]
  work of the forward (masks, decays, the inverse) takes the value heads
  of a key head side by side along lanes (:func:`heads_side_by_side`);
* the decays and document masks of a chunk are built in registers from
  per-token scalars (``G`` = the in-chunk cumulative sum of ``g``,
  ``beta``, ``segment_ids`` and the two masks "same document as the state
  that comes in" / "as the chunk's last token": [B, S, Hv]-sized, made by
  XLA with positions along lanes; the kernel turns the ones it needs down
  sublanes by a product with an identity): no [.., C, C] array is written
  to HBM but ``T`` itself, in ``v``'s dtype, for the backward;
* the l2-normalisation of q and k, where the caller asks for it
  (``scales``), is done on the tiles the kernels hold, forward and
  backward, so that no float32 copy of q or k is written to HBM;
* the backward is written by hand.  The forward rule saves each chunk's
  incoming state (float32, what the scan's checkpoint kept) and ``T``
  (``v``'s dtype, as ``W`` and ``U`` read it); the backward recomputes the
  chunk-local products from q, k, v and walks ``dS`` down the sequence,
  with ``dL = -T^T dT T^T`` on the strict lower triangle in place of the
  transposed solve.  ``dg`` leaves the kernel as the gradient of ``G``
  and XLA takes its reverse cumulative sum.

One grid step takes the ``rep`` value heads of a key head (they share
``k k^T`` and ``q k^T``) and :func:`chunks_per_step` chunks — a rule of
shapes, dtypes and ``vmem.budget()``.
"""
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import vmem

L2NORM_EPS = 1e-6                          # ops/linear_attention.py l2norm
_NN, _NT, _TN = (1, 0), (1, 1), (0, 0)    # a @ b, a @ b^T, a^T @ b
_HIGHEST = lax.Precision.HIGHEST
_f32 = jnp.float32


def _dot(a, b, contract=_NN):
    """Matrix product with float32 accumulation; [C, .] operands, or
    [chunks, C, .] ones as one product per chunk."""
    lead = a.ndim - 2
    dims = (((contract[0] + lead,), (contract[1] + lead,)),
            (((0,), (0,)) if lead else ((), ())))
    return lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32,
        # fp32 operands: full-precision passes
        precision=_HIGHEST if a.dtype == jnp.float32 else None)


class Blocking(NamedTuple):
    chunk: int           # C, tokens of one chunk
    chunks: int          # chunks one grid step walks
    heads: int           # value heads one grid step takes (rep)
    vmem_bytes: int      # the buffers the backward call names


def supported(dk, dv, chunk, rep) -> bool:
    """Shapes the kernels take: lane-wide heads, a chunk that halves down
    to one token (the inverse's levels) and fills a bf16 tile, and no more
    value heads to a key head than the gradients' block has rows for."""
    return (dk % 128 == 0 and dv % 128 == 0 and chunk >= 16
            and chunk & (chunk - 1) == 0 and rep <= 4)


def heads_side_by_side(C, rep) -> int:
    """How many value heads of a key head the forward lays side by side
    along lanes for the [C, C] work (masks, decays, the inverse): as many
    as fill a 128-lane register and divide ``rep`` — two for a chunk of
    64, where one alone half-fills every register and every MXU pass."""
    return max(p for p in range(1, rep + 1)
               if rep % p == 0 and (p == 1 or p * C <= 128))


def _scalar_rows(rep):
    """Rows of the per-token scalars' array, a multiple of a float32
    tile's 8: per value head G, beta, G_last - G; the document id and the
    two masks."""
    return -(-(3 * rep + 3) // 8) * 8


def chunks_per_step(n, C, rep, dk, dv, itemsize) -> Blocking:
    """How many chunks one grid step walks: the most (of 8, 4, 2, 1
    dividing ``n``) whose double-buffered blocks and scratch — the
    backward's, the larger set — fit what a call is granted unasked, so
    that the per-step overhead is paid once per block and nothing asks for
    a raised limit; where even one chunk passes that, one chunk and the
    limit of ``vmem.limit_for``."""
    def need(nc):
        T = nc * C
        rows = T * (2 * dk + 2 * rep * dv) * itemsize      # q k v do
        outs = T * (2 * dk + rep * dv) * itemsize          # dq dk dv
        small = nc * (2 * _scalar_rows(rep) + 16) * 128 * 4
        saved = nc * rep * (dk * dv * 4 + C * 128 * itemsize)
        scratch = rep * T * (dk + dv + 128) * itemsize + 2 * T * dk * itemsize
        return (2 * (rows + outs + small + saved) + scratch
                + 3 * rep * dk * dv * 4)
    for nc in (8, 4, 2, 1):
        if n % nc == 0 and need(nc) <= vmem.UNASKED:
            return Blocking(C, nc, rep, need(nc))
    return Blocking(C, 1, rep, need(1))


def _compiler_params(blocking: Blocking):
    limit = vmem.limit_for(blocking.vmem_bytes)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        **({"vmem_limit_bytes": limit} if limit else {}))


# ------------------------------------------------------------ chunk maths
def _positions(C, p=1):
    """Row index, column index within its head and head index of a
    [C, p * C] array of ``p`` heads side by side."""
    ii = lax.broadcasted_iota(jnp.int32, (C, p * C), 0)
    lane = lax.broadcasted_iota(jnp.int32, (C, p * C), 1)
    return ii, lane & (C - 1), lane >> (C.bit_length() - 1)


def _identity(n, lead=()):
    eye = (lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == lax.broadcasted_iota(jnp.int32, (n, n), 1)).astype(_f32)
    return jnp.broadcast_to(eye, lead + (n, n)) if lead else eye


def _level_masks(ii, jj, C):
    """For s = 1, 2, .. C/2: where ``L`` joins the second half of a block
    of 2s rows to its first half."""
    masks, s = [], 1
    while s < C:
        sh = s.bit_length() - 1
        masks.append(((ii >> (sh + 1)) == (jj >> (sh + 1)))
                     & (((ii >> sh) & 1) == 1) & (((jj >> sh) & 1) == 0))
        s *= 2
    return masks


def _split(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(_f32)).astype(jnp.bfloat16)


def _block_diagonal(b, head, p):
    """[.., C, p * C] (p heads side by side) -> [.., p * C, p * C] with
    head i's block on the diagonal, so that ``a @ it`` multiplies head by
    head in one 128-wide pass."""
    if p == 1:
        return b
    return jnp.concatenate(
        [jnp.where(head == i, b, jnp.zeros_like(b)) for i in range(p)],
        axis=-2)


def _inverse_product(dt, head, p):
    """``a, b -> a @ b`` head by head for float32 [.., C, p * C] operands.
    The inverse is float32; its ten products are full-precision ones where
    ``v`` is float32, and three bfloat16 passes (high x high, high x low,
    low x high: 2^-17) where the result is rounded to bfloat16 (2^-9)
    before anything reads it."""
    if dt != jnp.bfloat16:
        return lambda a, b: _dot(a, _block_diagonal(b, head, p))

    def dot3(a, b):
        (ah, al), (bh, bl) = _split(a), _split(b)
        bh, bl = (_block_diagonal(x, head, p) for x in (bh, bl))
        return _dot(ah, bh) + (_dot(ah, bl) + _dot(al, bh))
    return dot3


def _inverse(L, eye, masks, mm):
    """(I + L)^-1 for strictly lower ``L`` [.., C, C] float32 (or several
    heads' side by side), by block forward substitution (module
    docstring); ``mm`` multiplies."""
    X = eye - jnp.where(masks[0], L, 0.0)
    for m in masks[1:]:
        N = jnp.where(m, L, 0.0)
        X = X - mm(X, mm(N, X))
    return X


def _normalized(x, scale, dt):
    """``x`` [.., C, d] as the products read it, in ``dt``: l2-normalised
    over d and scaled where ``scale`` is given (with ``r`` = 1 / |x|, for
    the backward), else as it is."""
    if scale is None:
        return x.astype(dt), None
    xf = x.astype(_f32)
    r = lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + L2NORM_EPS)
    return (xf * r * scale).astype(dt), r


def _unnormalized(dy, x, r, scale, dt):
    """The gradient of ``x`` from that of ``_normalized(x)`` (float32
    ``dy``, rounded to ``dt`` as at any dtype boundary)."""
    dy = dy.astype(dt)
    if scale is None:
        return dy.astype(x.dtype)
    dy, unit = dy.astype(_f32), x.astype(_f32) * r
    return ((scale * r) * (dy - unit * jnp.sum(dy * unit, axis=-1,
                                               keepdims=True))
            ).astype(x.dtype)


def _columns(rows, eye):
    """[.., W, C] (positions along lanes) -> [.., C, W] (down sublanes),
    exactly: a full-precision product with the identity."""
    return _dot(rows, eye, _TN)


def _shared(qc, kc, col, seg_r, ii, jj, rep):
    """What the value heads of one key head share in one chunk: k k^T,
    q k^T and "j is a position of i's document at or before i" — [.., C,
    p * C], the same for each of the p heads that ``seg_r`` [.., 1, p * C]
    lays side by side."""
    p = seg_r.shape[-1] // kc.shape[-2]
    kcat = jnp.concatenate([kc] * p, axis=-2) if p > 1 else kc
    seg_c = col[..., 3 * rep:3 * rep + 1]
    return (_dot(kc, kcat, _NT), _dot(qc, kcat, _NT),
            (seg_c == seg_r) & (ii >= jj))


def _head_scalars(col, r, rep):
    """Value head ``r``'s columns [.., C, 1]: G, beta, the factor from the
    incoming state to each position and from each position to the
    chunk's end."""
    lane = lambda i: col[..., i:i + 1]
    Gc, bc, GLc = lane(r), lane(rep + r), lane(2 * rep + r)
    frm = lane(3 * rep + 1) * jnp.exp(Gc)
    to = lane(3 * rep + 2) * jnp.exp(GLc)
    return Gc, bc, frm, to


def _last_row(x, width):
    """[1, width] holding the last entry of the column ``x`` [C, 1] (what
    the state keeps of itself over the chunk, for ``x`` = frm).  Mosaic
    broadcasts over lanes or over sublanes, not over both at once: the
    column's last tile goes over lanes and is summed over sublanes."""
    tail = x[x.shape[0] - 8:, :]
    is_last = lax.broadcasted_iota(jnp.int32, (8, 1), 0) == 7
    return jnp.sum(jnp.broadcast_to(jnp.where(is_last, tail, 0.0),
                                    (8, width)), axis=0, keepdims=True)


def _decay(Gc, Gr, visible):
    # the difference is taken only where it is <= 0
    return jnp.where(visible, jnp.exp(jnp.minimum(Gc - Gr, 0.0)), 0.0)


def _local(kc, vc, T, bc, frm, dt):
    """The products that need no state: beta-scaled keys and values, W and
    U, in ``v``'s dtype."""
    kb = (kc.astype(_f32) * (bc * frm)).astype(dt)
    vb = (vc.astype(_f32) * bc).astype(dt)
    return kb, vb, _dot(T, kb).astype(dt), _dot(T, vb).astype(dt)


# --------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, sc_ref, row_ref, o_ref, *rest,
                C, nc, rep, dk, dv, save, scales):
    if save:
        sin_ref, t_ref, *rest = rest
    s_ref, q_scr, k_scr, w_scr, u_scr, a_scr, col_scr = rest
    dt = v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    # what needs no state, for the block's chunks at once and for p value
    # heads side by side along lanes
    p = heads_side_by_side(C, rep)
    ii, jj, head = _positions(C, p)
    masks = _level_masks(ii, jj, C)
    strict = ii > jj
    eye = (ii == jj).astype(_f32)
    mm = _inverse_product(dt, head, p)
    q3, _ = _normalized(q_ref[0], scales and scales[0], dt)
    k3, _ = _normalized(k_ref[0], scales and scales[1], dt)
    q_scr[...], k_scr[...] = q3, k3
    col = _columns(sc_ref[0, 0], _identity(sc_ref.shape[-2], (nc,)))
    col_scr[...] = col
    row = row_ref[0, 0]
    kk, qk, visible = _shared(q3, k3, col, row[..., rep // p:, :], ii, jj,
                              rep)
    for first in range(0, rep, p):
        heads = [_head_scalars(col, r, rep) for r in range(first, first + p)]
        Gc, bc = heads[0][:2]
        for i in range(1, p):
            Gc = jnp.where(head == i, heads[i][0], Gc)
            bc = jnp.where(head == i, heads[i][1], bc)
        decay = _decay(Gc, row[..., first // p:first // p + 1, :], visible)
        T = _inverse(jnp.where(strict, bc * kk * decay, 0.0), eye, masks,
                     mm).astype(dt)
        attn = (qk * decay).astype(dt)
        for i, (_, bc, frm, _) in enumerate(heads):
            r, at = first + i, slice(i * C, (i + 1) * C)
            _, _, w_scr[r], u_scr[r] = _local(
                k3, v_ref[0, :, :, r * dv:(r + 1) * dv], T[..., at], bc, frm,
                dt)
            a_scr[r] = attn[..., at]
            if save:
                t_ref[0, 0, :, r] = T[..., at]

    # the state's pass down the block, chunk by chunk
    def chunk(c, _):
        qc, kc = q_scr[c], k_scr[c]
        for r in range(rep):
            _, _, frm, to = _head_scalars(col_scr[c], r, rep)
            S = s_ref[r]
            H = S.astype(dt)
            v_new = u_scr[r, c].astype(_f32) - _dot(w_scr[r, c], H)
            o = frm * _dot(qc, H) + _dot(a_scr[r, c], v_new.astype(dt))
            o_ref[0, c, :, r * dv:(r + 1) * dv] = o.astype(dt)
            if save:
                sin_ref[0, 0, c, r] = S
            s_ref[r] = S * _last_row(frm, dv) \
                + _dot(kc, (to * v_new).astype(dt), _TN)
        return 0

    # unrolled: the next chunk's products that do not wait for the state
    # fill the gaps of this one's
    lax.fori_loop(0, nc, chunk, 0, unroll=True)


def _token_specs(nc, C, dk, dv, rep, index):
    """q, k, v as [B, n, C, heads * width]: a block of ``nc`` chunks of
    one key head and its value heads."""
    return [pl.BlockSpec((1, nc, C, dk), index),
            pl.BlockSpec((1, nc, C, dk), index),
            pl.BlockSpec((1, nc, C, rep * dv), index)]


def _scalar_specs(nc, scalars, rows, index):
    return [pl.BlockSpec((1, 1, nc) + scalars.shape[3:], index),
            pl.BlockSpec((1, 1, nc) + rows.shape[3:], index)]


def _forward(q, k, v, scalars, rows, blocking, scales, save, interpret):
    B, n, C, _ = q.shape
    _, nc, rep, _ = blocking
    Hk = scalars.shape[1]
    dk, dv = q.shape[3] // Hk, v.shape[3] // (Hk * rep)
    tok = lambda b, h, i: (b, i, 0, h)
    per = lambda b, h, i: (b, h, i, 0, 0)
    per6 = lambda b, h, i: (b, h, i, 0, 0, 0)
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    out_specs = [pl.BlockSpec((1, nc, C, rep * dv), tok)]
    if save:
        out_shape += [
            jax.ShapeDtypeStruct((B, Hk, n, rep, dk, dv), jnp.float32),
            jax.ShapeDtypeStruct((B, Hk, n, rep, C, C), v.dtype)]
        out_specs += [pl.BlockSpec((1, 1, nc, rep, dk, dv), per6),
                      pl.BlockSpec((1, 1, nc, rep, C, C), per6)]
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, C=C, nc=nc, rep=rep, dk=dk, dv=dv,
                          save=save, scales=scales),
        grid=(B, Hk, n // nc), name="ds_gdr_fwd", interpret=interpret,
        compiler_params=_compiler_params(blocking),
        in_specs=_token_specs(nc, C, dk, dv, rep, tok)
        + _scalar_specs(nc, scalars, rows, per),
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((rep, dk, dv), jnp.float32),
                        pltpu.VMEM((nc, C, dk), v.dtype),        # q
                        pltpu.VMEM((nc, C, dk), v.dtype),        # k
                        pltpu.VMEM((rep, nc, C, dk), v.dtype),   # W
                        pltpu.VMEM((rep, nc, C, dv), v.dtype),   # U
                        pltpu.VMEM((rep, nc, C, C), v.dtype),    # attn
                        pltpu.VMEM((nc, C, scalars.shape[3]), jnp.float32)],
    )(q, k, v, scalars, rows)
    return out if save else out[0]


# -------------------------------------------------------------- backward
def _place(width, parts, C):
    """[C, width] float32 with ``parts[i]`` ([C, 1]) in lane i."""
    lane = lax.broadcasted_iota(jnp.int32, (C, width), 1)
    out = jnp.zeros((C, width), _f32)
    for i, part in enumerate(parts):
        out = jnp.where(lane == i, part, out)
    return out


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, sc_ref, row_ref, sin_ref,
                t_ref, dq_ref, dk_ref, dv_ref, dsc_ref, ds_ref,
                *, C, nc, rep, dk, dv, scales):
    dt = v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    p = heads_side_by_side(C, rep)
    ii, jj, _ = _positions(C)
    strict = ii > jj
    eye_w, eye_c = _identity(sc_ref.shape[-2]), _identity(C)
    last = lax.broadcasted_iota(jnp.int32, (C, 1), 0) == C - 1
    rsum = lambda x: jnp.sum(x, axis=1, keepdims=True)

    def chunk(step, _):
        c = nc - 1 - step
        qc, rq = _normalized(q_ref[0, c], scales and scales[0], dt)
        kc, rk = _normalized(k_ref[0, c], scales and scales[1], dt)
        kf = kc.astype(_f32)
        col, row = _columns(sc_ref[0, 0, c], eye_w), row_ref[0, 0, c]
        kk, qk, visible = _shared(qc, kc, col, row[rep // p:, :C], ii, jj,
                                  rep)
        dq = jnp.zeros((C, dk), _f32)
        dkc = jnp.zeros((C, dk), _f32)
        dkk = jnp.zeros((C, C), _f32)
        dGs, dbetas = [], []
        dsc_ref[0, 0, c, 8:] = jnp.zeros((8, C), _f32)
        for r in range(rep):
            Gc, bc, frm, to = _head_scalars(col, r, rep)
            Gr = row[r // p:r // p + 1, (r % p) * C:(r % p + 1) * C]
            decay = _decay(Gc, Gr, visible)
            T = t_ref[0, 0, c, r]
            S = sin_ref[0, 0, c, r]
            H = S.astype(dt)
            vc = v_ref[0, c, :, r * dv:(r + 1) * dv]
            vf = vc.astype(_f32)
            # the forward's values again, but for T and the state
            kb, vb, W, U = _local(kc, vc, T, bc, frm, dt)
            v_new = U.astype(_f32) - _dot(W, H)
            attn = (qk * decay).astype(dt)
            vt = (to * v_new).astype(dt)
            vn = v_new.astype(dt)
            do = do_ref[0, c, :, r * dv:(r + 1) * dv]
            dof = do.astype(_f32)
            # o = frm * (q H) + attn vn
            d_frm = rsum(dof * _dot(qc, H))
            dqH = (frm * dof).astype(dt)
            dq += _dot(dqH, H, _NT)
            dH = _dot(qc, dqH, _TN)
            d_attn = _dot(do, vn, _NT)
            d_vnew = _dot(attn, do, _TN)
            d_qk = (d_attn * decay).astype(dt)
            d_decay = d_attn * qk
            dq += _dot(d_qk, kc)
            dkc += _dot(d_qk, qc, _TN)
            # S' = keep S + k^T vt
            dSp = ds_ref[r]
            dSb = dSp.astype(dt)
            dkc += _dot(vt, dSb, _NT)
            d_vt = _dot(kc, dSb)
            d_keep = jnp.sum(rsum(dSp * S), axis=0, keepdims=True)
            d_vnew += to * d_vt
            d_to = rsum(d_vt * v_new)
            # v_new = U - W H
            dU = d_vnew.astype(dt)
            dW = (-_dot(dU, H, _NT)).astype(dt)
            dH -= _dot(W, dU, _TN)
            keep = frm[C - 1:C, :]
            ds_ref[r] = _last_row(frm, dv) * dSp + dH
            # W = T kb, U = T vb
            dT = _dot(dW, kb, _NT) + _dot(dU, vb, _NT)
            d_kb = _dot(T, dW, _TN)
            d_vb = _dot(T, dU, _TN)
            dkc += (bc * frm) * d_kb
            d_bf = rsum(d_kb * kf)
            d_beta = frm * d_bf + rsum(d_vb * vf)
            d_frm += bc * d_bf
            dv_ref[0, c, :, r * dv:(r + 1) * dv] = (bc * d_vb).astype(dt)
            # T = (I + L)^-1, L = beta kk decay below the diagonal
            dL = -_dot(_dot(T, dT.astype(dt), _TN).astype(dt), T, _NT)
            dL = jnp.where(strict, dL, 0.0)
            d_beta += rsum(dL * kk * decay)
            dkk += dL * bc * decay
            dD = (d_decay + dL * bc * kk) * decay
            d_last = jnp.sum(d_to * to, axis=0, keepdims=True) + d_keep * keep
            dGs.append(rsum(dD) + d_frm * frm - d_to * to
                       + jnp.where(last, d_last, 0.0))
            dbetas.append(d_beta)
            dsc_ref[0, 0, c, 8 + r:9 + r, :] = -jnp.sum(dD, axis=0,
                                                        keepdims=True)
        dkkb = dkk.astype(dt)
        dkc += _dot(dkkb, kc) + _dot(dkkb, kc, _TN)
        dq_ref[0, c] = _unnormalized(dq, q_ref[0, c], rq,
                                     scales and scales[0], dt)
        dk_ref[0, c] = _unnormalized(dkc, k_ref[0, c], rk,
                                     scales and scales[1], dt)
        # the columns dG (its part by rows of the chunk) and dbeta, turned
        # along lanes: rows 0 .. 2 rep of the gradients' block
        dsc_ref[0, 0, c, :8] = _dot(_place(8, dGs + dbetas, C), eye_c, _TN)
        return 0

    lax.fori_loop(0, nc, chunk, 0, unroll=True)


def _backward(q, k, v, do, scalars, rows, s_in, t, blocking, scales,
              interpret):
    B, n, C, _ = q.shape
    _, nc, rep, _ = blocking
    Hk = scalars.shape[1]
    dk, dv = q.shape[3] // Hk, v.shape[3] // (Hk * rep)
    nb = n // nc
    tok = lambda b, h, i: (b, nb - 1 - i, 0, h)
    per = lambda b, h, i: (b, h, nb - 1 - i, 0, 0)
    per6 = lambda b, h, i: (b, h, nb - 1 - i, 0, 0, 0)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, C=C, nc=nc, rep=rep, dk=dk, dv=dv,
                          scales=scales),
        grid=(B, Hk, nb), name="ds_gdr_bwd", interpret=interpret,
        compiler_params=_compiler_params(blocking),
        in_specs=_token_specs(nc, C, dk, dv, rep, tok) + [
            pl.BlockSpec((1, nc, C, rep * dv), tok)]
        + _scalar_specs(nc, scalars, rows, per) + [
            pl.BlockSpec((1, 1, nc, rep, dk, dv), per6),
            pl.BlockSpec((1, 1, nc, rep, C, C), per6)],
        out_specs=_token_specs(nc, C, dk, dv, rep, tok) + [
            pl.BlockSpec((1, 1, nc, 16, C), per)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((B, Hk, n, 16, C), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rep, dk, dv), jnp.float32)],
    )(q, k, v, do, scalars, rows, s_in, t)


# ------------------------------------------------- the differentiable op
def _scalars_and_rows(g, beta, seg, C, Hk):
    """Per-token scalars as the kernels read them, positions along lanes.
    ``scalars`` [B, Hk, n, W, C] (W = :func:`_scalar_rows`): per value head
    G, beta and G_last - G, then the document id and the masks "the
    state's document" / "the last token's document", then zeros — the
    kernels turn them down sublanes; ``rows`` [B, Hk, n, rep / p + 1,
    p * C]: G of p value heads side by side (:func:`heads_side_by_side`)
    and, last, the document id p times."""
    B, Sp, Hv = g.shape
    n, rep = Sp // C, Hv // Hk
    p = heads_side_by_side(C, rep)
    by_head = lambda a: jnp.transpose(a, (0, 2, 1)).reshape(B, Hk, rep, n, C)
    G = jnp.cumsum(by_head(g), axis=-1)
    sc = seg.reshape(B, n, C)
    # the document the previous chunk ended in (chunk 0: no state yet)
    prev = jnp.concatenate([sc[:, :1, 0], sc[:, :-1, -1]], axis=1)
    per_key = lambda m: jnp.broadcast_to(
        m.astype(jnp.float32)[:, None, None], (B, Hk, 1, n, C))
    seg_f = per_key(sc)
    scalars = jnp.concatenate(
        [G, by_head(beta), G[..., -1:] - G, seg_f,
         per_key(sc == prev[..., None]), per_key(sc == sc[..., -1:]),
         jnp.zeros((B, Hk, _scalar_rows(rep) - 3 * rep - 3, n, C),
                   jnp.float32)], axis=2)
    by_chunk = lambda a: jnp.transpose(a, (0, 1, 3, 2, 4))
    rows = jnp.concatenate(
        [by_chunk(G).reshape(B, Hk, n, rep // p, p * C),
         jnp.tile(by_chunk(seg_f), p)], axis=3)
    return by_chunk(scalars), rows


def _by_chunk(C, *arrays):
    """[B, Sp, heads, width] -> [B, n, C, heads * width]"""
    B, Sp = arrays[0].shape[:2]
    return tuple(a.reshape(B, Sp // C, C, -1) for a in arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _gdr(q, k, v, g, beta, seg, blocking, scales, interpret):
    scalars, rows = _scalars_and_rows(g, beta, seg, blocking.chunk,
                                      q.shape[2])
    return _forward(*_by_chunk(blocking.chunk, q, k, v), scalars, rows,
                    blocking, scales, False, interpret)


def _gdr_fwd(q, k, v, g, beta, seg, blocking, scales, interpret):
    scalars, rows = _scalars_and_rows(g, beta, seg, blocking.chunk,
                                      q.shape[2])
    o, s_in, t = _forward(*_by_chunk(blocking.chunk, q, k, v), scalars, rows,
                          blocking, scales, True, interpret)
    return o, (q, k, v, scalars, rows, s_in, t)


def _gdr_bwd(blocking, scales, interpret, res, do):
    q, k, v, scalars, rows, s_in, t = res
    B, Sp, Hk, _ = q.shape
    Hv = v.shape[2]
    C, rep = blocking.chunk, Hv // Hk
    dq, dk, dv, dsc = _backward(
        *_by_chunk(C, q, k, v, do.reshape(v.shape)), scalars, rows, s_in, t,
        blocking, scales, interpret)
    # [B, Hk, n, rep, C] -> [B, Hv, Sp] -> [B, Sp, Hv]
    by_token = lambda a: jnp.transpose(
        jnp.transpose(a, (0, 1, 3, 2, 4)).reshape(B, Hv, Sp), (0, 2, 1))
    dG = dsc[:, :, :, :rep] + dsc[:, :, :, 8:8 + rep]
    dg = jnp.flip(jnp.cumsum(jnp.flip(dG, -1), axis=-1), -1)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            by_token(dg), by_token(dsc[:, :, :, rep:2 * rep]), None)


_gdr.defvjp(_gdr_fwd, _gdr_bwd)


def gated_delta_rule_kernels(q, k, v, g, beta, seg, blocking: Blocking,
                             scales=None, interpret=False):
    """``o`` [B, Sp, Hv, dv] — the arguments as ops/linear_attention.py
    prepared them: g and beta float32, ``seg`` int32, Sp a multiple of the
    chunk; q and k in ``v``'s dtype, or, with ``scales`` = (q's, k's), as
    the layer made them: the kernels l2-normalise and scale them."""
    o = _gdr(q, k, v, g, beta, seg, blocking,
             scales and tuple(float(s) for s in scales), bool(interpret))
    return o.reshape(v.shape)
