"""Softmax attention over the key blocks each query picked for itself
(ops/sparse_attention.py ``selected_attention`` has the equations) as three
Mosaic kernels under one ``jax.custom_vjp``: ``ds_sel_fwd``,
``ds_sel_bwd_dq`` and ``ds_sel_bwd_dkv``.

FlashAttention-2 tiles (online softmax forward saving the log-sum-exp rows;
the backward recomputes a tile's scores from ``q``, ``k`` and those rows, in
two passes: dq tiles walking key tiles, dk/dv tiles walking query tiles) with
what the selection changes:

* **The mask is data.**  ``kept(q, key) = kept_columns[q, key_col[key]]`` and
  the key lies in ``[start of q's document, q]`` (documents are runs, so a
  causal key of another document is one before the query's document
  starts: the column product cannot tell two documents that share a column
  at their boundary apart, the start can).  On a tile it is one small
  product of zeros and ones, exact in one bfloat16 pass — which column each
  key lies in ``[keys, columns]`` against the columns each query keeps
  ``[columns, queries]`` — and two comparisons of positions; it becomes an
  additive ``0 / NEG_INF`` tile in VMEM before the softmax.  No score-sized
  array goes through HBM.
* **A key/value head's query heads share a tile.**  The selection is per
  (token, key/value head): one grid step takes a tile of tokens for *all*
  ``R`` query heads of a group, forms the mask once and walks the heads
  inside (their running maxima, sums and accumulators in VMEM scratch), so
  the mask's product costs ``2 / (2 R)`` of the forward's products, not as
  much again.
* **Scores live transposed** (``[keys, queries]``: keys along sublanes,
  queries along lanes) in all three kernels: a query's statistics are
  lane-dense rows ``[1, queries]``, reductions over keys run down sublanes,
  and with ``v`` (forward) and ``k`` (dq) handed over transposed every
  product is one the matrix unit takes as it is; ``o`` and ``dq`` leave as
  ``[head width, queries]`` tiles and XLA turns them.

Grid ``(batch, key/value head, outer tile, inner tile)``, the last axis
sequential.  A tile pair the diagonal leaves out is a grid step that does
nothing and fetches nothing (its block index is held at the last visited
pair's); nothing is skipped for being unselected or for being another
document's, so the device's work is a function of shapes alone
(:func:`visited_tiles`).

Same precision as the XLA form: products take their operands in ``q``'s
dtype and accumulate in float32; scores, softmax and its statistics are
float32.
"""
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import vmem

NEG_INF = -1e30
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_f32 = jnp.float32

#: (queries, keys) of a tile, chosen on a v5e at [1, 16384, 32 | 2, 128]
#: (scripts/sparse_attend_table.py; PERF.md section 6, PR 65): 256 x 512 and
#: 256 x 256 ran 44% and 79% slower a step, 512 x 1024 4.6% faster for a
#: third more VMEM and 256 more keys a query
TILES = (512, 512)


class Blocking(NamedTuple):
    block_q: int         # queries of a tile (lanes)
    block_k: int         # keys of a tile (sublanes)
    vmem_bytes: int      # the buffers the largest of the three calls names


def _need(bq, bk, R, hd, C, itemsize) -> int:
    """The dq call's buffers, the largest set of the three: ``q``, ``do``
    and ``dq`` tiles of all ``R`` heads (double-buffered) and the float32
    accumulator, the key side's tiles, the mask's two operands, the
    statistics' rows, and a tile's float32 working set."""
    heads = R * bq * hd
    return (2 * 3 * heads * itemsize + heads * 4
            + 2 * 3 * bk * hd * itemsize
            + 2 * (bk + bq) * C * itemsize
            + 2 * 3 * max(R, 8) * bq * 4
            + 6 * bk * bq * 4)


def supported(S, hd, block_size, interpret=False) -> bool:
    """Shapes the kernels take: whole columns, whole tiles of at least 8
    (:func:`blocking`) and, for Mosaic, lane-wide heads and tiles."""
    if S % block_size:
        return False
    bq, bk = _tiles(S)
    if min(bq, bk) < 8:
        return False
    return bool(interpret) or (hd % 128 == 0 and bq % 128 == 0
                               and bk % 128 == 0)


def _tiles(S):
    bq, bk = TILES
    while bq > 1 and S % bq:
        bq //= 2
    while bk > 1 and S % bk:
        bk //= 2
    return bq, bk


def blocking(S, R, hd, block_size, itemsize) -> Blocking:
    """:data:`TILES`, halved until they divide ``S``."""
    bq, bk = _tiles(S)
    return Blocking(bq, bk, _need(bq, bk, R, hd, S // block_size, itemsize))


def _last_k(i, bq, bk):
    """The last key tile query tile ``i`` reaches under causality."""
    return ((i + 1) * bq - 1) // bk


def _first_q(j, bq, bk):
    """The first query tile that reaches key tile ``j``."""
    return (j * bk) // bq


def visited_tiles(S, bq, bk) -> int:
    """(query tile, key tile) pairs a pass of one key/value head computes:
    every pair with a causal (query, key) in it — the three kernels visit
    the same, whatever was selected."""
    return sum(_last_k(i, bq, bk) + 1 for i in range(S // bq))


def visited_keys_per_query(S, bq, bk) -> float:
    """Keys the kernels multiply a query by, a mean over the sequence's
    queries: ``(S + block_q) / 2`` for square tiles."""
    return visited_tiles(S, bq, bk) * bk * bq / S


def _compiler_params(blocking: Blocking):
    limit = vmem.limit_for(blocking.vmem_bytes)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        **({"vmem_limit_bytes": limit} if limit else {}))


# ---------------------------------------------------------------- the tile
def _bias(incol_ref, kept_ref, start_ref, i, j, bq, bk):
    """[bk, bq] float32: 0 where the query (lane) sees the key (sublane),
    ``NEG_INF`` elsewhere."""
    hit = lax.dot_general(incol_ref[0], kept_ref[0, 0], _NN,
                          preferred_element_type=_f32)
    k_pos = j * bk + lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    q_pos = i * bq + lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    seen = (hit > 0.5) & (k_pos <= q_pos) & (k_pos >= start_ref[0])
    return jnp.where(seen, 0.0, NEG_INF)


def _scores(k, q, bias, scale):
    """A head's masked scores [bk, bq]; what the mask hides is ``NEG_INF``
    to the last bit (a score's magnitude is lost in it)."""
    return lax.dot_general(k, q, _NT, preferred_element_type=_f32) * scale \
        + bias


def _row(ref, r):
    """Row ``r`` of a statistics block [R, bq] as [1, bq]."""
    return ref[0, 0, pl.ds(r, 1), :]


def _probabilities(k, q, bias, scale, lse):
    """``exp(scores - lse)`` [bk, bq]; a query that saw nothing (``lse``
    ``NEG_INF``) reads zeros."""
    lse = jnp.where(lse > 0.5 * NEG_INF, lse, -NEG_INF)
    return jnp.exp(_scores(k, q, bias, scale) - lse)


# ----------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, vt_ref, incol_ref, kept_ref, start_ref,
                o_ref, lse_ref, m_sc, l_sc, acc_sc, *, scale, bq, bk, R):
    i, j = pl.program_id(2), pl.program_id(3)
    last = _last_k(i, bq, bk)

    @pl.when(j == 0)
    def _start():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, _f32)
        l_sc[...] = jnp.zeros(l_sc.shape, _f32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, _f32)

    @pl.when(j <= last)
    def _tile():
        bias = _bias(incol_ref, kept_ref, start_ref, i, j, bq, bk)
        k, vt = k_ref[0], vt_ref[0, 0]

        def head(r, carry):
            s = _scores(k, q_ref[0, 0, r], bias, scale)
            m = m_sc[r]
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            # a query with nothing visible so far subtracts 0: its hidden
            # scores are exp(NEG_INF), not exp(0)
            p = jnp.exp(s - jnp.where(m_new > NEG_INF, m_new, 0.0))
            alpha = jnp.exp(m - m_new)
            l_sc[r] = l_sc[r] * alpha + jnp.sum(p, axis=0, keepdims=True)
            acc_sc[r] = acc_sc[r] * alpha + lax.dot_general(
                vt, p.astype(vt.dtype), _NN, preferred_element_type=_f32)
            m_sc[r] = m_new
            return carry

        lax.fori_loop(0, R, head, 0)

    @pl.when(j == last)
    def _finish():
        def head(r, carry):
            l = l_sc[r]
            l_safe = jnp.where(l > 0, l, 1.0)
            o_ref[0, 0, r] = (acc_sc[r] / l_safe).astype(o_ref.dtype)
            lse_ref[0, 0, pl.ds(r, 1), :] = jnp.where(
                l > 0, m_sc[r] + jnp.log(l_safe), NEG_INF)
            return carry

        lax.fori_loop(0, R, head, 0)


def _mask_operands(bq, bk, C, key_side, query_side):
    """Specs of (which column each key lies in [B, S, C], the columns each
    query keeps [B, G, C, S], the first position of each query's document
    [B, 1, S]); ``key_side`` / ``query_side`` give a grid step's key and
    query tile."""
    return [
        pl.BlockSpec((1, bk, C), lambda b, g, x, y: (b, key_side(x, y), 0)),
        pl.BlockSpec((1, 1, C, bq),
                     lambda b, g, x, y: (b, g, 0, query_side(x, y))),
        pl.BlockSpec((1, 1, bq), lambda b, g, x, y: (b, 0, query_side(x, y)))]


def _forward(qT, k2, vT, incol, kept, start, blocking, interpret):
    """``qT`` [B, G, R, S, hd], ``k2`` [B, S, G * hd], ``vT`` [B, G, hd, S]
    -> (``oT`` [B, G, R, hd, S], ``lse`` [B, G, R, S])."""
    B, G, R, S, hd = qT.shape
    bq, bk = blocking.block_q, blocking.block_k
    C = incol.shape[2]
    qi = lambda i, j: i
    kj = lambda i, j: jnp.minimum(j, _last_k(i, bq, bk))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=hd ** -0.5, bq=bq, bk=bk, R=R),
        grid=(B, G, S // bq, S // bk), name="ds_sel_fwd",
        interpret=interpret, compiler_params=_compiler_params(blocking),
        in_specs=[
            pl.BlockSpec((1, 1, R, bq, hd),
                         lambda b, g, i, j: (b, g, 0, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, g, i, j: (b, kj(i, j), g)),
            pl.BlockSpec((1, 1, hd, bk),
                         lambda b, g, i, j: (b, g, 0, kj(i, j))),
        ] + _mask_operands(bq, bk, C, kj, qi),
        out_specs=[
            pl.BlockSpec((1, 1, R, hd, bq),
                         lambda b, g, i, j: (b, g, 0, 0, i)),
            pl.BlockSpec((1, 1, R, bq), lambda b, g, i, j: (b, g, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((B, G, R, hd, S), qT.dtype),
                   jax.ShapeDtypeStruct((B, G, R, S), _f32)],
        scratch_shapes=[pltpu.VMEM((R, 1, bq), _f32),
                        pltpu.VMEM((R, 1, bq), _f32),
                        pltpu.VMEM((R, hd, bq), _f32)],
    )(qT, k2, vT, incol, kept, start)


# ---------------------------------------------------------------- backward
def _dq_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, kt_ref, v_ref,
               incol_ref, kept_ref, start_ref, dq_ref, dq_sc, *, scale, bq,
               bk, R):
    i, j = pl.program_id(2), pl.program_id(3)
    last = _last_k(i, bq, bk)

    @pl.when(j == 0)
    def _start():
        dq_sc[...] = jnp.zeros(dq_sc.shape, _f32)

    @pl.when(j <= last)
    def _tile():
        bias = _bias(incol_ref, kept_ref, start_ref, i, j, bq, bk)
        k, kt, v = k_ref[0], kt_ref[0, 0], v_ref[0]

        def head(r, carry):
            p = _probabilities(k, q_ref[0, 0, r], bias, scale,
                               _row(lse_ref, r))
            dp = lax.dot_general(v, do_ref[0, 0, r], _NT,
                                 preferred_element_type=_f32)
            ds = p * (dp - _row(delta_ref, r))
            dq_sc[r] += lax.dot_general(kt, ds.astype(kt.dtype), _NN,
                                        preferred_element_type=_f32)
            return carry

        lax.fori_loop(0, R, head, 0)

    @pl.when(j == last)
    def _finish():
        def head(r, carry):
            dq_ref[0, 0, r] = (dq_sc[r] * scale).astype(dq_ref.dtype)
            return carry

        lax.fori_loop(0, R, head, 0)


def _dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, incol_ref,
                kept_ref, start_ref, dk_ref, dv_ref, dk_sc, dv_sc, *, scale,
                bq, bk, R, nq):
    j, i = pl.program_id(2), pl.program_id(3)

    @pl.when(i == 0)
    def _start():
        dk_sc[...] = jnp.zeros(dk_sc.shape, _f32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, _f32)

    @pl.when(i >= _first_q(j, bq, bk))
    def _tile():
        bias = _bias(incol_ref, kept_ref, start_ref, i, j, bq, bk)
        k, v = k_ref[0], v_ref[0]

        def head(r, carry):
            dk, dv = carry
            q, do = q_ref[0, 0, r], do_ref[0, 0, r]
            p = _probabilities(k, q, bias, scale, _row(lse_ref, r))
            dv = dv + lax.dot_general(p.astype(do.dtype), do, _NN,
                                      preferred_element_type=_f32)
            dp = lax.dot_general(v, do, _NT, preferred_element_type=_f32)
            ds = p * (dp - _row(delta_ref, r))
            dk = dk + lax.dot_general(ds.astype(q.dtype), q, _NN,
                                      preferred_element_type=_f32)
            return dk, dv

        dk, dv = lax.fori_loop(
            0, R, head, (jnp.zeros(dk_sc.shape, _f32),
                         jnp.zeros(dv_sc.shape, _f32)))
        dk_sc[...] += dk
        dv_sc[...] += dv

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _backward(qT, doT, lse, delta, k2, kT, v2, incol, kept, start, blocking,
              interpret):
    """-> (``dqT`` [B, G, R, hd, S], ``dk2``, ``dv2`` [B, S, G * hd])."""
    B, G, R, S, hd = qT.shape
    bq, bk = blocking.block_q, blocking.block_k
    C = incol.shape[2]
    nq, nk = S // bq, S // bk
    common = dict(interpret=interpret,
                  compiler_params=_compiler_params(blocking))
    static = dict(scale=hd ** -0.5, bq=bq, bk=bk, R=R)

    def query_side(at):
        """Specs of ``q``, ``do`` tiles of every head and their rows."""
        heads = pl.BlockSpec((1, 1, R, bq, hd),
                             lambda b, g, x, y: (b, g, 0, at(x, y), 0))
        rows = pl.BlockSpec((1, 1, R, bq),
                            lambda b, g, x, y: (b, g, 0, at(x, y)))
        return [heads, heads, rows, rows]

    # dq: a query tile walks its key tiles
    qi = lambda i, j: i
    kj = lambda i, j: jnp.minimum(j, _last_k(i, bq, bk))
    rows_of = pl.BlockSpec((1, bk, hd), lambda b, g, i, j: (b, kj(i, j), g))
    dqT = pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        grid=(B, G, nq, nk), name="ds_sel_bwd_dq", **common,
        in_specs=query_side(qi) + [
            rows_of,
            pl.BlockSpec((1, 1, hd, bk),
                         lambda b, g, i, j: (b, g, 0, kj(i, j))),
            rows_of] + _mask_operands(bq, bk, C, kj, qi),
        out_specs=pl.BlockSpec((1, 1, R, hd, bq),
                               lambda b, g, i, j: (b, g, 0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((B, G, R, hd, S), qT.dtype),
        scratch_shapes=[pltpu.VMEM((R, hd, bq), _f32)],
    )(qT, doT, lse, delta, k2, kT, v2, incol, kept, start)

    # dk, dv: a key tile walks the query tiles from its own on
    kj = lambda j, i: j
    qi = lambda j, i: jnp.maximum(i, _first_q(j, bq, bk))
    rows_of = pl.BlockSpec((1, bk, hd), lambda b, g, j, i: (b, j, g))
    dk2, dv2 = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, **static),
        grid=(B, G, nk, nq), name="ds_sel_bwd_dkv", **common,
        in_specs=query_side(qi) + [rows_of, rows_of]
        + _mask_operands(bq, bk, C, kj, qi),
        out_specs=[rows_of, rows_of],
        out_shape=[jax.ShapeDtypeStruct(k2.shape, k2.dtype),
                   jax.ShapeDtypeStruct(v2.shape, v2.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, hd), _f32),
                        pltpu.VMEM((bk, hd), _f32)],
    )(qT, doT, lse, delta, k2, v2, incol, kept, start)
    return dqT, dk2, dv2


# ------------------------------------------------- the differentiable op
def _by_group(x, G):
    """[B, S, H, hd] -> [B, G, R, S, hd]"""
    B, S, H, hd = x.shape
    return jnp.transpose(x.reshape(B, S, G, H // G, hd), (0, 2, 3, 1, 4))


def _from_group(xT):
    """[B, G, R, hd, S] -> [B, S, H, hd]"""
    B, G, R, hd, S = xT.shape
    return jnp.transpose(xT, (0, 4, 1, 2, 3)).reshape(B, S, G * R, hd)


def _flat(x):
    return x.reshape(x.shape[:2] + (-1,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _selected(q, k, v, incol, kept, start, blocking, interpret):
    return _selected_fwd(q, k, v, incol, kept, start, blocking,
                         interpret)[0]


def _selected_fwd(q, k, v, incol, kept, start, blocking, interpret):
    G = k.shape[2]
    oT, lse = _forward(_by_group(q, G), _flat(k),
                       jnp.transpose(v, (0, 2, 3, 1)), incol, kept, start,
                       blocking, interpret)
    o = _from_group(oT)
    return o, (q, k, v, o, lse, incol, kept, start)


def _selected_bwd(blocking, interpret, res, do):
    q, k, v, o, lse, incol, kept, start = res
    B, S, H, hd = q.shape
    G = k.shape[2]
    delta = jnp.sum(do.astype(_f32) * o.astype(_f32), axis=-1)   # [B, S, H]
    delta = jnp.transpose(delta.reshape(B, S, G, H // G), (0, 2, 3, 1))
    dqT, dk2, dv2 = _backward(
        _by_group(q, G), _by_group(do.astype(q.dtype), G), lse, delta,
        _flat(k), jnp.transpose(k, (0, 2, 3, 1)), _flat(v), incol, kept,
        start, blocking, interpret)
    return (_from_group(dqT), dk2.reshape(k.shape), dv2.reshape(v.shape),
            None, None, None)


_selected.defvjp(_selected_fwd, _selected_bwd)


def selected_attention_kernels(q, k, v, incol, kept, start,
                               blocking: Blocking, interpret=False):
    """``o`` [B, S, H, hd] in ``q``'s dtype.  ``q`` [B, S, H, hd], ``k``,
    ``v`` [B, S, G, hd]; the mask's three operands as
    ops/sparse_attention.py prepared them: ``incol`` [B, S, C] (1 at the
    column each key lies in) and ``kept`` [B, G, C, S] (1 at the columns
    each query keeps), zeros and ones in ``q``'s dtype, and ``start`` [B,
    1, S] int32, the first position of each query's document.
    Differentiable in ``q``, ``k`` and ``v``."""
    return _selected(q, k, v, incol, kept, start, blocking, bool(interpret))
