"""From-scratch Pallas flash attention, forward AND backward, with
segment-id (sequence-packing) support.

Reference capability: the fused training transformer kernel
(csrc/transformer/softmax_kernels.cu + ds_transformer_cuda.cpp) — rebuilt
as a TPU kernel rather than translated.  Algorithm: FlashAttention-2
(online softmax forward saving per-row logsumexp; recompute-based
backward in two passes — dK/dV blocks looping over query tiles, dQ blocks
looping over key tiles).

Layouts: q [B, S, H, dk], k [B, S, KV, dk], v [B, S, KV, dv] (grouped-query
attention: KV may divide H — each group of H/KV query heads reads one KV
head, so GQA models stream KV at 1/group the HBM traffic instead of
repeating heads).  The value head may be of another width than the score
head, narrower (latent attention scores at 128 + 64 and reads values at
128) or wider (differential attention scores at 64 and reads a pair of
value heads, 128): ``o``, ``do``, ``dv`` are then ``dv`` wide in HBM and in
VMEM, ``q``, ``k``, ``dq``, ``dk`` stay ``dk`` wide, and no operand is
padded.
``segment_ids`` [B, S] int32 restricts attention to same-segment pairs —
packed-sequence training the stock wrapper lacked (pass None for a single
segment) — and bounds the kernels' tile loops: a q-block's loop starts at
the first key block that holds an id of its own, a key block's stops at
the last such q-block (``document_block_tables``), so other documents'
tiles are skipped, not masked; the mask stays in the tile body for the
tiles a boundary crosses and for ids in no order.  The [S, S] score matrix
never materialises in HBM; VMEM holds one [block_q, block_k] tile.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from deepspeed_tpu.ops.pallas import vmem

NEG_INF = -1e30


def _causal_kblocks(iq, block_q, block_k, seq_len):
    """#key-blocks a causal q-block row needs (whole blocks; block_q is a
    multiple of block_k by construction)."""
    return jnp.minimum((iq + 1) * block_q // block_k, seq_len // block_k)


def _window_first_kblock(iq, block_q, block_k, window):
    """The first key block a q-block row reaches under a window: its lowest
    query ``iq * block_q`` sees keys from ``iq * block_q - window + 1``."""
    return jnp.maximum(iq * block_q - (window - 1), 0) // block_k


def _fwd_kernel(*refs, sm_scale, causal, block_q, block_k, seq_len,
                has_seg, window=None):
    if has_seg:
        (first_ref, q_ref, k_ref, v_ref, segq_ref, segk_ref, o_ref,
         lse_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
    iq = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale       # [Bq, hd]
    q_pos = iq * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_base = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    segq = segq_ref[0] if has_seg else None              # [Bq, 1]

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32)
    n_kblocks = (_causal_kblocks(iq, block_q, block_k, seq_len)
                 if causal else seq_len // block_k)
    # a window moves the loop's START: key blocks below it are never read;
    # so do the documents (document_block_tables): the blocks before the
    # first that holds an id of this q-block's are other documents' whole
    first = (0 if window is None
             else _window_first_kblock(iq, block_q, block_k, window))
    if has_seg:
        first = jnp.maximum(first, first_ref[pl.program_id(0), iq])

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.dslice(j * block_k, block_k)].astype(jnp.float32)
        v = v_ref[0, 0, pl.dslice(j * block_k, block_k)].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        mask = None
        if has_seg:
            segk = segk_ref[0, :, pl.dslice(j * block_k, block_k)]  # [1,Bk]
            mask = segq == segk
        if causal:
            cm = q_pos >= (j * block_k + k_base)
            mask = cm if mask is None else (mask & cm)
        if window is not None:
            mask = mask & (q_pos - (j * block_k + k_base) < window)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        if mask is None:
            p = jnp.exp(s - m_new)
        else:
            # what the mask hid is exp(NEG_INF - m_new) = 0 with no second
            # select over the tile, but for a row with nothing visible SO
            # FAR (m_new still NEG_INF: a packed row whose first tiles are
            # other documents'), where it would be exp(0): such a row
            # subtracts 0 — a guard on one column, not on the tile
            p = jnp.exp(s - jnp.where(m_new > NEG_INF, m_new, 0.0))
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = lax.fori_loop(first, n_kblocks, body, (m0, l0, acc0))
    l_safe = jnp.where(l > 0, l, 1.0)
    o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.where(l > 0, m + jnp.log(l_safe), NEG_INF)


def _dkv_kernel(*refs, sm_scale, causal, block_q, block_k, seq_len, rep,
                has_seg, window=None):
    """Grid (B, S//block_k, H) with the Q-head dim INNERMOST: consecutive
    grid steps within one rep-group revisit the same dk/dv output block
    (index h//rep), which persists in VMEM — the kernel accumulates into
    it, so VMEM holds one head's tiles regardless of the GQA group size.
    dk/dv outputs are fp32 (exact accumulation across the group).

    Scores live TRANSPOSED ([Bk, Bq] — k along sublanes, q along lanes) so
    the per-q statistics (lse/delta) broadcast as cheap [1, Bq] rows: a
    per-q [Bq, 1] column layout tile-pads the lane dim x128 and blows the
    VMEM budget at long S (16k-fp32-class working sets)."""
    if has_seg:
        (last_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         segq_ref, segk_ref, dk_ref, dv_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref) = refs
    ik = pl.program_id(1)
    ih = pl.program_id(2)
    k = k_ref[0, 0].astype(jnp.float32)                  # [Bk, hd]
    v = v_ref[0, 0].astype(jnp.float32)
    k_pos = ik * block_k + lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 0)
    q_base = lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1)
    segk = segk_ref[0] if has_seg else None              # [Bk, 1]

    dk0 = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    dv0 = jnp.zeros((block_k, v.shape[-1]), jnp.float32)
    start = (ik * block_k) // block_q if causal else 0
    stop = seq_len // block_q
    if window is not None:
        # the last query that sees this block's last key is window - 1
        # past it: q blocks beyond are never read
        stop = jnp.minimum(
            stop, ((ik + 1) * block_k + window - 2) // block_q + 1)
    if has_seg:
        # nor the q blocks past the last that holds an id of this key
        # block's: later documents' whole
        stop = jnp.minimum(stop, last_ref[pl.program_id(0), ik] + 1)

    def body(j, carry):
        dk, dv = carry
        qs = q_ref[0, 0, pl.dslice(j * block_q, block_q)].astype(
            jnp.float32) * sm_scale
        do = do_ref[0, 0, pl.dslice(j * block_q, block_q)].astype(
            jnp.float32)
        lse = lse_ref[0, 0, :, pl.dslice(j * block_q, block_q)]  # [1, Bq]
        delta = delta_ref[0, 0, :, pl.dslice(j * block_q, block_q)]
        s_t = lax.dot_general(k, qs, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [Bk,Bq]
        mask = None
        if has_seg:
            segq = segq_ref[0, :, pl.dslice(j * block_q, block_q)]  # [1,Bq]
            mask = segk == segq
        if causal:
            cm = (j * block_q + q_base) >= k_pos
            mask = cm if mask is None else (mask & cm)
        if window is not None:
            mask = mask & ((j * block_q + q_base) - k_pos < window)
        p_t = jnp.exp(s_t - lse)
        if mask is not None:
            p_t = jnp.where(mask, p_t, 0.0)
        dv_new = dv + lax.dot_general(
            p_t, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp_t = lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
        # the scale rides the q the scores were made with, not the tile
        dk_new = dk + lax.dot_general(
            p_t * (dp_t - delta), qs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk, dv = lax.fori_loop(start, stop, body, (dk0, dv0))

    @pl.when(ih % rep == 0)
    def _init():
        dk_ref[0, 0] = dk
        dv_ref[0, 0] = dv

    @pl.when(ih % rep != 0)
    def _accum():
        dk_ref[0, 0] = dk_ref[0, 0] + dk
        dv_ref[0, 0] = dv_ref[0, 0] + dv


def _dq_kernel(*refs, sm_scale, causal, block_q, block_k, seq_len,
               has_seg, window=None):
    """Transposed score space, like _dkv_kernel (lse/delta as [1, Bq]
    rows); the dq accumulator itself stays [Bq, hd] (contraction over the
    sublane k dim of ds_t) and takes the scale once, after the loop."""
    if has_seg:
        (first_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         segq_ref, segk_ref, dq_ref) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref = refs
    iq = pl.program_id(2)
    qs = q_ref[0, 0].astype(jnp.float32) * sm_scale
    do = do_ref[0, 0].astype(jnp.float32)
    # rows staged whole-S (always lane-legal: S == array dim) and sliced
    # by the q-block index here — a [1, Bq] block would need bq % 128 == 0
    rows = pl.dslice(iq * block_q, block_q)
    lse = lse_ref[0, 0, :, rows]                         # [1, Bq]
    delta = delta_ref[0, 0, :, rows]
    q_pos = iq * block_q + lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 1)
    k_base = lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
    segq = segq_ref[0, :, rows] if has_seg else None     # [1, Bq]

    dq0 = jnp.zeros((block_q, qs.shape[-1]), jnp.float32)
    n_kblocks = (_causal_kblocks(iq, block_q, block_k, seq_len)
                 if causal else seq_len // block_k)
    first = (0 if window is None
             else _window_first_kblock(iq, block_q, block_k, window))
    if has_seg:
        first = jnp.maximum(first, first_ref[pl.program_id(0), iq])

    def body(j, dq):
        k = k_ref[0, 0, pl.dslice(j * block_k, block_k)].astype(jnp.float32)
        v = v_ref[0, 0, pl.dslice(j * block_k, block_k)].astype(jnp.float32)
        s_t = lax.dot_general(k, qs, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [Bk,Bq]
        mask = None
        if has_seg:
            segk = segk_ref[0, pl.dslice(j * block_k, block_k)]  # [Bk, 1]
            mask = segk == segq
        if causal:
            cm = q_pos >= (j * block_k + k_base)
            mask = cm if mask is None else (mask & cm)
        if window is not None:
            mask = mask & (q_pos - (j * block_k + k_base) < window)
        p_t = jnp.exp(s_t - lse)
        if mask is not None:
            p_t = jnp.where(mask, p_t, 0.0)
        dp_t = lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
        return dq + lax.dot_general(
            p_t * (dp_t - delta), k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = lax.fori_loop(first, n_kblocks, body, dq0)
    dq_ref[0, 0] = (dq * sm_scale).astype(dq_ref.dtype)


def _to_bhsd(x):
    return jnp.transpose(x, (0, 2, 1, 3))


def _document_bounds(xp, segment_ids, block_q, block_k):
    """:func:`document_block_tables` in ``xp`` (jax.numpy or numpy)."""
    rows, seq_len = segment_ids.shape

    def id_range(block):
        ids = segment_ids.reshape(rows, seq_len // block, block)
        return ids.min(axis=-1), ids.max(axis=-1)

    q_lo, q_hi = id_range(block_q)
    k_lo, k_hi = id_range(block_k)
    # [rows, q blocks, key blocks]: the two blocks' id intervals meet
    meets = ((k_lo[:, None, :] <= q_hi[:, :, None])
             & (k_hi[:, None, :] >= q_lo[:, :, None]))
    first_kblock = xp.argmax(meets, axis=2)
    last_qblock = meets.shape[1] - 1 - xp.argmax(meets[:, ::-1], axis=1)
    return first_kblock.astype(xp.int32), last_qblock.astype(xp.int32)


def document_block_tables(segment_ids, block_q, block_k):
    """What the documents add to the tile loops' bounds, once a call, in
    XLA: ``first_kblock`` [B, S / block_q], the first key block whose
    least-to-greatest id interval meets the q-block's, and ``last_qblock``
    [B, S / block_k], the last q-block whose interval meets the key
    block's.  A pair of equal ids lies in both blocks' intervals, so no
    block outside the bounds holds a visible pair, whatever the order of
    the ids (the mask in the tile body stays the arbiter); for the
    monotone runs a packer writes the bounds are exact: the block of the
    first key of the q-block's first query's document, and the block of
    the last query of the key block's last key's document."""
    return _document_bounds(jnp, segment_ids, block_q, block_k)


def document_block_bounds(segment_ids, block_q, block_k):
    """:func:`document_block_tables` on the host (numpy in, numpy out),
    for the steps of a family whose loss returns no counts, and for tests
    and scripts."""
    import numpy as np
    return _document_bounds(np, np.asarray(segment_ids), block_q, block_k)


def visited_tiles(first_kblock, seq_len, block_q, block_k, causal=True,
                  window=None):
    """``(visited, positional)``: the score tiles one head's forward pass
    visits over the rows of ``first_kblock`` (of either function above) —
    the kernels' own ``n_kblocks - first`` summed over the q-blocks, each
    from ``max(first by window, first_kblock)`` to its diagonal — and what
    position alone visits there (:func:`tile_counts` a row)."""
    import numpy as np
    iq = np.arange(seq_len // block_q)
    stop = (np.minimum((iq + 1) * block_q // block_k, seq_len // block_k)
            if causal else np.full_like(iq, seq_len // block_k))
    first = jnp.maximum if isinstance(first_kblock, jax.Array) else np.maximum
    if window is not None:
        first_kblock = first(
            np.maximum(iq * block_q - (window - 1), 0) // block_k,
            first_kblock)
    positional = sum(tile_counts(seq_len, block_q, block_k, causal, window))
    return ((stop - first_kblock).sum(),
            positional * first_kblock.shape[0])


#: what the documents did to a step's tile loops, among the sums that leave
#: the fused step beside its loss (``engine.step_load()``): the score tiles
#: one head's forward pass visited over the step's rows, a call shape of the
#: step's packed ``tracing.flash_calls`` rows each, and what position alone
#: would have visited there (those rows' ``tiles``)
VISITED_TILES = "flash/visited_tiles"
POSITIONAL_TILES = "flash/positional_tiles"
STEP_LOAD = (VISITED_TILES, POSITIONAL_TILES)


def step_tile_sums(segment_ids, calls):
    """``{VISITED_TILES, POSITIONAL_TILES}`` (int32 scalars) of a
    micro-batch's ``segment_ids`` [rows, S] over ``calls``, rows of
    ``tracing.flash_calls``: :func:`visited_tiles` by each packed row's
    blocks and window, from the tables its kernels' loops are bounded by —
    made here once a micro-batch, not once a layer.  ``{}`` where no row
    is a packed call over S keys."""
    segment_ids = segment_ids.astype(jnp.int32)
    seq_len = segment_ids.shape[1]
    tables, sums = {}, []       # a table a block shape, however many rows
    for row in calls:
        if row["packed"] and row["seq_len"] == seq_len:
            blocks = tuple(row["blocks"])
            if blocks not in tables:
                tables[blocks] = document_block_tables(segment_ids,
                                                       *blocks)[0]
            sums.append(visited_tiles(
                tables[blocks], seq_len, *blocks, row.get("causal", True),
                row.get("window")))
    if not sums:
        return {}
    return {VISITED_TILES: sum(v for v, _ in sums).astype(jnp.int32),
            POSITIONAL_TILES: jnp.int32(sum(p for _, p in sums))}


def _choose_blocks(seq_len, block_q, block_k):
    bq = min(block_q, seq_len)
    bk = min(block_k, seq_len)
    while bq > 1 and seq_len % bq:
        bq //= 2
    while bk > 1 and seq_len % bk:
        bk //= 2
    # the causal loop bounds assume block_q is a multiple of block_k
    while bq % bk and bk > 1:
        bk //= 2
    if seq_len % bq or seq_len % bk or bq % bk or bq < 8 or bk < 8:
        raise ValueError(
            f"ds_flash_attention: seq_len {seq_len} does not decompose "
            f"into >=8-sized blocks (got block_q={bq}, block_k={bk}); pad "
            "the sequence to a multiple of 8")
    return bq, bk


def _vmem_budget() -> int:
    """The device kind's (ops/pallas/vmem.py; S 8192 at head width 256,
    packed, stages 27 MB); DS_FLASH_VMEM_MB overrides it."""
    import os
    if os.environ.get("DS_FLASH_VMEM_MB"):
        return int(os.environ["DS_FLASH_VMEM_MB"]) << 20
    return vmem.budget()


def working_set_bytes(seq_len, head_dim, itemsize, block_q=512,
                      block_k=512, packed=False, v_head_dim=None) -> int:
    """One (batch, head) grid step's VMEM working set.

    The kernels stage the full-sequence K/V (forward/dq) or Q/dO (dk/dv
    pass) per grid step via whole-S BlockSpecs, so the dominant term is
    S*(hd_padded + v_hd_padded)*itemsize (the lane dim pads to a multiple
    of 128; ``v_head_dim``, the width of v, o and do, is ``head_dim``
    unless given); Pallas double-buffers the pipelined blocks, hence the
    factor 2 on top, plus the [1, S] fp32 lse/delta rows (sublane-padded
    x8) and the block tiles.  ``packed`` adds the dq pass's whole-S
    segment column, whose single-lane layout pads x128."""
    bq, bk = _choose_blocks(seq_len, block_q, block_k)
    hd_pad = -(-head_dim // 128) * 128
    v_pad = hd_pad if v_head_dim is None else -(-v_head_dim // 128) * 128
    # K+V (or Q+dO) whole-S
    full_kv = seq_len * (hd_pad + v_pad) * itemsize
    rows = 2 * 8 * seq_len * 4                       # lse+delta [1,S] fp32
    if packed:
        rows += seq_len * 128 * 4                    # dq segk [S,1] column
        # whole-S [1, S] int32 segment rows staged by the fwd/dkv/dq
        # passes (x8 sublane pad) — small next to the column term
        rows += 8 * seq_len * 4
    # in tiles + fp32 acc, at the wider of the two widths
    tiles = (bq + bk) * max(hd_pad, v_pad) * (itemsize + 2 * 4)
    return 2 * (full_kv + rows) + tiles


def vmem_fits(seq_len, head_dim, itemsize, block_q=512, block_k=512,
              budget_bytes=None, packed=False, v_head_dim=None):
    """Whether :func:`working_set_bytes` fits on-core.  The dispatch layer
    calls this before selecting the kernel — ``jax.eval_shape`` probes
    only shapes and would pass a 16k-fp32 sequence that Mosaic then
    rejects at compile time (advisor round 3).  The budget is the device
    kind's (ops/pallas/vmem.py; 12 MiB where the kind is not listed);
    DS_FLASH_VMEM_MB overrides it."""
    if budget_bytes is None:
        budget_bytes = _vmem_budget()
    try:
        return working_set_bytes(seq_len, head_dim, itemsize, block_q,
                                 block_k, packed, v_head_dim) <= budget_bytes
    except ValueError:
        return False


def _vmem_limit(q, block_q, block_k, packed, v=None):
    """The VMEM limit the three calls ask for (None: what a call is
    granted unasked); ``v`` where its head width is not ``q``'s."""
    return vmem.limit_for(working_set_bytes(
        q.shape[1], q.shape[3], q.dtype.itemsize, block_q, block_k, packed,
        None if v is None else v.shape[3]))


def _compiler_kw(q, block_q, block_k, packed, v=None):
    """``compiler_params`` for the three calls: a raised VMEM limit where
    the working set passes what a call is granted unasked, else nothing
    (and then the call is the one it always was)."""
    limit = _vmem_limit(q, block_q, block_k, packed, v)
    if limit is None:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=limit)}


def _grid_kw(packed, grid, in_specs, out_specs):
    """A call's grid and specs: as plain arguments — the call that always
    was — or, for a packed call, as the grid spec that hands its first
    operand (the documents' table of loop bounds) to the kernel and the
    index maps as scalars in SMEM."""
    if not packed:
        return {"grid": grid, "in_specs": in_specs, "out_specs": out_specs}
    from jax.experimental.pallas import tpu as pltpu
    return {"grid_spec": pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
        out_specs=out_specs)}


def window_k_tiles(window, block_q, block_k):
    """Key tiles the forward and dq loops visit for a q-block far enough
    from the sequence's start: from the tile of its first query's lowest
    key to the tile of its last query (``block_q`` a multiple of
    ``block_k``)."""
    return block_q // block_k + -(-(window - 1) // block_k)


def tile_counts(seq_len, block_q, block_k, causal=True, window=None):
    """``[interior, boundary]``: the score tiles a head's pass visits —
    the three passes visit the same: every tile with a visible pair — by
    what their position says of the positional mask.  An interior tile
    lies wholly below the diagonal and, under a window, wholly inside it
    (the mask is all true on it); a boundary tile is one the diagonal or
    the window's edge crosses.  The kernels run one body on both: a body
    of their own for interior tiles bought nothing on the chip (PERF.md
    section 6, PR 49), and the counts say how many tiles that finding is
    about."""
    bq, bk = _choose_blocks(seq_len, block_q, block_k)
    interior = boundary = 0
    for i in range(seq_len // bq):
        for j in range(seq_len // bk):
            # the least and the greatest (query - key) over the tile
            least, most = i * bq - (j + 1) * bk + 1, (i + 1) * bq - 1 - j * bk
            if not causal or (least >= 0
                              and (window is None or most < window)):
                interior += 1
            elif most >= 0 and (window is None or least < window):
                boundary += 1
    return [interior, boundary]


def _record_call(q, k, v, block_q, block_k, packed, causal, window=None,
                 kv_of=None):
    """This call's row of the step's account
    (``tracing.flash_calls``): shapes only, written while the
    step is traced; ``tiles`` is :func:`tile_counts`.  A windowed call's
    row also holds its ``window`` and the key tiles a q-block visits; a
    call whose keys and values are another layer's, that layer
    (``kv_of``); one that is not causal, ``causal`` False."""
    from deepspeed_tpu.telemetry.tracing import count_in_step
    B, S, H, hd = q.shape
    bq, bk = _choose_blocks(S, block_q, block_k)
    row = {"batch": B, "seq_len": S, "heads": H, "kv_heads": k.shape[2],
           "dk": hd, "dv": v.shape[3], "packed": packed,
           "blocks": [bq, bk],
           "vmem_limit_bytes": _vmem_limit(q, block_q, block_k, packed, v),
           "tiles": tile_counts(S, bq, bk, causal, window)}
    key = f"{B}x{S}x{H}x{k.shape[2]}x{hd}x{v.shape[3]}x{int(packed)}"
    if not causal:
        row.update(causal=False)
        key += "nc"     # every tile interior: a row of its own
    if window is not None:
        row.update(window=window,
                   k_tiles_per_q_block=window_k_tiles(window, bq, bk))
        key += f"w{window}"
    if kv_of is not None:
        row.update(kv_of=kv_of)
        key += f"kv{kv_of}"
    count_in_step(flash_calls={key: row})


def ds_flash_attention(q, k, v, segment_ids=None, causal=True,
                       sm_scale=None, block_q=512, block_k=512,
                       window=None, kv_of=None):
    """q [B, S, H, dk], k [B, S, KV, dk], v [B, S, KV, dv] -> [B, S, H,
    dv], ``dv`` any width; ``sm_scale`` defaults to ``dk ** -0.5``.  KV may
    divide H (grouped-query attention — KV streams once per group).
    ``window`` (causal only): query i attends keys j with ``i - j <
    window``; the three kernels' loops start (dK/dV's: stop) at the first
    tile the window reaches, so tiles outside it are never read, and the
    calls are named ``ds_flash_win_*``.  None, or a window no shorter than
    the sequence, is the causal program as it always was.
    ``segment_ids``: None or a [B, S] array (any integer or float dtype —
    cast to int32 here, ONCE, so the custom_vjp's float0 cotangent always
    matches an integer primal); packed sequences attend only within their
    own segment (non-differentiable — a proper custom_vjp argument, NOT a
    closure capture: closed-over tracers break under jit/scan train
    steps), and tiles that are another document's whole are never read
    (:func:`document_block_tables`; any ids, no order assumed: same id
    attends).  ``kv_of``: the layer whose keys and values these are, where
    it is not the caller's own — for the account's row only."""
    if segment_ids is not None:
        segment_ids = segment_ids.astype(jnp.int32)
    if window is not None:
        if not causal or window < 1:
            raise ValueError(
                f"ds_flash_attention: a window is a causal one of at least "
                f"one key (the query's own), not window={window} with "
                f"causal={causal}")
        if window >= q.shape[1]:
            window = None
    return _ds_flash(q, k, v, segment_ids, causal, sm_scale, block_q,
                     block_k, window, kv_of)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _ds_flash(q, k, v, segment_ids, causal, sm_scale, block_q, block_k,
              window, kv_of):
    o, _ = _fwd(q, k, v, segment_ids, causal, sm_scale, block_q, block_k,
                window=window, kv_of=kv_of)
    return o


def _ds_flash_fwd(q, k, v, segment_ids, causal, sm_scale, block_q,
                  block_k, window, kv_of):
    o, res = _fwd(q, k, v, segment_ids, causal, sm_scale, block_q, block_k,
                  window=window, kv_of=kv_of)
    return o, (res, segment_ids)


def _ds_flash_bwd(causal, sm_scale, block_q, block_k, window, kv_of,
                  res_seg, do):
    res, segment_ids = res_seg
    dq, dk, dv = _bwd_rule(segment_ids, causal, sm_scale, block_q,
                           block_k, res, do, window)
    if segment_ids is None:
        return dq, dk, dv, None
    import numpy as np
    dseg = np.zeros(segment_ids.shape, jax.dtypes.float0)
    return dq, dk, dv, dseg


_ds_flash.defvjp(_ds_flash_fwd, _ds_flash_bwd)


def _fwd(q, k, v, segment_ids, causal, sm_scale, block_q, block_k,
         interpret=None, window=None, kv_of=None):
    # interpret=None leaves the pallas default (and any test monkeypatch)
    # in force; True forces interpret mode (ring path off-TPU)
    _ikw = {} if interpret is None else {"interpret": interpret}
    B, S, H, hd = q.shape
    KV, hv = k.shape[2], v.shape[3]
    if H % KV:
        raise ValueError(f"ds_flash_attention: q heads {H} not a multiple "
                         f"of kv heads {KV}")
    if k.shape[3] != hd:
        raise ValueError(
            f"ds_flash_attention: q and k share the score width (q {hd}, k "
            f"{k.shape[3]}); only v may be of another (v {hv})")
    rep = H // KV
    sm = sm_scale if sm_scale is not None else hd ** -0.5
    bq, bk = _choose_blocks(S, block_q, block_k)
    qT, kT, vT = _to_bhsd(q), _to_bhsd(k), _to_bhsd(v)
    has_seg = segment_ids is not None
    _record_call(q, k, v, block_q, block_k, has_seg, causal, window, kv_of)
    # TPU-legal layouts for per-row operands (Mosaic requires the last two
    # block dims to divide (8, 128) or equal the array dims — a bare
    # [B, S] block fails): segment ids (int32, cast once in the public
    # wrapper) travel twice — as a [B, S, 1] column (q side) and a
    # [B, 1, S] row (k side) — so the in-kernel mask is a plain
    # (Bq,1)==(1,Bk) broadcast; lse rides a trailing singleton dim.
    # Unpacked batches drop the segment operands entirely.
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm, causal=causal, block_q=bq, block_k=bk,
        seq_len=S, has_seg=has_seg, window=window)
    operands = [qT, kT, vT]
    in_specs = [
        pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, *_: (b, h, i, 0)),
        pl.BlockSpec((1, 1, S, hd),
                     lambda b, h, i, *_: (b, h // rep, 0, 0)),
        pl.BlockSpec((1, 1, S, hv),
                     lambda b, h, i, *_: (b, h // rep, 0, 0)),
    ]
    if has_seg:
        seg = segment_ids
        first_kblock, _ = document_block_tables(seg, bq, bk)
        operands = [first_kblock] + operands + [seg[:, :, None],
                                                seg[:, None, :]]
        in_specs += [pl.BlockSpec((1, bq, 1), lambda b, h, i, *_: (b, i, 0)),
                     pl.BlockSpec((1, 1, S), lambda b, h, i, *_: (b, 0, 0))]
    oT, lse = pl.pallas_call(
        kernel,
        name="ds_flash_fwd" if window is None else "ds_flash_win_fwd",
        **_ikw,
        **_compiler_kw(q, block_q, block_k, has_seg, v),
        **_grid_kw(has_seg, (B, H, S // bq), in_specs, [
            pl.BlockSpec((1, 1, bq, hv), lambda b, h, i, *_: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, *_: (b, h, i, 0)),
        ]),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, hv), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
        ])(*operands)
    o = jnp.transpose(oT, (0, 2, 1, 3))
    return o, (q, k, v, o, lse[..., 0])


def _bwd_rule(segment_ids, causal, sm_scale, block_q, block_k, res, do,
              window=None):
    q, k, v, o, lse = res
    doT, oT = _to_bhsd(do), _to_bhsd(o)
    delta = jnp.sum(doT.astype(jnp.float32) * oT.astype(jnp.float32),
                    axis=-1)                              # [B, H, S]
    return _bwd_calls(q, k, v, do, lse, delta, segment_ids, causal,
                      sm_scale, block_q, block_k, window=window)


def _bwd_calls(q, k, v, do, lse, delta, segment_ids, causal, sm_scale,
               block_q, block_k, interpret=None, keep_fp32=False,
               window=None):
    """The two backward pallas calls, driven by EXPLICIT lse/delta — the
    ring-attention composition feeds the GLOBAL logsumexp and delta here
    so each K/V chunk's contribution is the exact global-softmax term.
    ``keep_fp32`` returns dq/dk/dv unrounded (fp32) so a caller that sums
    chunk contributions (the ring) accumulates exactly and casts once."""
    _ikw = {} if interpret is None else {"interpret": interpret}
    B, S, H, hd = q.shape
    KV, hv = k.shape[2], v.shape[3]
    rep = H // KV
    sm = sm_scale if sm_scale is not None else hd ** -0.5
    bq, bk = _choose_blocks(S, block_q, block_k)
    qT, kT, vT = _to_bhsd(q), _to_bhsd(k), _to_bhsd(v)
    doT = _to_bhsd(do)
    has_seg = segment_ids is not None
    _ckw = _compiler_kw(q, block_q, block_k, has_seg, v)
    # per-q stats travel as [B, H, 1, S] ROWS (sublane-padded x8, vs the
    # x128 lane padding a [..., S, 1] column layout would cost in both
    # VMEM and HBM); the backward kernels consume them transposed
    lse_r = lse[:, :, None, :]
    delta_r = delta[:, :, None, :]

    # dK/dV: Q-head-innermost grid; rep-group steps accumulate into the
    # shared (b, h//rep, i) fp32 output block
    dkv_kernel = functools.partial(
        _dkv_kernel, sm_scale=sm, causal=causal, block_q=bq, block_k=bk,
        seq_len=S, rep=rep, has_seg=has_seg, window=window)
    dkv_in = [qT, kT, vT, doT, lse_r, delta_r]
    dkv_specs = [
        pl.BlockSpec((1, 1, S, hd), lambda b, i, h, *_: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, bk, hd),
                     lambda b, i, h, *_: (b, h // rep, i, 0)),
        pl.BlockSpec((1, 1, bk, hv),
                     lambda b, i, h, *_: (b, h // rep, i, 0)),
        pl.BlockSpec((1, 1, S, hv), lambda b, i, h, *_: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, 1, S), lambda b, i, h, *_: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, 1, S), lambda b, i, h, *_: (b, h, 0, 0))]
    dq_in = [qT, kT, vT, doT, lse_r, delta_r]
    dq_specs = [
        pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, *_: (b, h, i, 0)),
        pl.BlockSpec((1, 1, S, hd),
                     lambda b, h, i, *_: (b, h // rep, 0, 0)),
        pl.BlockSpec((1, 1, S, hv),
                     lambda b, h, i, *_: (b, h // rep, 0, 0)),
        pl.BlockSpec((1, 1, bq, hv), lambda b, h, i, *_: (b, h, i, 0)),
        pl.BlockSpec((1, 1, 1, S), lambda b, h, i, *_: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, 1, S), lambda b, h, i, *_: (b, h, 0, 0)),
    ]
    if has_seg:
        seg = segment_ids
        seg_col, seg_row = seg[:, :, None], seg[:, None, :]
        first_kblock, last_qblock = document_block_tables(seg, bq, bk)
        # dkv: segq row slices [1, Bq] (whole-S row), segk column block
        # [Bk, 1] indexed by the k grid dim (no whole-S column staging)
        dkv_in = [last_qblock] + dkv_in + [seg_row, seg_col]
        dkv_specs += [pl.BlockSpec((1, 1, S), lambda b, i, h, *_: (b, 0, 0)),
                      pl.BlockSpec((1, bk, 1), lambda b, i, h, *_: (b, i, 0))]
        # dq: segq whole-S row (sliced [1, Bq] in-kernel), segk whole-S
        # column (sliced [Bk, 1] per key block in-kernel)
        dq_in = [first_kblock] + dq_in + [seg_row, seg_col]
        dq_specs += [pl.BlockSpec((1, 1, S), lambda b, h, i, *_: (b, 0, 0)),
                     pl.BlockSpec((1, S, 1), lambda b, h, i, *_: (b, 0, 0))]
    dkT, dvT = pl.pallas_call(
        dkv_kernel,
        name="ds_flash_bwd_dkv" if window is None
        else "ds_flash_win_bwd_dkv", **_ikw, **_ckw,
        **_grid_kw(has_seg, (B, S // bk, H), dkv_specs, [
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, i, h, *_: (b, h // rep, i, 0)),
            pl.BlockSpec((1, 1, bk, hv),
                         lambda b, i, h, *_: (b, h // rep, i, 0))]),
        out_shape=[jax.ShapeDtypeStruct((B, KV, S, hd), jnp.float32),
                   jax.ShapeDtypeStruct((B, KV, S, hv), jnp.float32)],
    )(*dkv_in)

    dq_kernel = functools.partial(
        _dq_kernel, sm_scale=sm, causal=causal, block_q=bq, block_k=bk,
        seq_len=S, has_seg=has_seg, window=window)
    dqT = pl.pallas_call(
        dq_kernel,
        name="ds_flash_bwd_dq" if window is None else "ds_flash_win_bwd_dq",
        **_ikw, **_ckw,
        **_grid_kw(has_seg, (B, H, S // bq), dq_specs, pl.BlockSpec(
            (1, 1, bq, hd), lambda b, h, i, *_: (b, h, i, 0))),
        out_shape=jax.ShapeDtypeStruct(
            (B, H, S, hd), jnp.float32 if keep_fp32 else q.dtype),
    )(*dq_in)

    dq = jnp.transpose(dqT, (0, 2, 1, 3))
    dk = jnp.transpose(dkT, (0, 2, 1, 3))
    dv = jnp.transpose(dvT, (0, 2, 1, 3))
    if not keep_fp32:
        dk, dv = dk.astype(k.dtype), dv.astype(v.dtype)
    return dq, dk, dv


# -------------------------------------------------------- ring composition
# Chunk-level entry points for blockwise context parallelism
# (sequence/ring_attention.py): the ring merges per-chunk (o, lse) pairs
# online in the forward and replays each chunk's backward against the
# GLOBAL lse/delta — exactly the flash decomposition, spread over the
# seq-axis ring instead of the in-kernel key loop.

def chunk_fwd(q, k, v, causal, sm_scale=None, block_q=512, block_k=512,
              interpret=None):
    """One K/V chunk's attention: -> (o [B,S,H,dv], lse [B,H,S]).
    Not differentiable on its own — the ring owns the VJP."""
    o, (_, _, _, _, lse) = _fwd(q, k, v, None, causal, sm_scale, block_q,
                                block_k, interpret=interpret)
    return o, lse


def chunk_bwd(q, k, v, do, lse, delta, causal, sm_scale=None, block_q=512,
              block_k=512, interpret=None):
    """One K/V chunk's gradient contributions given the GLOBAL softmax
    stats: -> (dq, dk, dv), all fp32 — the ring sums sp of these, so
    per-chunk rounding would defeat its fp32 travel accumulators."""
    return _bwd_calls(q, k, v, do, lse, delta, None, causal, sm_scale,
                      block_q, block_k, interpret=interpret, keep_fp32=True)

