"""The flash kernels' tile body after PR 49: the forward selects once (what
the mask hid is ``exp(NEG_INF - m)`` = 0 by itself; a guard on one column
keeps rows with nothing visible yet at 0) and neither backward kernel
scales its tile (the scale rides the ``q`` the scores were made with, and
dQ's sum).  Held to the PARENT's kernels, kept below as the oracle (``p``
selected twice, ``ds_t * sm_scale`` a tile): ``o`` and ``lse`` to the
bit, the three gradients to float32 rounding and to the XLA mask within
the tolerances the kernels always had — on tiles of every kind: interior
ones (wholly below the diagonal and inside the window), ones a boundary
crosses, ones of other documents; the row the second select guarded; the
account's ``tiles`` against a count of the positional mask itself.  (A
body of their own for interior tiles, with no positional mask, was built
and measured and bought nothing: PERF.md section 6, PR 49.)"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.experimental import pallas as pl

from deepspeed_tpu.ops.attention import xla_causal_attention
from deepspeed_tpu.ops.pallas import ds_flash_attention as dsf
from deepspeed_tpu.ops.pallas.ds_flash_attention import ds_flash_attention
from deepspeed_tpu.telemetry import tracing

NEG_INF = dsf.NEG_INF


# ------------------------------------------------- the parent's kernels
# ds_flash_attention.py at 98b071e, lines 35-239, as they stood: the oracle

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
        q_ref, k_ref, v_ref, segq_ref, segk_ref, o_ref, lse_ref = refs
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
    # a window moves the loop's START: key blocks below it are never read
    first = (0 if window is None
             else _window_first_kblock(iq, block_q, block_k, window))

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
        p = jnp.exp(s - m_new)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
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
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
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

    def body(j, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.dslice(j * block_q, block_q)].astype(jnp.float32)
        do = do_ref[0, 0, pl.dslice(j * block_q, block_q)].astype(
            jnp.float32)
        lse = lse_ref[0, 0, :, pl.dslice(j * block_q, block_q)]  # [1, Bq]
        delta = delta_ref[0, 0, :, pl.dslice(j * block_q, block_q)]
        s_t = lax.dot_general(k, q * sm_scale, (((1,), (1,)), ((), ())),
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
        ds_t = p_t * (dp_t - delta) * sm_scale
        dk_new = dk + lax.dot_general(
            ds_t, q, (((1,), (0,)), ((), ())),
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
    sublane k dim of ds_t)."""
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         segq_ref, segk_ref, dq_ref) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref = refs
    iq = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    # rows staged whole-S (always lane-legal: S == array dim) and sliced
    # by the q-block index here — a [1, Bq] block would need bq % 128 == 0
    qs = pl.dslice(iq * block_q, block_q)
    lse = lse_ref[0, 0, :, qs]                           # [1, Bq]
    delta = delta_ref[0, 0, :, qs]
    q_pos = iq * block_q + lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 1)
    k_base = lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
    segq = segq_ref[0, :, qs] if has_seg else None       # [1, Bq]

    dq0 = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    n_kblocks = (_causal_kblocks(iq, block_q, block_k, seq_len)
                 if causal else seq_len // block_k)
    first = (0 if window is None
             else _window_first_kblock(iq, block_q, block_k, window))

    def body(j, dq):
        k = k_ref[0, 0, pl.dslice(j * block_k, block_k)].astype(jnp.float32)
        v = v_ref[0, 0, pl.dslice(j * block_k, block_k)].astype(jnp.float32)
        s_t = lax.dot_general(k, q * sm_scale, (((1,), (1,)), ((), ())),
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
        ds_t = p_t * (dp_t - delta) * sm_scale
        return dq + lax.dot_general(
            ds_t, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = lax.fori_loop(first, n_kblocks, body, dq0)
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _by_position_alone(kernel):
    """A parent's kernel under today's packed call (PR 71): the documents'
    table of loop bounds, the call's first operand, is not handed on — its
    loops are bounded by position and the other documents' tiles masked."""
    def call(*refs, has_seg, **static):
        return kernel(*(refs[1:] if has_seg else refs), has_seg=has_seg,
                      **static)
    return call


@pytest.fixture
def parent_kernels(monkeypatch):
    """``dsf._fwd`` / ``dsf._bwd_calls`` with the parent's three kernels:
    same grids, BlockSpecs and names, the old bodies."""
    def swap():
        for name, kernel in (("_fwd_kernel", _fwd_kernel),
                             ("_dkv_kernel", _dkv_kernel),
                             ("_dq_kernel", _dq_kernel)):
            monkeypatch.setattr(dsf, name, _by_position_alone(kernel))
    return swap


# ------------------------------------------------------------- the cases
S, BQ = 256, 64
#: in blocks of the q tile, as the cells' 512-blocks see them: no window,
#: window 512 (one block: no interior tile), window 1024 (two: one tile in
#: three interior) and a window between multiples
WINDOWS = {"causal": None, "w512": BQ, "w1024": 2 * BQ, "w_odd": 2 * BQ + 17}
BLOCKS = {"bq=bk": (BQ, BQ), "bq=2bk": (BQ, BQ // 2)}
WIDTHS = {"dk=dv": (32, 32), "mla_192_128": (192, 128)}


def _inputs(s, rep, dk, dv, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (1, s, rep, dk)),
            jax.random.normal(k[1], (1, s, 1, dk)),
            jax.random.normal(k[2], (1, s, 1, dv)),
            jax.random.normal(k[3], (1, s, rep, dv)))


def _segments(s, packed):
    """Documents cut inside blocks: the one from 3s/4 + 9 on sees its first
    three q-tiles' worth of key tiles wholly in other documents."""
    if not packed:
        return None
    cuts = np.array([s // 4 - 5, s // 2 + 1, 3 * s // 4 + 9])
    return jnp.asarray((np.arange(s)[None, :, None]
                        >= cuts[None, None, :]).sum(-1).astype(np.int32))


def _all_three(q, k, v, w, seg, blocks, window, causal=True):
    """o, lse and the float32 dq, dk, dv of one call of the kernels now in
    ``dsf``."""
    o, (_, _, _, _, lse) = dsf._fwd(q, k, v, seg, causal, None, *blocks,
                                    window=window)
    delta = jnp.sum(jnp.transpose(w * o, (0, 2, 1, 3)), axis=-1)
    grads = dsf._bwd_calls(q, k, v, w, lse, delta, seg, causal, None,
                           *blocks, keep_fp32=True, window=window)
    return o, lse, grads


def _einsum_grads(q, k, v, w, seg, window):
    rep = q.shape[2] // k.shape[2]
    fn = lambda q, k, v: jnp.sum(xla_causal_attention(
        q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2), seg,
        window) * w)
    return jax.grad(fn, (0, 1, 2))(q, k, v)


def _hold_to_the_parent(new, old):
    """``o`` and ``lse`` to the bit; the gradients to 1e-5 of the
    parent's largest entry."""
    np.testing.assert_array_equal(new[0], old[0])
    np.testing.assert_array_equal(new[1], old[1])
    for a, b in zip(new[2], old[2]):
        assert a.dtype == b.dtype == jnp.float32
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=1e-5 * float(jnp.abs(b).max()))


@functools.lru_cache(maxsize=None)
def _einsum_grads_of(packed, window, widths, rep):
    """The XLA mask's gradients of a case: they do not depend on the
    blocks, so the two block shapes of a case share one oracle."""
    q, k, v, w = _inputs(S, rep, *WIDTHS[widths])
    return _einsum_grads(q, k, v, w, _segments(S, packed), WINDOWS[window])


def tile_body_cases(packed):
    """The 64 cases' parameters with ``packed`` held: a file takes a half
    of them (``tests/test_flash_tile_bodies_packed.py`` the other), so
    that ``--dist loadfile`` gives them to two workers."""
    def decorate(fn):
        for name, values, ids in (
                ("packed", [packed], ["packed" if packed else "dense"]),
                ("window", sorted(WINDOWS), None),
                ("blocks", sorted(BLOCKS), None),
                ("widths", sorted(WIDTHS), None),
                ("rep", [1, 4], ["rep1", "rep4"])):
            fn = pytest.mark.parametrize(name, values, ids=ids)(fn)
        return fn
    return decorate


def hold_the_tile_body_to_the_parent(packed, window, blocks, widths, rep,
                                     parent_kernels):
    wanted = _einsum_grads_of(packed, window, widths, rep)
    blocks, window = BLOCKS[blocks], WINDOWS[window]
    q, k, v, w = _inputs(S, rep, *WIDTHS[widths])
    seg = _segments(S, packed)
    new = _all_three(q, k, v, w, seg, blocks, window)
    with tracing.step_account("test/tiles"):
        jax.eval_shape(lambda *a: ds_flash_attention(
            *a, segment_ids=seg, block_q=blocks[0], block_k=blocks[1],
            window=window), q, k, v)
    interior, boundary = tracing.flash_calls("test/tiles")[0]["tiles"]
    # the case holds the tiles it is here for: interior ones but under a
    # window of one block, boundary ones always
    assert boundary > 0 and (interior > 0) == (window != BQ)
    for a, b in zip(new[2], wanted):
        np.testing.assert_allclose(
            a, b, atol=2e-5 * (1 + float(jnp.abs(b).max())))
    parent_kernels()
    _hold_to_the_parent(new, _all_three(q, k, v, w, seg, blocks, window))


@tile_body_cases(packed=False)
def test_the_tile_body_is_the_parents_formula(packed, window, blocks, widths,
                                              rep, interpret_pallas,
                                              parent_kernels):
    hold_the_tile_body_to_the_parent(packed, window, blocks, widths, rep,
                                     parent_kernels)


@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
def test_a_chunk_with_no_causal_mask_is_all_interior(packed, interpret_pallas,
                                                     parent_kernels):
    """The ring's off-diagonal chunk: every tile interior; unpacked it has
    no mask at all and no guard, as it was."""
    q, k, v, w = _inputs(S, 2, 32, 32)
    seg = _segments(S, packed)
    new = _all_three(q, k, v, w, seg, (BQ, BQ // 2), None, causal=False)
    assert dsf.tile_counts(S, BQ, BQ // 2, causal=False) == [32, 0]
    parent_kernels()
    _hold_to_the_parent(new, _all_three(q, k, v, w, seg, (BQ, BQ // 2), None,
                                        causal=False))


def test_a_row_whose_first_tiles_are_another_documents(interpret_pallas,
                                                       parent_kernels):
    """A row with nothing visible SO FAR has ``m_new`` at NEG_INF, and
    ``exp(s - m_new)`` there is exp(0) on every hidden key.  The last
    document starts inside the last q-block, so its rows run three interior
    tiles of other documents' keys first: V under those tiles is loud, and
    none of it reaches the rows."""
    q, k, v, w = _inputs(S, 2, 32, 32)
    seg = _segments(S, True)
    start = 3 * S // 4 + 9
    assert start // BQ == 3 and int(seg[0, start]) != int(seg[0, start - 1])
    loud = jnp.where(jnp.arange(S)[None, :, None, None] < 3 * BQ, 1e6, v)
    new = _all_three(q, k, loud, w, seg, (BQ, BQ), None)
    want = xla_causal_attention(q, jnp.repeat(k, 2, axis=2),
                                jnp.repeat(v, 2, axis=2), seg)
    np.testing.assert_allclose(new[0][:, start:], want[:, start:], atol=2e-5)
    parent_kernels()
    _hold_to_the_parent(new, _all_three(q, k, loud, w, seg, (BQ, BQ), None))


def _forward_kernel_alone(kernel, q, k, v, seg_q, seg_k, block):
    """The forward call as ``dsf._fwd`` makes it, with the q side's and the
    k side's segment ids given apart (heads as batch, not causal); the
    documents' table says nothing: every q-block from key block 0."""
    from jax.experimental.pallas import tpu as pltpu
    B, s, hd = q.shape
    fn = functools.partial(kernel, sm_scale=hd ** -0.5, causal=False,
                           block_q=block, block_k=block, seq_len=s,
                           has_seg=True)
    whole = lambda b, h, i, *_: (b, h, 0, 0)
    tile = lambda b, h, i, *_: (b, h, i, 0)
    return pl.pallas_call(
        fn, grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, 1, s // block),
            in_specs=[pl.BlockSpec((1, 1, block, hd), tile),
                      pl.BlockSpec((1, 1, s, hd), whole),
                      pl.BlockSpec((1, 1, s, hd), whole),
                      pl.BlockSpec((1, block, 1),
                                   lambda b, h, i, *_: (b, i, 0)),
                      pl.BlockSpec((1, 1, s), lambda b, h, i, *_: (b, 0, 0))],
            out_specs=[pl.BlockSpec((1, 1, block, hd), tile),
                       pl.BlockSpec((1, 1, block, 1), tile)]),
        out_shape=[jax.ShapeDtypeStruct((B, 1, s, hd), q.dtype),
                   jax.ShapeDtypeStruct((B, 1, s, 1), jnp.float32)],
    )(jnp.zeros((B, s // block), jnp.int32), q[:, None], k[:, None],
      v[:, None], seg_q[:, :, None], seg_k[:, None, :])


def test_a_row_that_sees_nothing_at_all_is_zero(interpret_pallas):
    """The case the second select guarded, to its end: queries of a
    document no key belongs to (the two sides' ids given apart, as a ring's
    chunk would) keep ``l`` at 0 through every tile — weight exp(NEG_INF -
    0) = 0 by the guard on the subtrahend, where ``exp(s - m_new)`` alone
    is 1 a hidden key — so ``o`` is 0 and ``lse`` NEG_INF, and rows that do
    see keys are the parent's to the bit."""
    q, k, v, _ = (x[:, :, 0] for x in _inputs(S, 1, 32, 32))
    seg_k = _segments(S, True)
    seg_q = jnp.where(jnp.arange(S)[None] % 5 == 0, 7, seg_k)
    new = _forward_kernel_alone(dsf._fwd_kernel, q, k, v, seg_q, seg_k, BQ)
    old = _forward_kernel_alone(_by_position_alone(_fwd_kernel), q, k, v,
                                seg_q, seg_k, BQ)
    for a, b in zip(new, old):
        np.testing.assert_array_equal(a, b)
    blind = np.asarray(seg_q[0] == 7)
    assert not np.asarray(new[0])[0, 0, blind].any()
    assert (np.asarray(new[1])[0, 0, blind] == np.float32(NEG_INF)).all()
    assert np.isfinite(np.asarray(new[1])[0, 0, ~blind]).all()


@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
def test_a_sequence_of_one_block_has_no_interior_tile(packed,
                                                      interpret_pallas,
                                                      parent_kernels):
    q, k, v, w = _inputs(BQ, 4, 192, 128)
    seg = _segments(BQ, packed)
    assert dsf.tile_counts(BQ, BQ, BQ) == [0, 1]
    new = _all_three(q, k, v, w, seg, (BQ, BQ), None)
    for a, b in zip(new[2], _einsum_grads(q, k, v, w, seg, None)):
        np.testing.assert_allclose(
            a, b, atol=2e-5 * (1 + float(jnp.abs(b).max())))
    parent_kernels()
    _hold_to_the_parent(new, _all_three(q, k, v, w, seg, (BQ, BQ), None))


def _count_the_mask(s, bq, bk, causal, window):
    """[interior, boundary] tiles by the positional mask itself: a tile
    with a visible pair is visited; interior where every pair is."""
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = np.ones((s, s), bool) if not causal else (i >= j)
    if window is not None:
        seen &= i - j < window
    tiles = seen.reshape(s // bq, bq, s // bk, bk)
    whole, some = tiles.all((1, 3)), tiles.any((1, 3))
    return [int(whole.sum()), int((some & ~whole).sum())]


@pytest.mark.parametrize("s,bq,bk,causal,window", [
    (8192, 512, 512, True, None), (4096, 512, 512, True, None),
    (2048, 512, 512, True, None), (1024, 512, 512, True, None),
    (8192, 512, 512, True, 512), (8192, 512, 512, True, 1024),
    (2048, 512, 256, True, None), (2048, 512, 256, True, 700),
    (2048, 256, 128, True, 129), (1024, 128, 128, True, 1),
    (1024, 256, 64, True, 1023), (1024, 256, 128, False, None)])
def test_the_accounts_tiles_are_a_count_of_the_mask(s, bq, bk, causal,
                                                    window):
    """The tiles with a visible pair, and of them those the positional
    mask is all true on; the numbers the issue names at the cells'
    shapes."""
    want = _count_the_mask(s, bq, bk, causal, window)
    assert dsf.tile_counts(s, bq, bk, causal, window) == want
    if (bq, bk, causal, window) == (512, 512, True, None):
        assert want == {8192: [120, 16], 4096: [28, 8], 2048: [6, 4],
                        1024: [1, 2]}[s]
