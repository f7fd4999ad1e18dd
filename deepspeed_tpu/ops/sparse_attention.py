"""Block-sparse attention (reference: deepspeed/ops/sparse_attention/ —
``SparsityConfig`` hierarchy in sparsity_config.py, ``SparseSelfAttention``,
Triton block matmul/softmax kernels).

The layouts (fixed / bigbird / bslongformer / variable) are faithful
reimplementations of the reference's mask construction.  Two compute
paths, selected by ``impl``:

* ``dense`` — block-masked dense attention: the [S, S] score tile is
  MXU-friendly and XLA folds the block mask into the softmax fusion; the
  right trade below ~16k tokens.
* ``pallas`` — the from-scratch block-skipping kernel
  (ops/pallas/block_sparse_attention.py): masked blocks are never DMA'd or
  multiplied, so cost scales with layout density — the long-sequence path.
"""
import random
from dataclasses import dataclass
from typing import List, Optional

import jax
import numpy as np
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.telemetry.tracing import count_in_step

NEG_INF = -1e30


class SparsityConfig:
    """Base layout builder (reference sparsity_config.py:22)."""

    def __init__(self, num_heads: int, block: int = 16,
                 different_layout_per_head: bool = False):
        self.num_heads = num_heads
        self.block = block
        self.different_layout_per_head = different_layout_per_head

    def setup_layout(self, seq_len: int) -> np.ndarray:
        if seq_len % self.block != 0:
            raise ValueError(
                f"seq_len {seq_len} not divisible by block {self.block}")
        n = seq_len // self.block
        return np.zeros((self.num_heads, n, n), dtype=np.int64)

    def check_and_propagate_first_head_layout(self, layout: np.ndarray):
        if not self.different_layout_per_head:
            layout[1:] = layout[0]
        return layout

    def make_layout(self, seq_len: int) -> np.ndarray:
        raise NotImplementedError


class DenseSparsityConfig(SparsityConfig):
    """All blocks attended — dense baseline (reference :105)."""

    def make_layout(self, seq_len: int) -> np.ndarray:
        layout = self.setup_layout(seq_len)
        layout[:] = 1
        return layout


class FixedSparsityConfig(SparsityConfig):
    """Local windows + fixed global columns (reference :135
    FixedSparsityConfig: num_local_blocks window, num_global_blocks summary
    columns chosen from each window's tail)."""

    def __init__(self, num_heads: int, block: int = 16,
                 different_layout_per_head: bool = False,
                 num_local_blocks: int = 4, num_global_blocks: int = 1,
                 attention: str = "bidirectional",
                 horizontal_global_attention: bool = False):
        super().__init__(num_heads, block, different_layout_per_head)
        self.num_local_blocks = num_local_blocks
        self.num_global_blocks = num_global_blocks
        self.attention = attention
        self.horizontal_global_attention = horizontal_global_attention

    def make_layout(self, seq_len: int) -> np.ndarray:
        layout = self.setup_layout(seq_len)
        n = layout.shape[1]
        for h in range(self.num_heads if self.different_layout_per_head
                       else 1):
            # local windows
            for start in range(0, n, self.num_local_blocks):
                end = min(start + self.num_local_blocks, n)
                layout[h, start:end, start:end] = 1
            # global columns: last num_global_blocks of each window
            for start in range(0, n, self.num_local_blocks):
                end = min(start + self.num_local_blocks, n)
                g0 = max(end - self.num_global_blocks, start)
                layout[h, :, g0:end] = 1
                if self.horizontal_global_attention:
                    layout[h, g0:end, :] = 1
        if self.attention == "unidirectional":
            layout = np.tril(layout)
        return self.check_and_propagate_first_head_layout(layout)


class BigBirdSparsityConfig(SparsityConfig):
    """Random + sliding window + global blocks (reference :375)."""

    def __init__(self, num_heads: int, block: int = 16,
                 different_layout_per_head: bool = False,
                 num_random_blocks: int = 1, num_sliding_window_blocks: int = 3,
                 num_global_blocks: int = 1, attention: str = "bidirectional",
                 seed: int = 0):
        super().__init__(num_heads, block, different_layout_per_head)
        self.num_random_blocks = num_random_blocks
        self.num_sliding_window_blocks = num_sliding_window_blocks
        self.num_global_blocks = num_global_blocks
        self.attention = attention
        self.seed = seed

    def make_layout(self, seq_len: int) -> np.ndarray:
        layout = self.setup_layout(seq_len)
        n = layout.shape[1]
        rng = np.random.default_rng(self.seed)
        w = self.num_sliding_window_blocks // 2
        for h in range(self.num_heads if self.different_layout_per_head
                       else 1):
            for i in range(n):
                lo, hi = max(0, i - w), min(n, i + w + 1)
                layout[h, i, lo:hi] = 1                       # sliding window
                choices = rng.choice(n, size=min(self.num_random_blocks, n),
                                     replace=False)
                layout[h, i, choices] = 1                     # random blocks
            g = min(self.num_global_blocks, n)
            layout[h, :g, :] = 1                              # global rows
            layout[h, :, :g] = 1                              # global cols
        if self.attention == "unidirectional":
            layout = np.tril(layout)
        return self.check_and_propagate_first_head_layout(layout)


class BSLongformerSparsityConfig(SparsityConfig):
    """Sliding window + selected global-attention block indices (reference
    :558)."""

    def __init__(self, num_heads: int, block: int = 16,
                 different_layout_per_head: bool = False,
                 num_sliding_window_blocks: int = 3,
                 global_block_indices: Optional[List[int]] = None,
                 global_block_end_indices: Optional[List[int]] = None,
                 attention: str = "bidirectional"):
        super().__init__(num_heads, block, different_layout_per_head)
        self.num_sliding_window_blocks = num_sliding_window_blocks
        self.global_block_indices = global_block_indices or [0]
        self.global_block_end_indices = global_block_end_indices
        self.attention = attention

    def make_layout(self, seq_len: int) -> np.ndarray:
        layout = self.setup_layout(seq_len)
        n = layout.shape[1]
        w = self.num_sliding_window_blocks // 2
        for h in range(self.num_heads if self.different_layout_per_head
                       else 1):
            for i in range(n):
                lo, hi = max(0, i - w), min(n, i + w + 1)
                layout[h, i, lo:hi] = 1
            if self.global_block_end_indices is None:
                for idx in self.global_block_indices:
                    if idx < n:
                        layout[h, idx, :] = 1
                        layout[h, :, idx] = 1
            else:
                for s, e in zip(self.global_block_indices,
                                self.global_block_end_indices):
                    layout[h, s:e, :] = 1
                    layout[h, :, s:e] = 1
        if self.attention == "unidirectional":
            layout = np.tril(layout)
        return self.check_and_propagate_first_head_layout(layout)


class VariableSparsityConfig(SparsityConfig):
    """Variable local window sizes + global blocks (reference :232)."""

    def __init__(self, num_heads: int, block: int = 16,
                 different_layout_per_head: bool = False,
                 num_random_blocks: int = 0,
                 local_window_blocks: Optional[List[int]] = None,
                 global_block_indices: Optional[List[int]] = None,
                 global_block_end_indices: Optional[List[int]] = None,
                 attention: str = "bidirectional",
                 horizontal_global_attention: bool = False, seed: int = 0):
        super().__init__(num_heads, block, different_layout_per_head)
        self.num_random_blocks = num_random_blocks
        self.local_window_blocks = local_window_blocks or [4]
        self.global_block_indices = global_block_indices or [0]
        self.global_block_end_indices = global_block_end_indices
        self.attention = attention
        self.horizontal_global_attention = horizontal_global_attention
        self.seed = seed

    def make_layout(self, seq_len: int) -> np.ndarray:
        layout = self.setup_layout(seq_len)
        n = layout.shape[1]
        rng = np.random.default_rng(self.seed)
        for h in range(self.num_heads if self.different_layout_per_head
                       else 1):
            start = 0
            wi = 0
            while start < n:
                w = self.local_window_blocks[
                    min(wi, len(self.local_window_blocks) - 1)]
                end = min(start + w, n)
                layout[h, start:end, start:end] = 1
                start = end
                wi += 1
            if self.num_random_blocks:
                for i in range(n):
                    choices = rng.choice(
                        n, size=min(self.num_random_blocks, n),
                        replace=False)
                    layout[h, i, choices] = 1
            if self.global_block_end_indices is None:
                for idx in self.global_block_indices:
                    if idx < n:
                        layout[h, :, idx] = 1
                        if self.horizontal_global_attention:
                            layout[h, idx, :] = 1
            else:
                for s, e in zip(self.global_block_indices,
                                self.global_block_end_indices):
                    layout[h, :, s:e] = 1
                    if self.horizontal_global_attention:
                        layout[h, s:e, :] = 1
        if self.attention == "unidirectional":
            layout = np.tril(layout)
        return self.check_and_propagate_first_head_layout(layout)


# ------------------------------------------------------------------- compute

def layout_to_mask(layout: np.ndarray, seq_len: int) -> jnp.ndarray:
    """[H, n, n] block layout -> [H, S, S] boolean attention mask."""
    block = seq_len // layout.shape[1]
    mask = np.repeat(np.repeat(layout, block, axis=1), block, axis=2)
    return jnp.asarray(mask.astype(bool))


def sparse_self_attention(q, k, v, sparsity_config: SparsityConfig,
                          causal: bool = False, sm_scale=None,
                          impl: str = "dense"):
    """q/k/v [B, S, H, hd] -> [B, S, H, hd] under the config's block layout
    (reference SparseSelfAttention.forward).

    ``impl="pallas"`` routes to the block-skipping Pallas kernels
    (ops/pallas/block_sparse_attention.py, fused forward AND backward):
    identical numerics and gradients, compute and HBM traffic scale with
    layout density instead of S² — the long-sequence path.  ``dense``
    keeps the block-masked XLA softmax fusion (the right trade below ~16k
    tokens)."""
    B, S, H, hd = q.shape
    scale = sm_scale if sm_scale is not None else hd ** -0.5
    layout = sparsity_config.make_layout(S)
    if impl == "pallas":
        from deepspeed_tpu.ops.pallas.block_sparse_attention import (
            block_sparse_attention_trainable)
        return block_sparse_attention_trainable(q, k, v, layout,
                                                causal=causal,
                                                sm_scale=sm_scale)
    mask = layout_to_mask(layout, S)                     # [H, S, S]
    if causal:
        mask = jnp.logical_and(mask, jnp.tril(jnp.ones((S, S), bool)))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(mask[None], s, NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    # fully-masked rows emit 0 (flash convention, shared with the Pallas
    # block-skipping kernel) — a uniform softmax over -1e30 scores would
    # leak masked V into the output
    row_any = mask.any(-1)                               # [H, S] (mask is
    out = jnp.where(row_any.T[None, :, :, None], out, 0.0)  # already causal)
    return out.astype(q.dtype)


class SparseSelfAttention:
    """Module shim mirroring the reference class."""

    def __init__(self, sparsity_config: SparsityConfig,
                 attn_mask_mode: str = "mul", impl: str = "dense"):
        self.sparsity_config = sparsity_config
        self.impl = impl

    def __call__(self, query, key, value, causal=False):
        return sparse_self_attention(query, key, value,
                                     self.sparsity_config, causal=causal,
                                     impl=self.impl)


# ------------------------------------------------ blocks a query picks itself
# InfLLM-V2 (MiniCPM4: arXiv:2506.07900; arXiv:2509.24663), the trainable
# sparse attention of models/minicpm_sala.py.  Above, a layout is a numpy
# constant of the configuration; here it is data: every query scores pooled
# keys, the scores are max-pooled to key blocks, and the ``topk`` highest
# blocks (with the first and the nearest always among them) are the keys the
# query's softmax runs over.  The choice is per query token and per
# key/value head (the query heads of a group vote with the sum of their
# softmaxes), has no parameter and carries no gradient.
@dataclass(frozen=True)
class BlockSelection:
    """The selection's seven numbers (positions are counted from a
    document's first token)."""
    #: keys of one block: positions ``[block_size * b, block_size * (b+1))``
    block_size: int = 64
    #: a pooled key is the mean of ``kernel_size`` keys, one every
    #: ``kernel_stride`` positions while the window lies inside the document
    kernel_size: int = 32
    kernel_stride: int = 16
    #: blocks a query keeps (a query with no more causal blocks keeps all)
    topk: int = 64
    #: the first ``init_blocks`` blocks and the blocks of the last
    #: ``window_size`` positions up to the query's own are always kept
    init_blocks: int = 1
    window_size: int = 2048
    #: every query of a document shorter than this keeps every causal block
    dense_len: int = 8192

    def __post_init__(self):
        if self.kernel_size % self.kernel_stride \
                or self.block_size % self.kernel_stride \
                or self.window_size % self.block_size:
            raise ValueError(
                f"block selection: kernel_size {self.kernel_size} and "
                f"block_size {self.block_size} must be multiples of "
                f"kernel_stride {self.kernel_stride}, window_size "
                f"{self.window_size} of block_size")
        if self.init_blocks + self.local_blocks > self.topk:
            raise ValueError(
                f"block selection: {self.init_blocks} first and "
                f"{self.local_blocks} nearest blocks are always kept, more "
                f"than topk {self.topk}")

    @property
    def local_blocks(self) -> int:
        return self.window_size // self.block_size

    @property
    def windows_per_block(self) -> int:
        return self.block_size // self.kernel_stride

    @property
    def reach(self) -> int:
        """Pooling windows that start before a block and end inside it."""
        return self.kernel_size // self.kernel_stride - 1


def _documents(segment_ids, S):
    """(first position, one past the last position) of each token's
    document, [b, S] int32 each, from ``segment_ids`` [b, S] whose
    documents are runs."""
    idx = jnp.arange(S, dtype=jnp.int32)
    changes = segment_ids[:, 1:] != segment_ids[:, :-1]
    edge = jnp.ones((segment_ids.shape[0], 1), bool)
    first = jnp.concatenate([edge, changes], axis=1)
    last = jnp.concatenate([changes, edge], axis=1)
    start = lax.cummax(jnp.where(first, idx, 0), axis=1)
    end = lax.cummin(jnp.where(last, idx + 1, S), axis=1, reverse=True)
    return start, end


def _shifted(x, n, axis):
    """``x[i + n]`` along ``axis``, zero where ``i + n`` is outside."""
    if n == 0:
        return x
    size = x.shape[axis]
    pad = [(0, 0)] * x.ndim
    pad[axis] = (max(-n, 0), max(n, 0))
    return lax.slice_in_dim(jnp.pad(x, pad), max(n, 0), max(n, 0) + size,
                            axis=axis)


def _sliding_sum(x, width, axis=1):
    """``sum(x[i : i + width])`` at every ``i`` along ``axis`` (zeros past
    the end), by doubling: log2(width) shifted sums."""
    out, offset, span = None, 0, 1
    while width:
        if width & 1:
            part = _shifted(x, offset, axis)
            out = part if out is None else out + part
            offset += span
        x = x + _shifted(x, span, axis)
        span, width = 2 * span, width >> 1
    return out


def _geometry(segment_ids, S, sel: BlockSelection):
    """What the selection and the attention both need of a packed row,
    [b, S] int32 each: a token's position in its document, its document's
    length, and ``c0``, the *column* of its document's block 0.  A column
    is a block index shifted so that one static axis of ``S / block_size``
    columns holds every document's blocks side by side: block ``b`` of a
    document that starts at ``a`` is column ``b + a // block_size``
    (two documents may share a column at their boundary; the document
    itself tells them apart)."""
    start, end = _documents(segment_ids, S)
    idx = jnp.arange(S, dtype=jnp.int32)
    slot0 = start // sel.kernel_stride
    return {"pos": idx - start, "len": end - start, "start": start,
            "end": end, "c0": slot0 // sel.windows_per_block,
            "lane": slot0 % sel.windows_per_block}


def select_blocks(q, k, segment_ids=None, sel: BlockSelection = None,
                  query_chunk: int = 512):
    """The key blocks each query keeps: ``(blocks [b, G, S, topk] int32,
    count [b, G, S] int32)`` — ``blocks`` the kept blocks' indices inside
    the query's document, ascending, ``-1`` where a query has fewer causal
    blocks than ``topk``; ``count`` how many are kept.  ``q`` [b, S, H,
    hd], ``k`` [b, S, G, hd] (``H`` a multiple of ``G``), after any norm
    and rotation the attention itself would see.  No gradient flows.

    Per document, positions from its first token (``sel``'s numbers):

    1. pooled keys ``K_j = mean(k[stride * j : stride * j + kernel])``
       while the window lies inside the document;
    2. ``p[h, t, :] = softmax_j(q[h, t] . K_j / sqrt(hd))`` over the
       windows that end at or before ``t``; ``a[g, t, j]`` its sum over the
       query heads of key/value head ``g``;
    3. block ``b``'s score is the maximum of ``a`` over the windows that
       touch it;
    4. the first ``init_blocks`` blocks and the ``window_size /
       block_size`` blocks that end with the query's own score +inf; the
       ``topk`` highest causal blocks are kept, the lower index on a tie.

    Scores, softmax and top-k are float32 (the products at
    ``Precision.HIGHEST``).  ``dense_len`` is not applied here: the
    choice is made for every query, and :func:`selected_attention` keeps
    every causal block of a short document whatever was chosen.

    The lowering works on static axes: pooled keys live in ``S / stride``
    *slots* (window ``j`` of a document that starts at ``a`` is slot ``a
    // stride + j``; documents never share a slot), blocks in ``S /
    block_size`` columns (:func:`_geometry`).  ``S`` must be a multiple
    of ``block_size``."""
    sel = sel or BlockSelection()
    b, S, H, hd = q.shape
    G = k.shape[2]
    st, ks, per = sel.kernel_stride, sel.kernel_size, sel.windows_per_block
    if S % sel.block_size:
        raise ValueError(f"select_blocks: S {S} is not a multiple of "
                         f"block_size {sel.block_size}")
    U, C = S // st, S // sel.block_size
    K = min(sel.topk, C)
    seg = (jnp.zeros((b, S), jnp.int32) if segment_ids is None
           else segment_ids.astype(jnp.int32))
    q, k = lax.stop_gradient((q, k))
    geo = _geometry(seg, S, sel)

    # -- step 1: a slot's window, read at the last position its start can be
    probe = jnp.arange(U, dtype=jnp.int32) * st + (st - 1)
    at = lambda t: t[:, probe]                               # [b, U]
    w_start = probe - (st - 1) + at(geo["start"]) % st
    w_ok = (w_start >= at(geo["start"])) & (w_start + ks <= at(geo["end"]))
    w_seg = at(seg)
    sums = _sliding_sum(k.astype(jnp.float32), ks)           # [b, S, G, hd]
    pooled = jnp.take_along_axis(
        sums, w_start[:, :, None, None], axis=1) / ks        # [b, U, G, hd]

    Qs = query_chunk if S % query_chunk == 0 else S
    col = jnp.arange(C, dtype=jnp.int32)

    def some_queries(xs):
        qc, t, seg_q, pos, c0, lane = xs
        # -- step 2
        s = jnp.einsum("bqgrd,bugd->bgrqu",
                       qc.astype(jnp.float32).reshape(b, Qs, G, H // G, hd),
                       pooled, precision=lax.Precision.HIGHEST) * hd ** -0.5
        seen = (w_ok[:, None] & (w_seg[:, None] == seg_q[:, :, None])
                & (w_start[:, None] + (ks - 1) <= t[None, :, None]))
        seen = seen[:, None, None]                           # [b,1,1,Qs,U]
        top = jnp.max(jnp.where(seen, s, -jnp.inf), axis=-1, keepdims=True)
        e = jnp.where(seen, jnp.exp(s - jnp.where(jnp.isfinite(top), top,
                                                  0.0)), 0.0)
        den = jnp.sum(e, axis=-1, keepdims=True)
        a = jnp.sum(e / jnp.where(den > 0, den, 1.0), axis=2)  # [b,G,Qs,U]
        # -- step 3: the windows reach .. per - 1 around a block's first,
        # then the slots that are a block's first for this query's document
        best = a
        for o in range(-sel.reach, per):
            if o:
                best = jnp.maximum(best, _shifted(a, o, 3))
        best = best.reshape(b, G, Qs, C, per)
        mine = jnp.arange(per) == lane[:, None, :, None, None]
        score = jnp.max(jnp.where(mine, best, 0.0), axis=-1)  # [b,G,Qs,C]
        # -- step 4
        own = (c0 + pos // sel.block_size)[:, None, :, None]
        first = c0[:, None, :, None]
        causal = (col >= first) & (col <= own)
        forced = (col < first + sel.init_blocks) \
            | (col > own - sel.local_blocks)
        score = jnp.where(causal, jnp.where(forced, jnp.inf, score),
                          -jnp.inf)
        value, column = lax.top_k(score, K)
        kept = value > -jnp.inf
        block = jnp.sort(jnp.where(kept, column - first, C), axis=-1)
        return (jnp.where(block < C, block, -1),
                jnp.sum(kept, axis=-1, dtype=jnp.int32))

    by_chunk = lambda t: jnp.moveaxis(
        t.reshape((b, S // Qs, Qs) + t.shape[2:]), 1, 0)
    blocks, count = lax.map(some_queries, (
        by_chunk(q), jnp.arange(S, dtype=jnp.int32).reshape(-1, Qs),
        by_chunk(seg), by_chunk(geo["pos"]), by_chunk(geo["c0"]),
        by_chunk(geo["lane"])))
    blocks = jnp.moveaxis(blocks, 0, 2).reshape(b, G, S, K)
    count = jnp.moveaxis(count, 0, 2).reshape(b, G, S)
    if K < sel.topk:
        blocks = jnp.pad(blocks, ((0, 0),) * 3 + ((0, sel.topk - K),),
                         constant_values=-1)
    return blocks, count


def _spans(S, query_chunk, key_spans):
    """(queries scored at a time, spans the sequence is walked in): the
    caller's where they divide ``S``, else one span of one chunk."""
    if S % (query_chunk * key_spans):
        return S, 1
    return query_chunk, key_spans


def visited_keys_per_query(S, query_chunk=128, key_spans=4):
    """Keys :func:`selected_attention` multiplies a query by, a mean over a
    sequence's queries: the queries of span ``i`` of ``n`` see the keys
    ``[0, (i + 1) S / n)`` under their mask, whatever was selected."""
    _, n = _spans(S, query_chunk, key_spans)
    return S * (n + 1) / (2.0 * n)


def _kept_columns(blk, c0, dense_q, col):
    """1 at the columns ``col`` a query keeps, [b, G, queries, columns]
    bool: its kept blocks ``blk`` [b, G, queries, topk] shifted by its
    document's column 0, or every column where its document is short."""
    column = jnp.where(blk >= 0, blk + c0[:, None, :, None], -1)
    return jnp.any(column[..., None] == col, axis=-2) \
        | dense_q[:, None, :, None]


def mask_operands(blocks, seg, sel: BlockSelection, dtype):
    """What the kernels' mask is made of, once a call: which column each
    key lies in [b, S, C] and the columns each query keeps [b, G, C, S]
    (``dense_len`` folded in as rows of ones), zeros and ones in
    ``dtype``, and the first position of each query's document [b, 1, S]
    int32."""
    S = seg.shape[1]
    geo = _geometry(seg, S, sel)
    col = jnp.arange(S // sel.block_size, dtype=jnp.int32)
    key_col = geo["c0"] + geo["pos"] // sel.block_size
    kept = _kept_columns(blocks, geo["c0"], geo["len"] < sel.dense_len, col)
    return ((key_col[..., None] == col).astype(dtype),
            jnp.swapaxes(kept, 2, 3).astype(dtype), geo["start"][:, None, :])


def _attend_blocking(interpret, S, R, hd, sel, dtype):
    """(the tiles of ops/pallas/selected_attention.py's kernels, or None for
    the XLA form; interpret), by ``vmem.lowering``'s rule."""
    from deepspeed_tpu.ops.pallas import selected_attention as kernels, vmem
    return vmem.lowering(
        interpret, kernels.supported(S, hd, sel.block_size, interpret),
        lambda: kernels.blocking(S, R, hd, sel.block_size,
                                 jnp.dtype(dtype).itemsize))


def selected_attention(q, k, v, blocks, segment_ids=None,
                       sel: BlockSelection = None, query_chunk: int = 128,
                       key_spans: int = 4, interpret=None):
    """Softmax attention of each query over the keys ``s <= t`` of its own
    document's kept blocks: ``q`` [b, S, H, hd], ``k``, ``v`` [b, S, G,
    hd], ``blocks`` [b, G, S, topk] as :func:`select_blocks` returns them
    -> [b, S, H, hd] in ``q``'s dtype.  Every causal block of a document
    shorter than ``sel.dense_len`` is kept whatever ``blocks`` says.
    Differentiable in ``q``, ``k`` and ``v``; products take their operands
    in ``q``'s dtype and accumulate in float32, the softmax is float32.

    One algorithm, two lowerings (:func:`_attend_blocking` chooses, from
    what the call observes; ``interpret``: None chooses, True runs the
    kernels in interpret mode, False the XLA form).  Both multiply a query
    by all the keys it could see, under a per-(token, block) mask — which
    column each key lies in against the columns each query keeps: one
    small product of zeros and ones — and skip nothing for being
    unselected, so the step's device time does not depend on the data.

    * ``mosaic_tiles`` — on one TPU, for a head width of 128 and an ``S``
      the tiles divide: the kernels of ops/pallas/selected_attention.py.
      Online-softmax tiles with the scores, the mask and the softmax in
      VMEM, a key/value head's query heads sharing a tile's mask, the
      backward by hand from the saved log-sum-exp rows; a query is
      multiplied by ``(S + tile) / 2`` keys.
    * ``masked_chunks`` — elsewhere: ``query_chunk`` queries at a time
      against every key of their span, the sequence walked in
      ``key_spans`` spans of growing key length (so a query is multiplied
      by :func:`visited_keys_per_query` keys, not by ``S``), each chunk
      rematerialised for its gradient; the scores go through HBM.

    Not built: a kernel that also leaves out a (query tile, key block) no
    query of the tile kept — its trip counts would be data (ROADMAP) — and
    one that gathers a query's kept keys."""
    sel = sel or BlockSelection()
    b, S, H, hd = q.shape
    G = k.shape[2]
    dtype = q.dtype
    seg = (jnp.zeros((b, S), jnp.int32) if segment_ids is None
           else segment_ids.astype(jnp.int32))
    row = {"batch": b, "seq_len": S, "heads": H, "kv_heads": G,
           "head_dim": hd,
           **{f"sparse/{f}": getattr(sel, f)
              for f in sel.__dataclass_fields__}}
    account = lambda **how: count_in_step(sparse_attention_calls={
        f"{b}x{S}x{H}x{G}x{hd}": {**row, **how}})

    tiles, interpret = _attend_blocking(interpret, S, H // G, hd, sel, dtype)
    if tiles is not None:
        from deepspeed_tpu.ops.pallas import selected_attention as kernels
        from deepspeed_tpu.ops.pallas.vmem import limit_for
        bq, bk = tiles.block_q, tiles.block_k
        account(lowering="mosaic_tiles", blocks=[bq, bk],
                tiles=kernels.visited_tiles(S, bq, bk),
                vmem_limit_bytes=limit_for(tiles.vmem_bytes),
                **{"sparse/visited_keys_per_query":
                   kernels.visited_keys_per_query(S, bq, bk)})
        return kernels.selected_attention_kernels(
            q, k, v, *mask_operands(blocks, seg, sel, dtype), tiles,
            interpret)

    geo = _geometry(seg, S, sel)
    key_col = geo["c0"] + geo["pos"] // sel.block_size          # [b, S]
    dense = geo["len"] < sel.dense_len
    col = jnp.arange(-(-S // sel.block_size), dtype=jnp.int32)
    Qc, n_spans = _spans(S, query_chunk, key_spans)
    account(lowering="masked_chunks", query_chunk=Qc, key_spans=n_spans,
            **{"sparse/visited_keys_per_query": visited_keys_per_query(
                S, query_chunk, key_spans)})
    # float32 outside the chunks' loop: its transpose sums their cotangents
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    by_chunk = lambda t, n: jnp.moveaxis(
        t.reshape(t.shape[:n] + (-1, Qc) + t.shape[n + 1:]), n, 0)

    def span(first, last):
        """Queries ``[first, last)`` over the keys ``[0, last)``."""
        in_col = (key_col[:, :last, None] == col).astype(dtype)  # [b,Sk,C]
        seg_k, at = seg[:, :last], jnp.arange(last, dtype=jnp.int32)

        @jax.checkpoint
        def some_queries(xs):
            qc, blk, t, seg_q, c0, dense_q = xs
            seen = jnp.einsum(
                "bgqc,bkc->bgqk",
                _kept_columns(blk, c0, dense_q, col).astype(dtype),
                in_col) > 0.5
            seen &= (seg_q[:, None, :, None] == seg_k[:, None, None, :]) \
                & (t[None, None, :, None] >= at)
            s = jnp.einsum("bqgrd,bkgd->bgrqk",
                           qc.reshape(b, Qc, G, H // G, hd),
                           k32[:, :last].astype(dtype),
                           preferred_element_type=jnp.float32) * hd ** -0.5
            p = jax.nn.softmax(jnp.where(seen[:, :, None], s, -jnp.inf),
                               axis=-1)
            o = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(dtype),
                           v32[:, :last].astype(dtype),
                           preferred_element_type=jnp.float32)
            return o.reshape(b, Qc, H, hd).astype(dtype)

        cut = lambda t, axis: lax.slice_in_dim(t, first, last, axis=axis)
        out = lax.map(some_queries, (
            by_chunk(cut(q, 1), 1), by_chunk(cut(blocks, 2), 2),
            jnp.arange(first, last, dtype=jnp.int32).reshape(-1, Qc),
            by_chunk(cut(seg, 1), 1), by_chunk(cut(geo["c0"], 1), 1),
            by_chunk(cut(dense, 1), 1)))
        return jnp.moveaxis(out, 0, 1).reshape(b, last - first, H, hd)

    width = S // n_spans
    return jnp.concatenate([span(i * width, (i + 1) * width)
                            for i in range(n_spans)], axis=1)


def selection_counts(blocks, count, segment_ids=None,
                     sel: BlockSelection = None):
    """What a selection adds up to, from its data (a diagnostic, outside
    the step): ``sparse/selected_blocks_per_query`` (the blocks a query
    attends over, a document under ``dense_len`` keeping all its causal
    ones), ``sparse/required_keys_per_query`` (the keys ``s <= t`` inside
    them: what step 5 of the equations multiplies), both means over
    queries and key/value heads, and ``sparse/dense_documents``, the share
    of queries whose document is under ``dense_len``."""
    sel = sel or BlockSelection()
    b, G, S, _ = blocks.shape
    seg = (jnp.zeros((b, S), jnp.int32) if segment_ids is None
           else segment_ids.astype(jnp.int32))
    geo = _geometry(seg, S, sel)
    own = geo["pos"] // sel.block_size
    dense = geo["len"] < sel.dense_len
    kept = jnp.where(dense[:, None], own[:, None] + 1, count)
    # every kept block is whole but the query's own
    keys = (kept - 1) * sel.block_size \
        + (geo["pos"] % sel.block_size + 1)[:, None]
    return {"sparse/selected_blocks_per_query": jnp.mean(kept * 1.0),
            "sparse/required_keys_per_query": jnp.mean(keys * 1.0),
            "sparse/dense_documents": jnp.mean(dense * 1.0)}
