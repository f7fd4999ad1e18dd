"""Ragged grouped GEMM Pallas kernels (``ds_ggemm``) — megablocks-style
expert dispatch (Gale et al. 2022, arXiv:2211.15841).

The GShard einsum dispatch in ``moe/layer.py`` materializes dense
``[T, E, C]`` combine/dispatch tensors (two O(T·E·C·D) einsums) and pads
every expert to capacity ``C``.  This module reformulates expert
computation as ONE ragged GEMM over tokens sorted by expert:

1. :func:`make_group_plan` — one stable sort of the flat ``[T·k]``
   expert choices, together with the padding entries that round each
   expert's contiguous group up to a multiple of the M-tile (``block_m``;
   empty experts keep one all-zero tile so backward tiles are always
   written), gives ``padded_to_row``; sorted back it gives its inverse
   ``row_to_padded``; beside them the per-M-tile expert id
   (``block_group_ids``, non-decreasing) and the number of tiles that
   hold a group (``used_blocks``).  The padded row count is **static**
   (``round_up(T·k, bm) + E·bm``) so the whole pipeline jits; the waste
   is < one tile per expert, versus the capacity formulation's
   ``E·C - T·k`` slots, plus the tiles that trail behind the last group
   (about one in ten at 512 rows an expert), which the kernels neither
   fetch nor multiply.  Rows move through the two maps by gathers only —
   :func:`dispatch_rows` from the token-major activations straight into
   the padded layout, :func:`sum_rows` back out, a token's rows summed
   as they are (the layer weights a row by its gate where its expert is,
   between the two products: the way back knows no gate and keeps no
   row), each the other's transpose and so the other's backward
   (autodiff would write a scatter-add, which costs 3× a gather of the
   same rows on a v5e) — and the plan itself holds no scatter either.
2. :func:`ds_ggemm` — one Pallas kernel over grid ``(N/bn, m_tiles,
   K/bk)``, N outermost and K innermost: the M-grid walks group
   boundaries via the scalar-prefetched ``block_group_ids`` (the
   block_sparse_attention idiom), so each M-tile contracts against
   exactly its expert's ``[K, N]`` slice of the stacked ``[E, K, N]``
   weights — zero top-k slot padding, no dense ``[T, E, C]`` tensors
   anywhere.  A tile at or past ``used_blocks`` — a trailing tile —
   repeats the block index of the last tile that holds rows (so nothing
   is copied for it) and does no MXU work.  What it leaves behind depends
   on the plan: zeros (a full plan, :func:`make_group_plan`: fewer than
   one tile in ten trails, and :func:`sum_rows` may gather anything),
   or nothing at all (a held plan, ``live_only``: its output block too
   stays on the last live tile, so the rows behind the live prefix — most
   of such a plan — are never written, and hold whatever the buffer held).
3. The blocks (:func:`_choose_blocks`) come from the shapes, the dtypes
   and a VMEM budget that is a constant per device kind.  **Resident**:
   ``bk = K``, one contraction per tile and no scratch accumulator, with
   the widest ``bn`` dividing N whose double-buffered ``[K, bn]`` panel
   fits beside the row and output tiles.  The weight block's index is
   then ``(g[i], 0, j)``: equal for consecutive M-tiles of one expert,
   so Pallas copies an expert's panel once per call and N block, not once
   per M-tile (``E·K·N`` weight values a call instead of
   ``m_tiles·K·N``), and the call raises its VMEM limit where the panel
   passes what Mosaic grants unasked.  **Streamed**: where no whole-K
   panel fits, or where it would move more bytes (rows are re-read once
   per N block), the K-innermost ``(512, 1024)`` blocks with an fp32
   scratch accumulator, every M-tile fetching its expert's blocks again.
   Blocks given by the caller or by ``DS_GGEMM_BLOCKS`` are taken as
   given, and one block over K is the resident regime.
4. int8 weights ride the exact ``qgemm`` per-tile VMEM scale-expansion
   design (selector-matmul dequant immediately before the MXU dot) at
   the K-innermost blocks, so routed experts stream at the same int8
   weight floor as dense layers.
5. backward (float path): ``dx`` reuses the forward kernel with a
   transposed-RHS contraction (its panel is ``[K, N]`` read along N);
   ``dw`` is a tgmm kernel (grid ``(K/bk, N/bn, m_tiles)``, M innermost)
   accumulating per-expert outer products in a ``[bk, bn]`` fp32 scratch
   and flushing on group change, its blocks by the same rule — with
   ``bk = K`` and ``bn = N`` both row operands are read once.  Per-step
   expert FLOPs stay ∝ routed tokens in BOTH directions.

What each call moves as tiled is counted while a step is traced
(``telemetry/tracing.py``: ``grouped_gemm_rows``): per kernel and weight
shape the blocks, the regime, and the weight and operand bytes of one
call.

Off-TPU the jnp reference (``jax.lax.ragged_dot`` over the same padded
layout) serves correctness and autodiff; ``interpret=True`` (or
``DS_GGEMM_INTERPRET=1``) runs the real kernels in interpret mode so the
CPU tier-1 suite exercises them.  Block shapes are sweepable via
``DS_GGEMM_BLOCKS="bm,bk,bn"`` / ``scripts/ggemm_sweep.py``.

Every ``pl.pallas_call`` here has a ``name=``, which is how the
step-program map (``get_program_map`` of telemetry/tracing.py) tells
these kernels from the flash kernel in a compiled step: ``ds_ggemm_fwd``,
``ds_ggemm_dx`` (the same kernel body on a transposed right-hand side),
``ds_ggemm_dw`` (the three that training runs),
``ds_ggemm_q`` (int8 weights) and ``ds_ggemm_slots`` /
``ds_ggemm_slots_q`` (decode-sized), and ``ds_rowsum`` (a held plan's
rows summed into their tokens).  ``ds_unwritten_*`` are no kernels
but allocations: the buffers a held plan's loops write their chunks into
(:func:`_unwritten`); ``ds_zeroed_padding_*`` is such an allocation with
zeros in the last tile of each group and nothing written elsewhere: the
buffer an exchange's rows land in (:func:`zeroed_padding`).

A held subset of the experts (``make_held_group_plan``: expert
parallelism's share of a layer) lays its rows out in a plan whose length
is a static bound, several times what is routed to it.  What such a plan
costs is what its rows cost: they are a prefix of ``used_blocks`` tiles, a
number the step computes from the routing, and every consumer — the
plan's own lookup, :func:`dispatch_held_rows`, the kernels' tile walk and
their output, :func:`map_live_rows` between two grouped calls,
:func:`combine_held_rows`, and each one's backward — walks that prefix and
stops.  The bound costs memory, not time.  The way back — a held plan's
rows summed into their tokens, forward in :func:`combine_held_rows` and
backward in :func:`dispatch_held_rows` — walks the *tokens*: the Mosaic
kernel ``ds_rowsum`` takes a block of tokens at a time and fetches, expert
by expert, the run of the plan's rows that are theirs
(:func:`_sum_live_into_tokens`).  An expert-parallel exchange's receive
plan reads each landed row once an expert the token chose
(:func:`gather_landed_rows`) and its way back is the same kernel with the
landed rows for its tokens (:func:`sum_into_landed_rows`).
"""
import functools
import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import vmem

#: the M-tile the plan pads to (the MXU's row dimension), and the
#: (bk, bn) of the K-innermost tiling: the int8 and slot kernels', and
#: what a float call falls back to when no whole-K weight panel fits VMEM
#: (:func:`_choose_blocks`)
DEFAULT_BLOCK_M = 128
_BLOCKS_KN = (512, 1024)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _env_blocks():
    env = os.environ.get("DS_GGEMM_BLOCKS")
    if not env:
        return None
    bm, bk, bn = (int(v) for v in env.split(","))
    return bm, bk, bn


def default_block_m() -> int:
    env = _env_blocks()
    return env[0] if env else DEFAULT_BLOCK_M


class GroupPlan(NamedTuple):
    """Static-shape layout for one routed batch (see module docstring).

    ``row_to_padded[f]`` maps flat routed element ``f`` (token-major:
    ``f = t * top_k + choice``) to its row in the group-padded array, and
    ``padded_to_row[p]`` is its inverse: the flat element that sits in
    padded row ``p``, ``R`` (out of range) on a padding row.  Rows move
    through the pair by gathers alone, in both directions and in both
    backward passes (:func:`dispatch_rows`, :func:`sum_rows`).
    """
    block_m: int                   # static M-tile the layout is padded to
    padded_rows: int               # static padded row count (Mp)
    num_blocks: int                # static Mp // block_m
    num_experts: int               # static E
    group_sizes: jnp.ndarray       # [E] padded rows per expert (⋅bm, ≥ bm)
    block_group_ids: jnp.ndarray   # [num_blocks] expert per M-tile (sorted)
    used_blocks: jnp.ndarray       # [1] M-tiles that hold a group; the rest
    #                                trail behind the last one
    row_to_padded: jnp.ndarray     # [R] flat element -> padded row
    padded_to_row: jnp.ndarray     # [Mp] padded row -> flat element | R
    counts: jnp.ndarray            # [E] true routed counts (telemetry)
    #: static: every consumer stops at ``used_blocks`` and the rows behind
    #: it are never written (a held plan, whose bound is mostly unused);
    #: False: the trailing tiles are written as zeros
    live_only: bool = False
    #: a held plan's (None for a whole plan, whose way back is a gather):
    #: the held expert of each routed element, ``E`` where its expert is
    #: held elsewhere.  An expert's rows lie in element order, so the rows
    #: of a block of tokens are one run in each expert's group, and where
    #: the runs begin is a count over this (:func:`_token_block_runs`)
    group_of_element: Optional[jnp.ndarray] = None    # [R]
    #: a whole plan's, where it was made with them: the routed elements'
    #: gates in plan order, 0 on a padding row — they rode the plan's sort
    #: (:func:`_sorted_with`), and their cotangent is sorted home
    gates: Optional[jnp.ndarray] = None               # [Mp] float32


@jax.custom_vjp
def _sorted_with(keys, index, riders):
    """(``index``, ``riders``) in the stable order of ``keys`` (all [n]):
    the plan's sort with a float32 a row riding it, where a gather of
    single float32s by the sorted index costs by the element (0.29 ms for
    40,960 on a v5e against 0.06 for the whole sort).  Backward: the
    riders' cotangent sorted back by the index — exact where the index is
    a permutation; entries whose index repeats get one another's."""
    _, index, riders = jax.lax.sort((keys, index, riders), num_keys=1,
                                    is_stable=True)
    return index, riders


def _sorted_with_fwd(keys, index, riders):
    out = _sorted_with(keys, index, riders)
    return out, out[0]


def _sorted_with_bwd(index, g):
    return None, None, jax.lax.sort((index, g[1]), num_keys=1)[1]


_sorted_with.defvjp(_sorted_with_fwd, _sorted_with_bwd)


def make_group_plan(expert_ids: jnp.ndarray, num_experts: int,
                    block_m: Optional[int] = None,
                    gates: Optional[jnp.ndarray] = None) -> GroupPlan:
    """``expert_ids`` [R] int32 (R static, e.g. T·top_k) -> GroupPlan.

    All outputs have static shapes; values are data-dependent.  No
    scatter: the counts are a comparison and a sum, and ONE stable sort
    lays the padded rows out — the R routed elements keyed by their
    expert, followed by the ``Mp - R`` padding entries, each keyed by the
    expert whose group it completes (left-over ones after every group).
    Its payload is ``padded_to_row``; the stable order keeps token order
    within an expert (determinism + the exact addition order the parity
    tests pin down) and puts an expert's padding after its tokens.
    ``row_to_padded`` is the same permutation sorted back by element.
    ``gates`` [R] (the routed elements', float32; differentiable) ride
    the sort as a second payload and leave it as the plan's ``gates``: in
    plan order, zeros on padding rows.
    """
    R = int(expert_ids.shape[0])
    E = int(num_experts)
    bm = int(block_m or default_block_m())
    eids = expert_ids.astype(jnp.int32)
    experts = jnp.arange(E, dtype=jnp.int32)
    counts = jnp.sum((eids[:, None] == experts[None, :]).astype(jnp.int32),
                     axis=0)
    blocks_e = jnp.maximum(-(-counts // bm), 1)        # ≥1 tile per expert
    group_sizes = blocks_e * bm
    padded_rows = _round_up(R, bm) + E * bm            # static upper bound
    num_blocks = padded_rows // bm
    # padding entry j completes the first group whose cumulative padding
    # exceeds j; the trailing unused tiles' entries read E and sort last
    pad_end = jnp.cumsum(group_sizes - counts)         # [E]
    pad_eids = jnp.sum(
        (jnp.arange(padded_rows - R, dtype=jnp.int32)[:, None]
         >= pad_end[None, :]).astype(jnp.int32), axis=1)
    flat = jnp.arange(padded_rows, dtype=jnp.int32)
    keys, element = jnp.concatenate([eids, pad_eids]), jnp.minimum(flat, R)
    if gates is None:
        _, padded_to_row = jax.lax.sort((keys, element), num_keys=1,
                                        is_stable=True)
    else:
        padded_to_row, gates = _sorted_with(
            keys, element, jnp.pad(gates, (0, padded_rows - R)))
    _, padded_of = jax.lax.sort((padded_to_row, flat), num_keys=1,
                                is_stable=True)
    row_to_padded = padded_of[:R]
    cum_blocks = jnp.cumsum(blocks_e)                  # [E]
    gids = _tile_group_ids(jnp.arange(num_blocks, dtype=jnp.int32),
                           cum_blocks)
    return GroupPlan(bm, padded_rows, num_blocks, E, group_sizes, gids,
                     cum_blocks[-1:].astype(jnp.int32), row_to_padded,
                     padded_to_row, counts, gates=gates)


def _tile_group_ids(bidx, cum_blocks):
    """The expert of each M-tile ``bidx``: tile b belongs to the first
    expert whose cumulative tile count exceeds b; the tiles past the last
    group (``used_blocks`` on) clamp to E-1, which keeps the ids monotone
    for tgmm: rows the kernels neither fetch nor multiply (a full plan's
    are zeros and so is what is written for them; a held plan's are not
    written at all)."""
    gids = jnp.sum((bidx[:, None] >= cum_blocks[None, :]).astype(jnp.int32),
                   axis=1)
    return jnp.minimum(gids, cum_blocks.shape[0] - 1).astype(jnp.int32)


# ----------------------------------------------------------- row movement
# Routed elements and non-padding padded rows are in bijection and the
# plan holds both directions, so every movement — and every transpose of
# one, which autodiff would write as a scatter-add — is a gather.
def _rows_or_zeros(x, idx):
    """x[idx] along dim 0, exact zeros where ``idx`` is out of range."""
    return jnp.take(x, idx, axis=0, mode="fill", fill_value=0)


def _rows(x, idx):
    return x.at[idx].get(mode="promise_in_bounds")


@jax.custom_vjp
def _to_groups(rows, padded_to_row, row_to_padded):
    return _rows_or_zeros(rows, padded_to_row)


@jax.custom_vjp
def _from_groups(padded, padded_to_row, row_to_padded):
    return _rows(padded, row_to_padded)


def _keeping_maps(move):
    """``move``'s forward rule: the plan's two maps (the two arguments
    behind ``x``) are all it keeps."""
    return lambda x, *rest: (move(x, *rest), rest[:2])


# each is the other's transpose
_to_groups.defvjp(_keeping_maps(_to_groups),
                  lambda maps, g: (_from_groups(g, *maps), None, None))
_from_groups.defvjp(_keeping_maps(_from_groups),
                    lambda maps, g: (_to_groups(g, *maps), None, None))


def scatter_to_groups(rows: jnp.ndarray, plan: GroupPlan) -> jnp.ndarray:
    """rows [R, D] (flat routed order) -> group-padded [Mp, D] (pad = 0):
    a gather by ``padded_to_row``, its backward one by ``row_to_padded``."""
    return _to_groups(rows, plan.padded_to_row, plan.row_to_padded)


def gather_from_groups(padded: jnp.ndarray, plan: GroupPlan) -> jnp.ndarray:
    """group-padded [Mp, D] -> [R, D] rows in flat routed order."""
    return _from_groups(padded, plan.padded_to_row, plan.row_to_padded)


def _token_rows(xt, token_of_row):
    """xt [T, D] -> [Mp, D]: padded row p reads token ``token_of_row[p]``,
    and ``T``, one past the last, reads zeros — a sentinel row appended to
    the (small) token-major operand, where ``mode="fill"`` would pay a
    second pass over the [Mp, D] result."""
    zero = jnp.zeros((1,) + xt.shape[1:], xt.dtype)
    return _rows(jnp.concatenate([xt, zero]), token_of_row)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(xt, padded_to_row, row_to_padded, top_k):
    return _token_rows(xt, padded_to_row // top_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _sum(y, padded_to_row, row_to_padded, top_k):
    # a token's top_k rows summed in float32, rounded once
    rows = _rows(y, row_to_padded).reshape(-1, top_k, *y.shape[1:])
    return jnp.sum(rows.astype(jnp.float32), axis=1).astype(y.dtype)


# each is the other's transpose
_dispatch.defvjp(_keeping_maps(_dispatch),
                 lambda top_k, maps, g: (_sum(g, *maps, top_k), None, None))
_sum.defvjp(_keeping_maps(_sum),
            lambda top_k, maps, g: (_dispatch(g, *maps, top_k), None, None))


def dispatch_rows(xt: jnp.ndarray, plan: GroupPlan, top_k: int):
    """Token-major ``xt`` [T, D] -> group-padded [Mp, D] in ONE gather
    (padded row ``p`` reads token ``padded_to_row[p] // top_k``; padding
    rows are exact zeros, which ``ds_ggemm_dw`` relies on).  Equals
    ``scatter_to_groups(repeat(xt, top_k), plan)`` without the [T·k, D]
    copy; backward: :func:`sum_rows` of the cotangent."""
    return _dispatch(xt, plan.padded_to_row, plan.row_to_padded, top_k)


def sum_rows(y: jnp.ndarray, plan: GroupPlan, top_k: int):
    """The way back of rows that carry their weight already (gated where
    their experts are: moe/layer.py ``_grouped_moe``): group-padded ``y``
    [Mp, D] -> [T, D], each token's ``top_k`` rows gathered by
    ``row_to_padded`` and summed in float32, rounded once.  Backward:
    :func:`dispatch_rows` of the tokens' cotangents (zeros on padding
    rows) — no row of ``y`` is a residual."""
    return _sum(y, plan.padded_to_row, plan.row_to_padded, top_k)


# ------------------------------------------------------ a held subset
# An expert layer that is told which experts it holds (expert parallelism's
# share of a layer: models/qwen3_next.py on one chip of sixteen).  The
# router chooses among all experts; only the rows routed to experts
# ``[offset, offset + held)`` enter the plan.  How many those are is data,
# and the plan's length is static, so it is a stated bound
# (:func:`held_rows_bound`): rows past it are counted, never lost silently.
# The rows sit in the plan's first ``used_blocks`` tiles — its live prefix;
# a trailing tile holds no row, and a trailing row (one behind the prefix)
# of any ``[Mp, ·]`` array of such a plan is neither written nor read:
# x_pad, the kernels' results, the activation, and every cotangent.  A
# padding row INSIDE the prefix (the rest of an expert's last tile) is
# exact zeros, as in a full plan.
def held_rows_bound(routed_rows: int, experts_held: int, num_experts: int,
                    block_m: Optional[int] = None, factor: int = 2) -> int:
    """``factor`` times (twice, unless the layer says otherwise:
    ``MoEConfig.held_rows_factor``) the expected share of ``routed_rows`` =
    tokens x top_k rows that land on ``experts_held`` of ``num_experts``
    under even routing, rounded up to M-tiles: the receive buffer a
    deployment sizes the same way."""
    bm = int(block_m or default_block_m())
    return _round_up(
        -(-int(factor) * routed_rows * experts_held // num_experts), bm)


def make_held_group_plan(expert_ids: jnp.ndarray, expert_offset: int,
                         experts_held: int, bound_rows: int,
                         block_m: Optional[int] = None,
                         row_to_padded: bool = False):
    """``expert_ids`` [R] over ALL experts -> (GroupPlan over the
    ``experts_held`` experts from ``expert_offset`` on, rows over the
    bound [] int32).  The padded row count is ``bound_rows + held * bm``
    (every held expert keeps at least one tile, as in
    :func:`make_group_plan`); where the held rows need more tiles than
    that, the groups are cut in expert order so that each expert still has
    one, and what is cut is the second result.  One stable sort lays the
    held rows out by expert; ``padded_to_row`` comes from it by
    arithmetic and a lookup over the live prefix, reading ``R`` on a
    padding row.  The plan has no ``row_to_padded``: the way back sums
    rows into tokens (:func:`combine_held_rows`), R being mostly rows held
    elsewhere, a block of tokens at a time: ``group_of_element`` is what
    that way reads of the plan beside ``padded_to_row`` (forward, recompute
    and backward share both).  Asked for (``row_to_padded``: a sender's
    plan of its own rows over all experts, whose per-row scalars go out by
    :func:`scatter_to_groups` and whose cotangents come home by a gather),
    it has one all the same — the plan's sort turned round by a second of
    the same ``R`` elements, ``padded_rows`` (out of range) for an element
    that has no row here.  It is ``live_only``: see the section's head."""
    R = int(expert_ids.shape[0])
    E = int(experts_held)
    bm = int(block_m or default_block_m())
    padded_rows = _round_up(int(bound_rows), bm) + E * bm
    num_blocks = padded_rows // bm
    local = expert_ids.astype(jnp.int32) - jnp.int32(expert_offset)
    key = jnp.where((local >= 0) & (local < E), local, E)
    experts = jnp.arange(E, dtype=jnp.int32)
    counts = jnp.sum((key[:, None] == experts[None, :]).astype(jnp.int32),
                     axis=0)
    # tiles in expert order, none taking the one tile each later expert keeps
    cum_blocks = jnp.minimum(jnp.cumsum(jnp.maximum(-(-counts // bm), 1)),
                             num_blocks - (E - 1 - experts))
    blocks_e = jnp.diff(cum_blocks, prepend=0)
    group_sizes = blocks_e * bm
    kept = jnp.minimum(counts, group_sizes)
    over = jnp.sum(counts - kept).astype(jnp.int32)
    element = jnp.arange(R, dtype=jnp.int32)
    by_key, by_expert = jax.lax.sort((key, element), num_keys=1,
                                     is_stable=True)
    bidx = jnp.arange(num_blocks, dtype=jnp.int32)
    gids = _tile_group_ids(bidx, cum_blocks)
    # padded row p of expert g is its ``p - group_start[g]``-th routed row
    # where it has that many (a tile past the last group reads beyond)
    first = jnp.cumsum(counts) - counts                # in ``by_expert``
    group_start = (cum_blocks - blocks_e) * bm
    to_padded = None
    if row_to_padded:
        # the same the other way: the j-th element in expert order lies
        # ``j - first`` rows into its expert's group, where the group keeps
        # that many (the experts' tables by a comparison and a sum: a
        # look-up of single int32s costs by the element)
        of = by_key[:, None] == experts[None, :]       # [R, E]
        pick = lambda table: jnp.sum(                  # noqa: E731
            jnp.where(of, table[None, :], 0), axis=1)
        place = jnp.where(element < pick(first + kept),
                          element + pick(group_start - first), padded_rows)
        _, to_padded = jax.lax.sort((by_expert, place), num_keys=1)
    within = (bidx * bm - group_start[gids])[:, None] \
        + jnp.arange(bm, dtype=jnp.int32)[None, :]     # [num_blocks, bm]
    source = jnp.where(within < kept[gids][:, None],
                       first[gids][:, None] + within, R).reshape(padded_rows)
    plan = GroupPlan(bm, padded_rows, num_blocks, E, group_sizes, gids,
                     cum_blocks[-1:].astype(jnp.int32), to_padded, None,
                     counts, live_only=True)
    # ``by_expert[source]`` over the live prefix alone (a gather of single
    # int32s costs by the element, not by the byte: chunks as for rows a
    # tile's int32s wide); a row behind the prefix is a padding row
    chunk = _live_chunk_rows(plan, 4 * bm)

    def lookup(start, first, out):
        return _put_chunk(out, jnp.take(
            by_expert, _chunk_of(source, start, chunk), mode="fill",
            fill_value=R), start)

    padded_to_row = _over_live_chunks(
        padded_rows, chunk, live_rows(plan), lookup,
        jnp.full((padded_rows,), R, jnp.int32))
    return plan._replace(padded_to_row=padded_to_row,
                         group_of_element=key), over


def held_group_starts(counts: jnp.ndarray, bound_rows: int,
                      block_m: Optional[int] = None):
    """``counts`` [..., E]: rows for each of the ``E`` experts of a held
    plan of ``bound_rows`` (one plan along the last axis) -> (the row at
    which each expert's group begins, its padded rows), both [..., E], by
    :func:`make_held_group_plan`'s rule and by cumulative sums alone: a
    group is its rows rounded up to M-tiles, a tile at least, cut in expert
    order where the plan's ``bound_rows + E * bm`` rows would be passed.
    Whoever knows the counts knows the layout — a chip of an exchange, of
    every other chip's (moe/mappings.py ``make_exchange_sizes``)."""
    E = int(counts.shape[-1])
    bm = int(block_m or default_block_m())
    num_blocks = _round_up(int(bound_rows), bm) // bm + E
    cum_blocks = jnp.minimum(
        jnp.cumsum(jnp.maximum(-(-counts // bm), 1), axis=-1),
        num_blocks - (E - 1 - jnp.arange(E, dtype=jnp.int32)))
    sizes = jnp.diff(cum_blocks, prepend=0, axis=-1) * bm
    return (cum_blocks * bm - sizes).astype(jnp.int32), sizes.astype(
        jnp.int32)


def make_counted_group_plan(counts: jnp.ndarray, bound_rows: int,
                            block_m: Optional[int] = None):
    """The held plan of rows that are in its layout already (an exchange's
    receive buffer, every row put at its place in its expert's group by the
    chip that sent it: moe/layer.py ``_exchanged_grouped_moe``): ``counts``
    [E], the rows each held expert has -> (GroupPlan, rows over the bound)
    as :func:`make_held_group_plan` gives them for the same counts, by
    arithmetic alone (:func:`held_group_starts`) — no sort and no look-up.
    It has neither ``padded_to_row`` nor ``group_of_element``: nothing is
    gathered into it or summed out of it here.  It is ``live_only``."""
    E = int(counts.shape[0])
    bm = int(block_m or default_block_m())
    padded_rows = _round_up(int(bound_rows), bm) + E * bm
    num_blocks = padded_rows // bm
    counts = counts.astype(jnp.int32)
    starts, group_sizes = held_group_starts(counts, bound_rows, bm)
    over = jnp.sum(counts - jnp.minimum(counts, group_sizes)).astype(
        jnp.int32)
    cum_blocks = (starts + group_sizes) // bm
    gids = _tile_group_ids(jnp.arange(num_blocks, dtype=jnp.int32),
                           cum_blocks)
    return GroupPlan(bm, padded_rows, num_blocks, E, group_sizes, gids,
                     cum_blocks[-1:].astype(jnp.int32), None, None, counts,
                     live_only=True), over


# ---- the live prefix.  A held plan's length is its bound; its rows are a
# prefix of ``used_blocks`` tiles, a number the step computes from the
# routing, and everything that reads or writes a ``[Mp, ·]`` array of such
# a plan walks that prefix alone: a loop over chunks of a static number of
# rows whose trip count is traced.  Behind the prefix nothing is written
# (:func:`_unwritten`), so a row there holds whatever the buffer held; what
# reads past the prefix (the one-pass way of a sum) drops such a row by its
# index, whatever it holds.
#: bytes of the ``[chunk, width]`` slab one pass of such a loop moves
_LIVE_CHUNK_BYTES = 8 << 20


def live_rows(plan: GroupPlan):
    """[] int32: the rows of the plan's live prefix, ``used_blocks`` tiles."""
    return plan.used_blocks[0] * plan.block_m


def _live_chunk_rows(plan: GroupPlan, row_bytes: int) -> int:
    """Rows of one chunk for arrays whose widest row is ``row_bytes`` long:
    whole tiles, about ``_LIVE_CHUNK_BYTES``, the plan at most."""
    bm = plan.block_m
    rows = max(_LIVE_CHUNK_BYTES // row_bytes // bm, 1) * bm
    return min(rows, plan.padded_rows)


def _unwritten(shape, dtype, after, what):
    """The array a live-prefix loop writes its chunks into.  Where the
    kernels run on a chip it is a buffer nobody initialised: the result of
    a Mosaic call that writes nothing (``ds_unwritten_<what>``).  It takes
    ``after`` — an array the loop reads — and does not touch it: a bare
    allocation depends on nothing, so the layers' scan computes it once
    outside itself and every loop that writes into it then copies it, and
    the scheduler may place it long before its loop, where it only holds
    memory.  ``what`` tells a layer's buffers of one shape apart: XLA
    makes one call of two that are equal, and copies its result for the
    second loop.  Where ``ragged_dot`` stands in for the kernels, which
    multiplies every padded row, or Pallas' interpreter does: zeros."""
    use_reference, interpret = _use_reference(None)
    if use_reference or interpret:
        return jnp.zeros(shape, dtype)
    return pl.pallas_call(
        lambda after_ref, out_ref: None,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        name=f"ds_unwritten_{what}")(after)


def _padding_tiles(counts, padded_rows, block_m):
    """[E] int32: the last M-tile of each group of the held plan of
    ``padded_rows`` rows that ``counts`` [E] lay out
    (:func:`held_group_starts`) — the tiles that hold the groups' padding
    rows, one a group (a group has a tile at least, so no two are one), all
    inside the live prefix."""
    E = int(counts.shape[0])
    starts, sizes = held_group_starts(counts, padded_rows - E * block_m,
                                      block_m)
    return (starts + sizes) // block_m - 1


def _zero_tiles(buf, tiles, block_m):
    """``buf`` [Mp, ·] with its M-tiles ``tiles`` zeros: the XLA form."""
    zeros = jnp.zeros((block_m,) + buf.shape[1:], buf.dtype)
    return jax.lax.fori_loop(
        0, tiles.shape[0],
        lambda e, out: _put_chunk(out, zeros, tiles[e] * block_m), buf)


def _pallas_zeroed_tiles(tiles, block_m, shape, dtype, after, what,
                         interpret=False):
    """An unwritten ``shape`` = [Mp, ·] buffer (:func:`_unwritten`, and
    ``after`` and ``what`` as there) but for its M-tiles ``tiles``, which
    are zeros: a tile of zeros copied over each, HBM to HBM, so the kernel
    touches no element and takes the buffer in whatever tiling its shape
    has."""
    n = int(tiles.shape[0])

    def kernel(tiles_ref, zeros_ref, after_ref, out_ref, sems):
        def copy(e):
            start = pl.multiple_of(tiles_ref[e] * block_m, block_m)
            return pltpu.make_async_copy(
                zeros_ref, out_ref.at[pl.ds(start, block_m)], sems.at[e])

        jax.lax.fori_loop(0, n, lambda e, _: copy(e).start(), None)
        jax.lax.fori_loop(0, n, lambda e, _: copy(e).wait(), None)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA((n,))]),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        interpret=interpret,
        name=f"ds_zeroed_padding_{what}")(
            tiles.astype(jnp.int32),
            jnp.zeros((block_m,) + shape[1:], dtype), after)


def zeroed_padding(counts, shape, dtype, after, what):
    """The ``shape`` = [Mp, ·] buffer that rows put at their places in
    the groups of a held plan land in (an exchange's receive buffer:
    moe/mappings.py ``_forth``; ``counts`` [E] as
    :func:`make_counted_group_plan` takes them, ``Mp`` its padded rows):
    **exact zeros in the groups' padding rows, and nothing written
    elsewhere** — a group's padding is the rest of its last tile, which is
    zeroed whole here and whose live rows whoever lands the rows writes
    afterwards; every other row either arrives or lies behind the live
    prefix, where nothing reads.  On a chip one Mosaic call
    (``ds_zeroed_padding_<what>``) is the allocation and the zeros, ``E``
    copies of a tile; ``after`` and ``what`` as :func:`_unwritten` takes
    them, and for its reasons.  Off the chip :func:`_unwritten`'s zeros
    with the same tiles zeroed over them.  With no ``counts`` the buffer
    has no groups — an exchange's landing buffer, read by row and only
    where a row landed: :func:`_unwritten` as it is."""
    if counts is None:
        return _unwritten(shape, dtype, after, what)
    bm = default_block_m()
    tiles = _padding_tiles(counts, shape[0], bm)
    use_reference, interpret = _use_reference(None)
    if use_reference or interpret:
        return _zero_tiles(_unwritten(shape, dtype, after, what), tiles, bm)
    return _pallas_zeroed_tiles(tiles, bm, shape, dtype, after, what)


def _over_live_chunks(padded_rows, chunk, live, body, carry):
    """``carry = body(start, first, carry)`` for each chunk of ``chunk``
    rows of the live prefix (``live`` rows, traced): ``first`` is where the
    chunk begins and ``start`` where it is read and written — the same,
    but for a last chunk that would pass the end of the plan and is pulled
    back inside it, over rows an earlier pass has seen."""
    def step(c, carry):
        first = c * chunk
        return body(jnp.minimum(first, padded_rows - chunk), first, carry)

    return jax.lax.fori_loop(0, -(-live // chunk), step, carry)


def _seen(start, first, chunk):
    """[chunk] bool: the rows of a last chunk pulled back inside the plan
    (from ``start`` on) that lie before ``first``, where it begins — an
    earlier pass has done them, and a sum must not take them twice."""
    return start + jnp.arange(chunk, dtype=jnp.int32) < first


def _chunk_of(x, start, chunk):
    if chunk == x.shape[0]:
        return x
    return jax.lax.dynamic_slice_in_dim(x, start, chunk, axis=0)


def _put_chunk(out, rows, start):
    return jax.lax.dynamic_update_slice_in_dim(out, rows, start, axis=0)


def _token_rows_live(xt, token_of_row, live, chunk):
    """xt [T, D] -> [Mp, D] over the live prefix: padded row p reads token
    ``token_of_row[p]``, zeros where that is ``T`` (a padding row)."""
    Mp = token_of_row.shape[0]

    def gather(start, first, out):
        tokens = _chunk_of(token_of_row, start, chunk)
        return _put_chunk(out, _rows_or_zeros(xt, tokens), start)

    return _over_live_chunks(
        Mp, chunk, live, gather,
        _unwritten((Mp,) + xt.shape[1:], xt.dtype, xt, "rows"))


# ---- the way back: a held plan's rows summed into their tokens
#: (tokens a grid step takes, rows its stage in VMEM holds) of ``ds_rowsum``,
#: by device kind (a substring of it; the last: any)
_ROWSUM_BLOCKS = (("v5 lite", (512, 512)), ("", (256, 256)))


def _rowsum_copy(dtype) -> int:
    """Rows of one copy from HBM: a tile of ``dtype`` rows in VMEM, 8 of
    float32 and 16 of bfloat16 (a bfloat16 row shares its 32-bit words with
    its neighbour, and Mosaic copies no slice of a tiled array that is not
    whole tiles).  The plan's M-tile is a multiple of it, so no copy reaches
    behind the live prefix."""
    return 32 // jnp.dtype(dtype).itemsize


#: lanes of the int32 array that holds a row's token and gate beside it
_ROWSUM_META_LANES = 128


class _WayBack(NamedTuple):
    """What a sum into tokens reads of its plan."""
    padded_to_row: jnp.ndarray       # [Mp] padded row -> element | R
    group_of_element: jnp.ndarray    # [R] element -> held expert | E
    group_sizes: jnp.ndarray         # [E] padded rows an expert
    counts: jnp.ndarray              # [E] rows routed to it


def _way_back(plan: GroupPlan) -> _WayBack:
    return _WayBack(plan.padded_to_row, plan.group_of_element,
                    plan.group_sizes, plan.counts)


def _rowsum_blocks(tokens: int):
    """(tokens a block, rows a stage)."""
    kind = vmem.device_kind()
    bt, rows = next(b for sub, b in _ROWSUM_BLOCKS if sub in kind)
    return min(bt, _round_up(tokens, 16)), rows


def _token_block_runs(back: _WayBack, tokens, top_k, bt):
    """(first, end) [blocks, E] int32: the rows of block ``b`` of ``bt``
    tokens that expert ``e`` holds are the padded rows ``[first[b, e],
    end[b, e])`` — an expert's rows lie in element order from its group's
    start on, so a block's begin after as many rows as the blocks before it
    were sent, and a row over the bound (behind what the group keeps) is
    cut.  Counts and running sums over the routing: no sort, no scatter."""
    E = back.group_sizes.shape[0]
    nb, cap = -(-tokens // bt), bt * top_k
    groups = jnp.pad(back.group_of_element, (0, nb * cap - tokens * top_k),
                     constant_values=E).reshape(nb, cap)
    sent = jnp.sum((groups[:, :, None] == jnp.arange(
        E, dtype=jnp.int32)[None, None, :]).astype(jnp.int32), axis=1)
    before = jnp.cumsum(sent, axis=0) - sent                   # [nb, E]
    group_start = jnp.cumsum(back.group_sizes) - back.group_sizes
    kept = jnp.minimum(back.counts, back.group_sizes)
    at = lambda n: (group_start + jnp.minimum(n, kept)).astype(  # noqa: E731
        jnp.int32)
    return at(before), at(before + sent)


def _rowsum_kernel(first_ref, end_ref, y_ref, meta_ref, o_ref, rows, meta,
                   acc, sem, *, bt, stage, copy, experts, gated, precision):
    """One block of ``bt`` tokens.  Expert by expert, the run of the plan's
    rows that are this block's (``[first, end)`` of the tables) is fetched
    from HBM in whole tiles of ``copy`` rows — the rows into
    ``rows``, their tokens and gates (lanes 0 and 1 of ``meta_ref``) into
    ``meta`` beside them, every copy of a stage in flight at once — and a
    full stage (and the last) is added into the tokens' lines of a float32
    accumulator by one product with the matrix that holds a row's gate (1
    where there are none) where the row is one of this block's tokens': a
    tile's other rows, another block's or padding, meet no token here.  The
    products are exact, the sum float32, one rounding on the way out."""
    b = pl.program_id(0)
    acc[:] = jnp.zeros_like(acc)
    token = jax.lax.broadcasted_iota(jnp.int32, (stage, bt), 1) + b * bt
    slot = jax.lax.broadcasted_iota(jnp.int32, (stage, 1), 0)

    def copies(tile, at):
        src = pl.ds(pl.multiple_of(tile * copy, copy), copy)
        dst = pl.ds(pl.multiple_of(at, copy), copy)
        return (pltpu.make_async_copy(y_ref.at[src], rows.at[dst], sem.at[0]),
                pltpu.make_async_copy(meta_ref.at[src], meta.at[dst],
                                      sem.at[1]))

    def add(filled):
        """The stage's first ``filled`` rows into the accumulator."""
        def wait(_, carry):
            for one in copies(0, 0):
                one.wait()
            return carry

        jax.lax.fori_loop(0, filled // copy, wait, 0)
        x = rows[:]
        x = jnp.where(slot < filled, x, jnp.zeros_like(x))   # stale: anything
        m = meta[:]
        mine = jnp.logical_and(m[:, 0:1] == token, slot < filled)
        weights = jnp.where(mine, jax.lax.bitcast_convert_type(
            m[:, 1:2], jnp.float32), 0.0) if gated else mine
        acc[:] += jax.lax.dot_general(
            weights.astype(x.dtype), x, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

    def run(e, filled):
        first, end = first_ref[b * experts + e], end_ref[b * experts + e]
        tile0 = first // copy
        tiles = jnp.where(end > first, (end - 1) // copy + 1 - tile0, 0)

        def fetch(tile, filled):
            @pl.when(filled == stage)
            def _full():
                add(stage)

            filled = jnp.where(filled == stage, 0, filled)
            for one in copies(tile, filled):
                one.start()
            return filled + copy

        return jax.lax.fori_loop(tile0, tile0 + tiles, fetch, filled)

    filled = jax.lax.fori_loop(0, experts, run, 0)

    @pl.when(filled > 0)
    def _the_rest():
        add(filled)

    o_ref[:] = acc[:].astype(o_ref.dtype)


def _pallas_rowsum(y, meta, runs, tokens, blocks, copy, gated, interpret):
    """``y`` [Mp, D] in plan order; ``meta`` [Mp, lanes] int32: lane 0 a
    row's token, lane 1 its gate's float32 bits (the live prefix's rows:
    behind it nothing is read); ``runs`` of :func:`_token_block_runs`
    -> [tokens, D]."""
    bt, stage = blocks
    (Mp, D), dtype = y.shape, y.dtype
    nb, experts = runs[0].shape
    need = (stage * D * dtype.itemsize + stage * meta.shape[1] * 4
            + bt * D * 4 + 2 * bt * D * dtype.itemsize + 2 * bt * stage * 4)
    limit = vmem.limit_for(need)
    out = pl.pallas_call(
        functools.partial(_rowsum_kernel, bt=bt, stage=stage, copy=copy,
                          experts=experts, gated=gated,
                          precision=_precision_for(dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=pl.BlockSpec((bt, D), lambda b, first, end: (b, 0)),
            scratch_shapes=[pltpu.VMEM((stage, D), dtype),
                            pltpu.VMEM((stage, meta.shape[1]), jnp.int32),
                            pltpu.VMEM((bt, D), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((nb * bt, D), dtype),
        interpret=interpret,
        compiler_params=limit and pltpu.CompilerParams(
            vmem_limit_bytes=limit),
        name="ds_rowsum",
    )(runs[0].reshape(-1), runs[1].reshape(-1), y, meta)
    return out[:tokens]


def _count_sum(tokens, width, plan_rows, blocks, path):
    """One row of the step's own account (telemetry/tracing.py
    ``held_row_sums``) per shape of sum."""
    from deepspeed_tpu.telemetry.tracing import count_in_step
    count_in_step(held_row_sums={f"{tokens}x{width}:{plan_rows}": {
        "tokens": tokens, "width": width, "plan_rows": plan_rows,
        "blocks": blocks, "path": path}})


def _sum_live_into_tokens(y, gates, back: _WayBack, tokens, top_k, live,
                          chunk):
    """A held plan's rows ``y`` [Mp, D] — each weighted by its routed
    element's gate (rounded to ``y``'s dtype; the product float32) where
    there are ``gates`` (flat, [tokens * top_k]) — summed into
    ``[tokens, D]``: ONE float32 accumulator a token, rounded once to
    ``y``'s dtype; a token with no row here gets zeros, and a row behind
    the live prefix (``live`` rows), whatever it holds, is not read.  On
    one TPU (and under ``interpret``) the Mosaic kernel ``ds_rowsum``: a
    block of tokens a grid step, whose rows — one run in each expert's
    group (:func:`_token_block_runs`) — it fetches from ``y`` in HBM itself
    and adds up in VMEM, each token written once; what it reads beside
    ``y`` is an int32 array of the live rows' tokens and gates, written a
    chunk at a time.  Elsewhere — off the chip, and on more than one
    device, where the grouped kernels give way to ``ragged_dot`` too — the
    reference form: one scatter-add of every row of the plan, a row with no
    element dropped by its index.  The two differ in the order a token's
    rows are added in, and in nothing else."""
    Mp, D = y.shape
    use_reference, interpret = _use_reference(None)
    if use_reference:
        _count_sum(tokens, D, Mp, None, "xla")
        rows = y.astype(jnp.float32)
        if gates is not None:
            rows = _gate_in(y.dtype, jnp.take(
                gates, back.padded_to_row, mode="fill",
                fill_value=0))[:, None] * rows
        return jnp.zeros((tokens, D), jnp.float32).at[
            back.padded_to_row // top_k].add(rows, mode="drop").astype(
                y.dtype)
    blocks, copy = _rowsum_blocks(tokens), _rowsum_copy(y.dtype)
    assert Mp % copy == 0 and blocks[1] % copy == 0, (Mp, blocks, copy)
    _count_sum(tokens, D, Mp, blocks, "kernel")
    lane = jnp.arange(_ROWSUM_META_LANES, dtype=jnp.int32)[None, :]

    def describe(start, first, meta):
        # (a gather of single float32s costs by the element: the live
        # rows' alone)
        element, gate, at = _gate_and_token(
            gates, back.padded_to_row, top_k, start, chunk)
        bits = jax.lax.bitcast_convert_type(_gate_in(y.dtype, gate),
                                            jnp.int32)
        return _put_chunk(meta, jnp.where(
            lane == 0, at[:, None], jnp.where(lane == 1, bits[:, None], 0)),
            start)

    meta = _over_live_chunks(
        Mp, chunk, live, describe,
        _unwritten((Mp, _ROWSUM_META_LANES), jnp.int32, y, "meta"))
    return _pallas_rowsum(
        y, meta, _token_block_runs(back, tokens, top_k, blocks[0]), tokens,
        blocks, copy, gates is not None, interpret)


def _gate_in(dtype, gate):
    """A gate as the rows' dtype holds it, in float32: its product with a
    row of that dtype is then exact in float32."""
    return gate.astype(dtype).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _dispatch_held(xt, back, live, top_k, chunk):
    return _token_rows_live(xt, back.padded_to_row // top_k, live, chunk)


def _dispatch_held_fwd(xt, back, live, top_k, chunk):
    return (_dispatch_held(xt, back, live, top_k, chunk),
            (back, live, xt.shape[0]))


def _dispatch_held_bwd(top_k, chunk, res, g):
    back, live, tokens = res
    return (_sum_live_into_tokens(g, None, back, int(tokens), top_k, live,
                                  chunk), None, None)


_dispatch_held.defvjp(_dispatch_held_fwd, _dispatch_held_bwd)


def dispatch_held_rows(xt: jnp.ndarray, plan: GroupPlan, top_k: int):
    """As :func:`dispatch_rows` for a held-subset plan: the rows of the
    plan's live prefix gathered out of ``xt`` [T, D], a chunk at a time
    (padding rows inside the prefix are exact zeros; rows behind it are not
    written).  Backward: the live rows' cotangents summed into their tokens
    (a token has 0 to ``top_k`` rows here)."""
    chunk = _live_chunk_rows(plan, xt.shape[1] * xt.dtype.itemsize)
    return _dispatch_held(xt, _way_back(plan), live_rows(plan), top_k, chunk)


def _gate_and_token(gates, padded_to_row, top_k, start, chunk):
    """Of the ``chunk`` padded rows from ``start`` on: the routed element,
    its gate (0 on a padding row, whose element is ``R``) and its token
    (``R // top_k``: none)."""
    element = _chunk_of(padded_to_row, start, chunk)
    gate = jnp.ones(element.shape, jnp.float32) if gates is None \
        else jnp.take(gates, element, mode="fill", fill_value=0)
    return element, gate, element // top_k


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _combine_held(y, gates, back, live, top_k, chunk):
    return _sum_live_into_tokens(y, gates, back, gates.shape[0] // top_k,
                                 top_k, live, chunk)


def _combine_held_fwd(y, gates, back, live, top_k, chunk):
    return (_combine_held(y, gates, back, live, top_k, chunk),
            (y, gates, back.padded_to_row, live))


def _combine_held_bwd(top_k, chunk, res, g):
    y, gates, padded_to_row, live = res
    routed = gates.shape[0]

    def pull(start, first, carry):
        dy, dgates = carry
        element, gate, at = _gate_and_token(gates, padded_to_row, top_k,
                                            start, chunk)
        g_rows = _rows_or_zeros(g, at)
        d = jnp.sum(_chunk_of(y, start, chunk).astype(jnp.float32)
                    * g_rows.astype(jnp.float32), axis=-1)
        # (a row behind the prefix that the last chunk reaches is dropped
        # with its element, ``routed``, whatever ``y`` holds there)
        return (_put_chunk(dy, gate.astype(y.dtype)[:, None] * g_rows, start),
                dgates.at[jnp.where(_seen(start, first, chunk), routed,
                                    element)].add(d, mode="drop"))

    dy, dgates = _over_live_chunks(
        y.shape[0], chunk, live, pull,
        (_unwritten(y.shape, y.dtype, g, "dy"),
         jnp.zeros((routed,), jnp.float32)))
    return dy, dgates.astype(gates.dtype), None, None


_combine_held.defvjp(_combine_held_fwd, _combine_held_bwd)


def combine_held_rows(y: jnp.ndarray, gates: jnp.ndarray, plan: GroupPlan,
                      top_k: int):
    """The gated way back of a held-subset plan: the live prefix of
    the expert outputs ``y`` [Mp, D], each row weighted by its routed
    element's gate (``gates`` flat [T*top_k]; a padding row's is 0), summed
    into their tokens -> [T, D], a chunk at a time into one float32
    accumulator.  A token none of whose choices is held here gets zeros:
    what the absent experts would have added is left out.  Backward: the
    prefix's rows of ``dy`` from their tokens' cotangents, and each routed
    element's ``dgate`` summed into its place."""
    chunk = _live_chunk_rows(plan, y.shape[1] * y.dtype.itemsize)
    return _combine_held(y, gates, _way_back(plan), live_rows(plan), top_k,
                         chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _sum_held(y, back, live, top_k, chunk):
    return _sum_live_into_tokens(y, None, back, back.group_of_element.shape[0]
                                 // top_k, top_k, live, chunk)


# :func:`_dispatch_held`'s transpose, and that its own: the cotangent of a
# token's sum is every one of its rows', so nothing of ``y`` is kept
_sum_held.defvjp(
    lambda y, back, live, top_k, chunk: (
        _sum_held(y, back, live, top_k, chunk), (back, live)),
    lambda top_k, chunk, res, g: (
        _dispatch_held(g, *res, top_k, chunk), None, None))


def sum_held_rows(y: jnp.ndarray, plan: GroupPlan, top_k: int):
    """The way back of rows that carry their weight already (an exchange's
    returned rows, gated where their experts are: moe/layer.py
    ``_exchanged_grouped_moe``): the live prefix of ``y`` [Mp, D] summed
    into its tokens -> [T, D], one float32 accumulator a token, as
    :func:`combine_held_rows` with every gate 1.  Backward:
    :func:`dispatch_held_rows`' forward over the tokens' cotangents — a
    gather, and no row of ``y`` is a residual."""
    chunk = _live_chunk_rows(plan, y.shape[1] * y.dtype.itemsize)
    return _sum_held(y, _way_back(plan), live_rows(plan), top_k, chunk)


# ---- rows that landed once and are read by several experts.  An exchange
# brings a token's row to a chip once (moe/layer.py
# ``_exchanged_grouped_moe``): the landing buffer ``[L, D]`` holds a row a
# (token, sender), and the chip's plan reads it once for each of its experts
# the token chose.  ``source`` [Mp] says which: the landed row behind each
# plan row, ``L`` on a padding row and behind the live prefix.  Out of the
# buffer a gather over the live prefix; back into it — the experts' results
# summed by landed row, a cotangent's transpose of the gather — ``ds_rowsum``
# with the landed rows for its tokens: an expert's rows lie in landed order,
# so a block of landed rows has one run in each group (``runs``: whoever
# knows the senders' counts knows them, ``mappings.make_exchange_sizes``).
def landed_block_rows(landed_rows: int) -> int:
    """Landed rows a block of :func:`sum_into_landed_rows`: what the runs
    of its plan are counted by."""
    return _rowsum_blocks(int(landed_rows))[0]


def _sum_live_into_landed(y, source, runs, landed_rows, live, chunk):
    """:func:`_sum_live_into_tokens` for rows whose token is given a plan
    row (``source``) and whose runs are given: ``y`` [Mp, D] summed by
    ``source`` into ``[landed_rows, D]``, float32, rounded once."""
    Mp, D = y.shape
    use_reference, interpret = _use_reference(None)
    if use_reference:
        _count_sum(landed_rows, D, Mp, None, "xla")
        return jnp.zeros((landed_rows, D), jnp.float32).at[source].add(
            y.astype(jnp.float32), mode="drop").astype(y.dtype)
    blocks, copy = _rowsum_blocks(landed_rows), _rowsum_copy(y.dtype)
    assert Mp % copy == 0 and blocks[1] % copy == 0, (Mp, blocks, copy)
    _count_sum(landed_rows, D, Mp, blocks, "kernel")
    lane = jnp.arange(_ROWSUM_META_LANES, dtype=jnp.int32)[None, :]

    def describe(start, first, meta):
        at = _chunk_of(source, start, chunk)
        return _put_chunk(meta, jnp.where(lane == 0, at[:, None], 0), start)

    meta = _over_live_chunks(
        Mp, chunk, live, describe,
        _unwritten((Mp, _ROWSUM_META_LANES), jnp.int32, y, "meta"))
    return _pallas_rowsum(y, meta, runs, landed_rows, blocks, copy, False,
                          interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _gather_landed(landed, source, runs, live, landed_rows, chunk):
    return _token_rows_live(landed, source, live, chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _sum_landed(y, source, runs, live, landed_rows, chunk):
    return _sum_live_into_landed(y, source, runs, landed_rows, live, chunk)


def _keeping_source(move):
    """``move``'s forward rule: what says which landed row a plan row reads
    (the three arguments behind the rows) is all it keeps."""
    return lambda x, *rest: (move(x, *rest), rest[:3])


# each is the other's transpose, and neither keeps a row
_gather_landed.defvjp(
    _keeping_source(_gather_landed),
    lambda landed_rows, chunk, res, g: (
        _sum_landed(g, *res, landed_rows, chunk), None, None, None))
_sum_landed.defvjp(
    _keeping_source(_sum_landed),
    lambda landed_rows, chunk, res, g: (
        _gather_landed(g, *res, landed_rows, chunk), None, None, None))


def gather_landed_rows(landed: jnp.ndarray, plan: GroupPlan, source, runs):
    """``landed`` [L, D] -> the plan's ``[Mp, D]``: plan row ``p`` of the
    live prefix reads landed row ``source[p]``, exact zeros where that is
    ``L`` (a group's padding); rows behind the prefix are not written.
    Backward: :func:`sum_into_landed_rows` of the cotangent."""
    chunk = _live_chunk_rows(plan, landed.shape[1] * landed.dtype.itemsize)
    return _gather_landed(landed, source, runs, live_rows(plan),
                          landed.shape[0], chunk)


def sum_into_landed_rows(y: jnp.ndarray, plan: GroupPlan, source, runs,
                         landed_rows: int):
    """The plan's live rows ``y`` [Mp, D] summed by ``source`` ->
    ``[landed_rows, D]``: one float32 accumulator a landed row, rounded
    once (``ds_rowsum`` on one TPU; ``runs`` = (first, end), each [blocks
    of :func:`landed_block_rows`, E]); a landed row no plan row reads gets
    zeros.  Backward: :func:`gather_landed_rows` of the cotangent — no row
    of ``y`` is a residual."""
    chunk = _live_chunk_rows(plan, y.shape[1] * y.dtype.itemsize)
    return _sum_landed(y, source, runs, live_rows(plan), int(landed_rows),
                       chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _map_live(fn, chunk, live, xs):
    shape = jax.eval_shape(fn, *(jax.ShapeDtypeStruct(
        (chunk,) + x.shape[1:], x.dtype) for x in xs))

    def apply(start, first, out):
        return _put_chunk(
            out, fn(*(_chunk_of(x, start, chunk) for x in xs)), start)

    return _over_live_chunks(
        xs[0].shape[0], chunk, live, apply,
        _unwritten(xs[0].shape[:1] + shape.shape[1:], shape.dtype, xs[-1],
                   "mapped"))


def _map_live_fwd(fn, chunk, live, xs):
    return _map_live(fn, chunk, live, xs), (live, xs)


def _map_live_bwd(fn, chunk, res, g):
    live, xs = res

    def pull(start, first, dxs):
        _, vjp = jax.vjp(fn, *(_chunk_of(x, start, chunk) for x in xs))
        return tuple(_put_chunk(dx, d, start) for dx, d in
                     zip(dxs, vjp(_chunk_of(g, start, chunk))))

    return None, _over_live_chunks(
        g.shape[0], chunk, live, pull,
        tuple(_unwritten(x.shape, x.dtype, g, f"pulled{i}")
              for i, x in enumerate(xs)))


_map_live.defvjp(_map_live_fwd, _map_live_bwd)


def map_live_rows(fn, plan: GroupPlan, *xs):
    """``fn(*rows)`` row by row over the live prefix of the plan's
    ``[Mp, ·]`` arrays ``xs`` -> [Mp, ·], a chunk at a time (``fn`` acts on
    each row alone: the activation between two grouped calls).  Backward:
    ``fn``'s own, chunk by chunk over the same prefix."""
    widest = max(x.shape[1] * x.dtype.itemsize for x in xs)
    return _map_live(fn, _live_chunk_rows(plan, widest), live_rows(plan), xs)


def _sum_live(chunk, live, gs):
    """The sum of ``gs`` [Mp, ·] over the live prefix, taken in place in
    the first (the others' rows are added to its own, so no further
    ``[Mp, ·]`` buffer is live while they are)."""
    into, *others = gs

    def add(start, first, acc):
        mine = _chunk_of(acc, start, chunk)
        total = functools.reduce(
            jnp.add, (_chunk_of(g, start, chunk) for g in others), mine)
        return _put_chunk(acc, jnp.where(
            _seen(start, first, chunk)[:, None], mine, total), start)

    return _over_live_chunks(into.shape[0], chunk, live, add, into)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _fan_out(ways, chunk, x, live):
    return (x,) * ways


_fan_out.defvjp(
    lambda ways, chunk, x, live: ((x,) * ways, live),
    lambda ways, chunk, live, gs: (_sum_live(chunk, live, gs), None))


def fan_out_live_rows(x, plan: GroupPlan, ways: int):
    """``x`` [Mp, ·] ``ways`` times, for as many grouped calls that read
    it: the sum of their cotangents, which autodiff would take over every
    padded row, is taken over the live prefix."""
    chunk = _live_chunk_rows(plan, x.shape[1] * x.dtype.itemsize)
    return _fan_out(ways, chunk, x, live_rows(plan))


# ------------------------------------------------------------- reference
def _full_group_sizes(plan: GroupPlan) -> jnp.ndarray:
    """group_sizes covering every padded row (ragged_dot wants the total
    to span the operand; trailing all-zero tiles fold into the last
    group, matching the block_group_ids clamp)."""
    tail = plan.padded_rows - jnp.sum(plan.group_sizes)
    last = jnp.arange(plan.num_experts) == plan.num_experts - 1
    return plan.group_sizes + jnp.where(last, tail, 0)


def _ref_ggemm(x, w, plan: GroupPlan, transpose_rhs, out_dtype):
    """jnp reference over the SAME padded layout: one ragged_dot.  Fully
    differentiable — the CPU/multi-device fallback for training too."""
    if transpose_rhs:
        w = jnp.swapaxes(w, 1, 2)
    out = jax.lax.ragged_dot(x, w.astype(x.dtype), _full_group_sizes(plan))
    return out.astype(out_dtype) if out_dtype is not None else out


def _ref_ggemm_q(x, q, scales, plan: GroupPlan, out_dtype):
    from deepspeed_tpu.ops.pallas.quantization import block_dequantize_int8
    w = block_dequantize_int8(q, scales).astype(x.dtype)
    return _ref_ggemm(x, w, plan, False, out_dtype)


# --------------------------------------------------------------- kernels
# Grid (N/bn, m_tiles, K/bk).  Both scalar-prefetched operands reach every
# kernel and index map: ``gid_ref`` [m_tiles] names each M-tile's expert,
# ``used_ref`` [1] how many tiles hold a group.
def _accumulate(i, used_ref, o_ref, acc, n_k, product, live_only=False):
    """o = Σ_k product() for a tile that holds rows (no MXU work for one
    that trails).  A trailing tile is written as zeros, which the next
    grouped call and ``ds_ggemm_dw`` read — or, ``live_only``, not at all:
    its grid steps stay on the last live tile's output block
    (:func:`_pallas_ggemm`), which they leave as it is and Pallas writes
    back once, and the rows behind the prefix are nobody's.  Where one
    block spans K (``n_k`` 1) there is no scratch and the product goes
    straight out."""
    live = i < used_ref[0]
    if n_k == 1:
        @pl.when(live)
        def _rows():
            o_ref[:] = product().astype(o_ref.dtype)

        if not live_only:
            @pl.when(jnp.logical_not(live))
            def _trailing():
                o_ref[:] = jnp.zeros_like(o_ref)
        return
    acc_ref, = acc
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _rows():
        acc_ref[:] += product()

    last = k_idx == n_k - 1

    @pl.when(jnp.logical_and(last, live) if live_only else last)
    def _finalize():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _ggemm_kernel(gid_ref, used_ref, x_ref, w_ref, o_ref, *acc, n_k,
                  transpose_rhs, precision, live_only=False):
    """One (j, i, k) step: x_tile @ w[g[i]]_tile in fp32."""
    contract = ((1,), (1,)) if transpose_rhs else ((1,), (0,))

    def product():
        x = x_ref[:]                               # [bm, bk]
        w = w_ref[0]                               # [bk, bn] | [bn, bk]
        return jax.lax.dot_general(
            x, w.astype(x.dtype), (contract, ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

    _accumulate(pl.program_id(1), used_ref, o_ref, acc, n_k, product,
                live_only)


def _dequant_tile(qt, s, j, qblock, block_n, dtype):
    """The qgemm selector-matmul scale expansion: dequantize one
    [bk, bn] int8 tile in VMEM right before its MXU dot (shared by the
    group-padded and slot int8 kernels — the scale-group math must not
    diverge between the train/prefill and decode paths)."""
    nb = s.shape[1]
    g_iota = jax.lax.broadcasted_iota(jnp.int32, (nb, block_n), 0)
    col = j * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (nb, block_n), 1)
    sel = (g_iota == col // qblock).astype(jnp.float32)
    s_exp = jax.lax.dot(s, sel,
                        preferred_element_type=jnp.float32)   # [bk, bn]
    return (qt.astype(jnp.float32) * s_exp).astype(dtype)


def _ggemm_q_kernel(gid_ref, used_ref, x_ref, q_ref, s_ref, o_ref, *acc,
                    qblock, block_n, n_k, precision):
    """int8 expert tile: fused dequant (:func:`_dequant_tile`) of expert
    g[i]'s [bk, bn] tile; the int8 bytes are the only HBM weight
    traffic."""
    j = pl.program_id(0)

    def product():
        x = x_ref[:]                                # [bm, bk]
        w = _dequant_tile(q_ref[0], s_ref[0], j, qblock, block_n, x.dtype)
        return jax.lax.dot(x, w, preferred_element_type=jnp.float32,
                           precision=precision)

    _accumulate(pl.program_id(1), used_ref, o_ref, acc, n_k, product)


def _tgmm_kernel(gid_ref, used_ref, x_ref, dy_ref, o_ref, acc_ref, *, nm,
                 precision):
    """dw[e] = Σ_{rows of group e} x_row ⊗ dy_row.  Grid (K/bk, N/bn,
    m_tiles) with M innermost: group_ids are non-decreasing, so each
    expert's (k, j) output tile is visited in ONE contiguous run —
    accumulate across the run, flush on group change (or last tile).
    The trailing tiles lengthen expert E-1's run and add nothing."""
    i = pl.program_id(2)
    g = gid_ref[i]
    prev = gid_ref[jnp.maximum(i - 1, 0)]
    first = jnp.logical_or(i == 0, g != prev)
    nxt = gid_ref[jnp.minimum(i + 1, nm - 1)]
    last = jnp.logical_or(i == nm - 1, nxt != g)

    @pl.when(first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(i < used_ref[0])
    def _rows():
        x = x_ref[:]                                # [bm, bk]
        dy = dy_ref[:]                              # [bm, bn]
        acc_ref[:] += jax.lax.dot_general(
            x, dy.astype(x.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

    @pl.when(last)
    def _flush():
        o_ref[0] = acc_ref[:].astype(o_ref.dtype)


# ------------------------------------------------------- the block shapes
def _whole(dim, quantum=128):
    """Whether ``dim`` is tiled by one block that is the dimension itself:
    a multiple of 64 that no multiple of 128 divides (an expert 1856 =
    14.5 x 128 wide).  A block equal to the whole dimension is legal in
    Mosaic whatever its size, so nothing is padded; the compiler lays the
    last, half-filled lane tile out itself."""
    return dim % quantum != 0 and dim % (quantum // 2) == 0


def _one_block(dim, quantum=128):
    """The one block that spans ``dim``: itself, or a ragged dim padded."""
    return dim if _whole(dim, quantum) else _round_up(dim, quantum)


def _fit_block(dim, requested, quantum=128):
    """qgemm's divisor-fitting rule: shrink to a quantum-multiple that
    divides a 128-aligned dim (padding a non-dividing weight dim would
    materialize a padded copy of the WHOLE expert stack); a dim that is
    :func:`_whole` is its own block, whatever was requested; ragged dims
    (tests) keep the request and pad."""
    if _whole(dim, quantum):
        return dim
    b = min(requested, _round_up(dim, quantum))
    if dim % quantum == 0:
        for cand in range(max(b - b % quantum, quantum), quantum - 1,
                          -quantum):
            if dim % cand == 0:
                return cand
    return b


class _Tiling(NamedTuple):
    """What :func:`_choose_blocks` settled for one kernel call."""
    bk: int
    bn: int
    regime: str          # "resident": one block spans K | "streamed"
    weight_bytes: int    # expert weights the grid moves as tiled, at most
    operand_bytes: int   # rows in and out (dw: both row operands)
    vmem_bytes: int      # the buffers the call names


def _choose_blocks(kernel, rows, K, N, E, bm, sizes, blocks=None):
    """(bk, bn) of one call of ``kernel`` over ``rows`` padded rows, with
    the account of that tiling from shapes and blocks alone (``sizes``:
    bytes of a row, weight and result value).  Given ``blocks``, those
    (fitted to the dims); else whichever of two moves fewer bytes — one
    block over K beside the widest divisor of N whose buffers fit the
    device's VMEM budget, so that a weight panel stays in VMEM across its
    expert's M-tiles (dw: so that each row operand is read once per block
    of the other's dim), or the K-innermost ``_BLOCKS_KN`` where no such
    panel fits."""
    isz, wsz, osz = sizes
    m = rows // bm

    def tile(bk, bn):
        n_n, n_k = -(-N // bn), -(-K // bk)
        if kernel == "ds_ggemm_dw":
            # out [E, K, N] written once; x is re-read per N block, dy per
            # K block; fp32 scratch and product beside the output block
            weight = E * K * N * wsz
            operand = rows * (K * n_n + N * n_k) * isz
            vmem = (2 * bm * (bk + bn) * isz + 2 * bk * bn * wsz
                    + 2 * bk * bn * 4)
        else:
            # the weight block's index is (g[i], k, j): with one block
            # over K it changes only where the expert does (min(E, m)
            # times per N block); with K innermost, on every grid step
            weight = (min(E, m) if n_k == 1 else m) * K * N * wsz
            operand = rows * (K * n_n * isz + N * osz)
            vmem = (2 * bm * bk * isz + 2 * bk * bn * wsz
                    + 2 * bm * bn * osz + (1 + (n_k > 1)) * bm * bn * 4)
        return _Tiling(bk, bn, "resident" if n_k == 1 else "streamed",
                       weight, operand, vmem)

    if blocks is not None:
        return tile(_fit_block(K, blocks[0]), _fit_block(N, blocks[1]))
    streamed = tile(_fit_block(K, _BLOCKS_KN[0]), _fit_block(N, _BLOCKS_KN[1]))
    quantum = 128
    widths = ([b for b in range(N, 0, -quantum) if N % b == 0]
              if N % quantum == 0 else [_one_block(N)])
    budget = vmem.budget()
    for bn in widths:
        resident = tile(_one_block(K), bn)
        if resident.vmem_bytes <= budget:
            return min(resident, streamed,
                       key=lambda t: t.weight_bytes + t.operand_bytes)
    return streamed


def _count_call(kernel, K, N, tiling: _Tiling):
    """One row of the step's own account (telemetry/tracing.py
    ``grouped_gemm_rows``): what this call moves as tiled."""
    from deepspeed_tpu.telemetry.tracing import count_in_step
    count_in_step(grouped_calls={f"{kernel}:{K}x{N}": {
        "kernel": kernel, "k": K, "n": N, "blocks": (tiling.bk, tiling.bn),
        "regime": tiling.regime,
        "weight_bytes_per_call": tiling.weight_bytes,
        "operand_bytes_per_call": tiling.operand_bytes}})


def _compiler_params(tiling: _Tiling):
    """A raised VMEM limit where the call's buffers pass what it is
    granted unasked; else nothing."""
    limit = vmem.limit_for(tiling.vmem_bytes)
    return limit and pltpu.CompilerParams(vmem_limit_bytes=limit)


# --------------------------------------------------------- pallas drivers
def _precision_for(dtype):
    # fp32 operands need full-precision MXU passes (decode_attention.py)
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _pad_operands(x, w, scales, bk, bn, transpose_rhs):
    """Zero-pad K/N to tile multiples (tests and odd adapter shapes only
    — a real model dim is a multiple of 128, which the fitted blocks
    divide, or of 64, which is one block: :func:`_whole`)."""
    Mp, K = x.shape
    kdim, ndim = (2, 1) if transpose_rhs else (1, 2)
    K_pad, N_pad = _round_up(K, bk), _round_up(w.shape[ndim], bn)
    if K_pad != K:
        x = jnp.pad(x, ((0, 0), (0, K_pad - K)))
        wpad = [(0, 0)] * 3
        wpad[kdim] = (0, K_pad - K)
        w = jnp.pad(w, wpad)
        if scales is not None:
            scales = jnp.pad(scales, ((0, 0), (0, K_pad - K), (0, 0)),
                             constant_values=1.0)
    if N_pad != w.shape[ndim]:
        wpad = [(0, 0)] * 3
        wpad[ndim] = (0, N_pad - w.shape[ndim])
        # padded int8 columns are zero; their out-of-range scale group
        # matches no selector row, so they dequantize to 0 either way
        w = jnp.pad(w, wpad)
    return x, w, scales


def _live_tile(i, used):
    """The M-tile index a row operand fetches at grid position ``i``: a
    trailing tile repeats the last one that holds rows, and a block whose
    index did not change is not copied again."""
    return jnp.minimum(i, used[0] - 1)


def _weight_block(transpose_rhs):
    """Index map of the weight operand over the grid (j, i, k): expert
    g[i]'s (k, j) block."""
    if transpose_rhs:
        return lambda j, i, k, g, u: (g[i], j, k)
    return lambda j, i, k, g, u: (g[i], k, j)


def _pallas_ggemm(x, w, tiles, block_m, *, blocks, interpret, out_dtype,
                  transpose_rhs=False, scales=None, live_only=False):
    """x [Mp, K] group-padded; w [E, K, N] (or [E, N, K] with
    ``transpose_rhs``); ``tiles`` = the plan's ``(block_group_ids
    [Mp // block_m], used_blocks [1])``; ``scales`` [E, K, nb] selects
    the int8 kernel; ``blocks`` (bk, bn) or None for
    :func:`_choose_blocks`' own; ``live_only`` (float weights): the
    result's rows behind the live prefix are not written."""
    Mp, K = x.shape
    bm = block_m
    num_blocks = Mp // bm
    gids, used = tiles
    assert num_blocks * bm == Mp and gids.shape == (num_blocks,), \
        (x.shape, bm, gids.shape)
    ndim_ax = 1 if transpose_rhs else 2
    N = w.shape[ndim_ax]
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    name = ("ds_ggemm_q" if scales is not None
            else "ds_ggemm_dx" if transpose_rhs else "ds_ggemm_fwd")
    if scales is not None and blocks is None:
        blocks = _BLOCKS_KN      # per-tile dequantisation: today's tiling
    tiling = _choose_blocks(
        name, Mp, K, N, w.shape[0], bm,
        (x.dtype.itemsize, w.dtype.itemsize, out_dtype.itemsize), blocks)
    bk, bn = tiling.bk, tiling.bn
    # scale-group width is defined by the UNPADDED N (quantization.py
    # shape contract: gw = ceil(N / nb)); compute before any padding
    qblock = -(-N // scales.shape[-1]) if scales is not None else None
    x, w, scales = _pad_operands(x, w, scales, bk, bn, transpose_rhs)
    K_pad = x.shape[1]
    N_pad = w.shape[ndim_ax]
    n_k = K_pad // bk
    # N outermost, K innermost: with one block over K the weight block's
    # index is that of its expert and N block alone, equal for consecutive
    # M-tiles of one expert, and the panel stays where it is
    grid = (N_pad // bn, num_blocks, n_k)
    precision = _precision_for(x.dtype)
    _count_call(name, K, N, tiling)

    x_spec = pl.BlockSpec((bm, bk),
                          lambda j, i, k, g, u: (_live_tile(i, u), k))
    if scales is not None:
        assert not transpose_rhs, "int8 grouped GEMM has no transposed RHS"
        nb = scales.shape[-1]
        kernel = functools.partial(
            _ggemm_q_kernel, qblock=qblock, block_n=bn, n_k=n_k,
            precision=precision)
        in_specs = [
            x_spec,
            pl.BlockSpec((1, bk, bn), _weight_block(False)),
            pl.BlockSpec((1, bk, nb), lambda j, i, k, g, u: (g[i], k, 0)),
        ]
        operands = (x, w, scales.astype(jnp.float32))
    else:
        kernel = functools.partial(
            _ggemm_kernel, n_k=n_k, transpose_rhs=transpose_rhs,
            precision=precision, live_only=live_only)
        in_specs = [x_spec,
                    pl.BlockSpec((1, bn, bk) if transpose_rhs
                                 else (1, bk, bn),
                                 _weight_block(transpose_rhs))]
        operands = (x, w)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            # live_only: a trailing step stays on the last live tile's
            # block, as the row operand does, and nothing is written for it
            out_specs=pl.BlockSpec(
                (bm, bn),
                (lambda j, i, k, g, u: (_live_tile(i, u), j)) if live_only
                else (lambda j, i, k, g, u: (i, j))),
            scratch_shapes=([pltpu.VMEM((bm, bn), jnp.float32)]
                            if n_k > 1 else []),
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, N_pad), out_dtype),
        interpret=interpret,
        compiler_params=_compiler_params(tiling),
        name=name,
    )(gids, used, *operands)
    return out[:, :N]


def _pallas_tgmm(x, dy, tiles, block_m, num_experts, *, blocks, interpret,
                 out_dtype):
    """per-expert x^T @ dy over the padded layout -> [E, K, N]."""
    Mp, K = x.shape
    _, N = dy.shape
    bm = block_m
    gids, used = tiles
    out_dtype = jnp.dtype(out_dtype)
    tiling = _choose_blocks(
        "ds_ggemm_dw", Mp, K, N, num_experts, bm,
        (x.dtype.itemsize, out_dtype.itemsize, out_dtype.itemsize), blocks)
    bk, bn = tiling.bk, tiling.bn
    K_pad, N_pad = _round_up(K, bk), _round_up(N, bn)
    if K_pad != K:
        x = jnp.pad(x, ((0, 0), (0, K_pad - K)))
    if N_pad != N:
        dy = jnp.pad(dy, ((0, 0), (0, N_pad - N)))
    nm = Mp // bm
    _count_call("ds_ggemm_dw", K, N, tiling)
    kernel = functools.partial(_tgmm_kernel, nm=nm,
                               precision=_precision_for(x.dtype))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(K_pad // bk, N_pad // bn, nm),
            in_specs=[
                pl.BlockSpec((bm, bk),
                             lambda k, j, i, g, u: (_live_tile(i, u), k)),
                pl.BlockSpec((bm, bn),
                             lambda k, j, i, g, u: (_live_tile(i, u), j)),
            ],
            out_specs=pl.BlockSpec((1, bk, bn),
                                   lambda k, j, i, g, u: (g[i], k, j)),
            scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (num_experts, K_pad, N_pad), out_dtype),
        interpret=interpret,
        compiler_params=_compiler_params(tiling),
        name="ds_ggemm_dw",
    )(gids, used, x, dy)
    return out[:, :K, :N]


# ------------------------------------------------- small-M slot kernels
#
# Decode/verify-sized calls (R = B·top_k rows, one M-tile) invert the
# loop nest: grid (N/bn, K/bk, S) with the SLOT dim innermost, where the
# S = min(R, E) scalar-prefetched slots name the distinct routed experts
# in ascending order (trailing slots repeat the last id, so consecutive
# equal weight-block indices are NOT refetched).  Each expert's weights
# stream from HBM exactly once per step — the top-k-distinct-expert
# floor the ISSUE 8 acceptance names — and rows mask their own expert's
# contribution, so no group padding or scatter/gather exists at all.

class SlotPlan(NamedTuple):
    num_slots: int                 # static S = min(R, E)
    active: jnp.ndarray            # [S] distinct expert ids, ascending;
    #                                trailing slots repeat the last id
    valid: jnp.ndarray             # [S] int32 1/0 — real vs repeated slot
    eids_col: jnp.ndarray          # [R, 1] int32 row -> expert (-1 = pad)


def make_slot_plan(expert_ids: jnp.ndarray, num_experts: int) -> SlotPlan:
    R = int(expert_ids.shape[0])
    S = min(R, int(num_experts))
    eids = expert_ids.astype(jnp.int32)
    se = jnp.sort(eids)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), se[1:] != se[:-1]])
    slot_of = jnp.cumsum(first.astype(jnp.int32)) - 1        # [R]
    active = jnp.zeros((S,), jnp.int32).at[slot_of].set(se)
    nuniq = jnp.sum(first.astype(jnp.int32))
    valid = (jnp.arange(S, dtype=jnp.int32) < nuniq).astype(jnp.int32)
    # repeated trailing id keeps the weight-block index constant
    active = jnp.where(valid > 0, active, se[R - 1])
    return SlotPlan(S, active, valid, eids[:, None])


def _slot_contrib(x, w, eid_col, g, v, precision):
    part = jax.lax.dot(x, w, preferred_element_type=jnp.float32,
                       precision=precision)
    mask = jnp.logical_and(eid_col == g, v > 0)         # [bm, 1]
    return jnp.where(mask, part, 0.0)


def _slot_kernel(active_ref, valid_ref, x_ref, eid_ref, w_ref, o_ref,
                 acc_ref, *, n_k, n_s, precision):
    k_idx = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(jnp.logical_and(k_idx == 0, s == 0))
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[:]                                        # [bm, bk]
    w = w_ref[0].astype(x.dtype)                        # [bk, bn]
    acc_ref[:] += _slot_contrib(x, w, eid_ref[:], active_ref[s],
                                valid_ref[s], precision)

    @pl.when(jnp.logical_and(k_idx == n_k - 1, s == n_s - 1))
    def _finalize():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _slot_q_kernel(active_ref, valid_ref, x_ref, eid_ref, q_ref, s_ref,
                   o_ref, acc_ref, *, qblock, block_n, n_k, n_s,
                   precision):
    j = pl.program_id(0)
    k_idx = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(jnp.logical_and(k_idx == 0, s == 0))
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[:]
    w = _dequant_tile(q_ref[0], s_ref[0], j, qblock, block_n, x.dtype)
    acc_ref[:] += _slot_contrib(x, w, eid_ref[:], active_ref[s],
                                valid_ref[s], precision)

    @pl.when(jnp.logical_and(k_idx == n_k - 1, s == n_s - 1))
    def _finalize():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _pallas_ggemm_slots(x, w, plan: SlotPlan, *, block_k, block_n,
                        interpret, out_dtype, scales=None):
    """x [R, K] RAW routed rows (flat order, no scatter); w [E, K, N]."""
    R, K = x.shape
    N = w.shape[2]
    m_align = 16 if x.dtype == jnp.bfloat16 else 8
    bm = _round_up(R, m_align)
    bk = _fit_block(K, block_k)
    bn = _fit_block(N, block_n)
    qblock = -(-N // scales.shape[-1]) if scales is not None else None
    x, w, scales = _pad_operands(x, w, scales, bk, bn, False)
    if bm != R:
        x = jnp.pad(x, ((0, bm - R), (0, 0)))
    eid_col = jnp.pad(plan.eids_col, ((0, bm - R), (0, 0)),
                      constant_values=-1)
    K_pad, N_pad = x.shape[1], w.shape[2]
    n_k, n_s = K_pad // bk, plan.num_slots
    grid = (N_pad // bn, n_k, n_s)
    precision = _precision_for(x.dtype)
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    x_spec = pl.BlockSpec((bm, bk), lambda j, k, s, a, v: (0, k))
    e_spec = pl.BlockSpec((bm, 1), lambda j, k, s, a, v: (0, 0))
    if scales is not None:
        kernel = functools.partial(
            _slot_q_kernel, qblock=qblock, block_n=bn, n_k=n_k, n_s=n_s,
            precision=precision)
        in_specs = [
            x_spec, e_spec,
            pl.BlockSpec((1, bk, bn), lambda j, k, s, a, v: (a[s], k, j)),
            pl.BlockSpec((1, bk, scales.shape[-1]),
                         lambda j, k, s, a, v: (a[s], k, 0)),
        ]
        operands = (x, eid_col, w, scales.astype(jnp.float32))
    else:
        kernel = functools.partial(_slot_kernel, n_k=n_k, n_s=n_s,
                                   precision=precision)
        in_specs = [
            x_spec, e_spec,
            pl.BlockSpec((1, bk, bn), lambda j, k, s, a, v: (a[s], k, j)),
        ]
        operands = (x, eid_col, w)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda j, k, s, a, v: (0, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bm, N_pad), out_dtype),
        interpret=interpret,
        name="ds_ggemm_slots_q" if scales is not None else "ds_ggemm_slots",
    )(plan.active, plan.valid, *operands)
    return out[:R, :N]


#: rows at or below this ride the slot kernels (decode/verify regime);
#: above it the group-padded tiling wins (prefill/training-scale M)
SLOT_MAX_ROWS = 128


def ds_ggemm_slots(x, w, plan: SlotPlan, *, out_dtype=None, block_k=None,
                   block_n=None, interpret=None):
    """Small-M grouped GEMM over RAW routed rows ``x`` [R, K] (flat
    order; no group padding): row r contracts against
    ``w[plan.eids_col[r]]``.  Serving-only (no VJP) — the decode /
    verify-window path where each distinct expert's weights must stream
    exactly once per step."""
    from deepspeed_tpu.models.model import QuantizedTensor
    env = _env_blocks()
    bk = block_k or (env[1] if env else _BLOCKS_KN[0])
    bn = block_n or (env[2] if env else _BLOCKS_KN[1])
    if isinstance(w, QuantizedTensor):
        w = (w.q, w.s)
    use_ref, interp = _use_reference(interpret)
    if isinstance(w, tuple):
        q, scales = w
        if use_ref:
            from deepspeed_tpu.ops.pallas.quantization import \
                block_dequantize_int8
            wf = block_dequantize_int8(q, scales)
            return _ref_ggemm_rows(x, wf, plan.eids_col[:, 0], out_dtype)
        return _pallas_ggemm_slots(x, q, plan, block_k=bk, block_n=bn,
                                   interpret=interp, out_dtype=out_dtype,
                                   scales=scales)
    if use_ref:
        return _ref_ggemm_rows(x, w, plan.eids_col[:, 0], out_dtype)
    return _pallas_ggemm_slots(x, w, plan, block_k=bk, block_n=bn,
                               interpret=interp, out_dtype=out_dtype)


def _ref_ggemm_rows(x, w, eids, out_dtype):
    """Row-expert reference for the slot path: E static one-hot masked
    matmuls (small R, small E — the regime the slot kernel serves)."""
    E = w.shape[0]
    out = jnp.zeros((x.shape[0], w.shape[2]), jnp.float32)
    for e in range(E):
        ye = jnp.dot(x.astype(jnp.float32), w[e].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
        out = jnp.where((eids == e)[:, None], ye, out)
    return out.astype(out_dtype or x.dtype)


# ----------------------------------------------------- differentiable core
# static config (tile sizes, expert count, interpret flag) rides
# nondiff_argnums; the traced per-tile expert map (``tiles``: the plan's
# block_group_ids and used_blocks) is a primal whose cotangent is
# symbolic-zero (int32 -> float0).  ``blocks``: (bk, bn) of the forward
# call, or None for each kernel's own by :func:`_choose_blocks`.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ggemm_diff(x, w, tiles, block_m, num_experts, blocks, interpret,
                live_only):
    return _pallas_ggemm(x, w, tiles, block_m, blocks=blocks,
                         interpret=interpret, out_dtype=x.dtype,
                         live_only=live_only)


def _ggemm_diff_fwd(x, w, tiles, block_m, num_experts, blocks, interpret,
                    live_only):
    out = _ggemm_diff(x, w, tiles, block_m, num_experts, blocks, interpret,
                      live_only)
    return out, (x, w, tiles)


def _ggemm_diff_bwd(block_m, num_experts, blocks, interpret, live_only, res,
                    g):
    x, w, tiles = res
    # dx: same kernel, transposed contraction against the SAME expert map
    dx = _pallas_ggemm(g.astype(x.dtype), w, tiles, block_m,
                       blocks=blocks and blocks[::-1], interpret=interpret,
                       out_dtype=x.dtype, transpose_rhs=True,
                       live_only=live_only)
    dw = _pallas_tgmm(x, g.astype(x.dtype), tiles, block_m, num_experts,
                      blocks=blocks, interpret=interpret, out_dtype=w.dtype)
    return dx, dw, None


_ggemm_diff.defvjp(_ggemm_diff_fwd, _ggemm_diff_bwd)


# ---------------------------------------------------------------- dispatch
def _use_reference(interpret) -> Tuple[bool, bool]:
    """Returns (use_reference, interpret) with the qgemm gating rules."""
    if interpret is None:
        if os.environ.get("DS_GGEMM_INTERPRET") == "1" \
                or os.environ.get("DS_QGEMM_INTERPRET") == "1":
            return False, True
        from deepspeed_tpu.ops.attention import _on_tpu
        if not _on_tpu():
            return True, False
        if not vmem.call_on_one_device():
            # a call the partitioner would have to split: no GSPMD rule
            # for the pallas custom call (the qgemm precedent) — the
            # ragged_dot reference keeps EP/TP serving correct.  Inside a
            # manual region of the mesh (the expert-parallel exchange:
            # moe/layer.py) the call is one device's and the kernels run
            return True, False
        return False, False
    return False, bool(interpret)


def _maybe_span(x, args):
    """Perfetto ``moe/grouped_gemm`` span for EAGER kernel invocations
    (sweeps, op-level calls — ISSUE 8 satellite); under a trace the span
    would only time tracing, so it degrades to a no-op context."""
    if isinstance(x, jax.core.Tracer):
        import contextlib
        return contextlib.nullcontext()
    from deepspeed_tpu.telemetry import get_tracer
    return get_tracer().span("moe/grouped_gemm", cat="moe", args=args)


def ds_ggemm(x, w, plan: GroupPlan, *, out_dtype=None, block_k=None,
             block_n=None, interpret=None, transpose_rhs=False):
    """Grouped GEMM over a :class:`GroupPlan`-padded operand.

    ``x`` [Mp, K] rows sorted by expert and group-padded
    (:func:`scatter_to_groups`); ``w`` is the stacked expert weight —
    a plain ``[E, K, N]`` array, a ``(q int8 [E, K, N], scales
    [E, K, nb])`` pair, or a ``models.model.QuantizedTensor`` holding
    the same — and the result is ``[Mp, N]`` with row r computed against
    ``w[expert_of(r)]``.  Float inputs are differentiable (custom VJP on
    the kernel path; ragged_dot autodiff on the reference path).
    """
    from deepspeed_tpu.models.model import QuantizedTensor
    env = _env_blocks()
    # a block given by the caller or by DS_GGEMM_BLOCKS is taken as given;
    # with neither, _choose_blocks settles both per kernel
    blocks = None
    if block_k or block_n or env:
        blocks = (block_k or (env[1] if env else _BLOCKS_KN[0]),
                  block_n or (env[2] if env else _BLOCKS_KN[1]))
    if isinstance(w, QuantizedTensor):
        w = (w.q, w.s)
    quantized = isinstance(w, tuple)
    use_ref, interp = _use_reference(interpret)
    tiles = (plan.block_group_ids, plan.used_blocks)
    if quantized:
        q, scales = w
        if q.ndim != 3 or scales.ndim != 3:
            raise ValueError(
                f"ds_ggemm expects stacked [E, K, N] int8 weights "
                f"(q {q.shape}, scales {scales.shape})")
        if transpose_rhs:
            raise ValueError("int8 grouped GEMM has no transposed-RHS "
                             "form (backward is float-only)")
        if use_ref:
            return _ref_ggemm_q(x, q, scales, plan, out_dtype)
        with _maybe_span(x, {"shape": f"{x.shape[0]}x{q.shape[1]}"
                                      f"x{q.shape[2]}",
                             "experts": int(q.shape[0]), "int8": True}):
            return _pallas_ggemm(x, q, tiles, plan.block_m, blocks=blocks,
                                 interpret=interp,
                                 out_dtype=out_dtype or x.dtype,
                                 scales=scales)
    if use_ref:
        return _ref_ggemm(x, w, plan, transpose_rhs, out_dtype)
    if transpose_rhs:
        return _pallas_ggemm(x, w, tiles, plan.block_m, blocks=blocks,
                             interpret=interp,
                             out_dtype=out_dtype or x.dtype,
                             transpose_rhs=True, live_only=plan.live_only)
    with _maybe_span(x, {"shape": f"{x.shape[0]}x{w.shape[1]}"
                                  f"x{w.shape[2]}",
                         "experts": int(w.shape[0]), "int8": False}):
        out = _ggemm_diff(x, w, tiles, plan.block_m, plan.num_experts,
                          blocks, interp, plan.live_only)
    if out_dtype is not None:
        out = out.astype(out_dtype)
    return out
