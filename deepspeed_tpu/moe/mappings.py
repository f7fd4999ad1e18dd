"""MoE × tensor-parallel token mappings (reference:
deepspeed/moe/mappings.py:28-101 — ``gather_tokens``/``drop_tokens``
all-gather activations across the TP group before expert routing and
re-slice after, so MoE composes with Megatron-style tensor parallelism).

TPU-native formulation: under SPMD the pair collapses to sharding
annotations.  ``gather_tokens`` constrains the dimension to be UNSHARDED
over the ``model`` axis (XLA inserts the all-gather) and ``drop_tokens``
constrains it to be sharded over ``model`` (XLA inserts the slice); the
autodiff transposes reproduce the reference's custom autograd pair
(_GatherTokens.backward = drop, _DropTokens.backward = gather) for free.
The in-tree MoE layer itself needs neither — its token dim is laid out
over the data/seq axes (moe/layer.py ``tok``), replicated across TP, so
routing, capacity, and the aux loss are TP-consistent by construction;
these entry points serve clients whose upstream activations arrive
TP-sharded (Megatron sequence-parallel blocks).

The second half of the file is the **expert-parallel exchange** of the
grouped dispatch (moe/layer.py ``_exchanged_grouped_moe``): two all-to-alls
out and one back, each from one grouped layout to another.  **The rows**
(the activations ``[rows, D]``) travel one a (token, destination chip): a
chip sends them from a held plan of its (token, chip) elements over the
chips (``grouped_gemm``: sorted by chip, a chip's in token order), one
slice a pair, and they land a row a (token, sender) — sender ``j``'s from
``j * tokens`` on — whatever number of the chip's experts a token chose.
**The lanes** (a few float32 a row: a routed row's gate, and the place its
token's row lands in) travel one a (token, expert): sent from a held plan
over ALL experts, and the chip that holds an expert receives them at their
place in that expert's group of ITS plan — the narrow exchange, so that the
receiver knows which landed row each row of its plan reads without sorting
anything, a routed row is weighted where its expert is, and the way back
carries sums its sender only adds up.  One small all-gather — every chip's
rows for every expert, counted before each block boundary of the landing
buffer, and for every chip — and cumulative sums tell every chip the
layouts of every chip (:func:`make_exchange_sizes`); the all-to-alls are
``lax.ragged_all_to_all`` (:func:`exchange_forth`, :func:`exchange_back`: a
chip puts on the wire the rows it has, and what it receives of the lanes
from all chips shares one buffer, so the bound is on a chip's rows and not
on a pair's; of the rows a sender's slot holds whatever it can send).  Each
direction is the other's transpose (``custom_vjp``), for rows and lanes
alike: a gate's cotangent, formed on the expert's chip, comes home by
:func:`exchange_back`'s movement.  What a rematerialised layer runs of
them: forward out, out (lanes) and back; recompute out and out (lanes) —
nothing that came back is a residual; backward the three transposes.
What a call lands in depends on its side.  Out (:func:`_forth`: rows,
lanes, and the cotangents of what came back), a buffer born unwritten: the
lanes' is the receiver's bound, several times what arrives — the last tile
of each held expert's group is zeroed (the group's padding rows, whose
gate and place must read 0), and behind the plan's live prefix nothing is
written, as nothing there is read; the rows' is read by row, and only
where the lanes say a row landed.  Home (:func:`_back`), zeros: a sender's
layout is nearly all live, and a place nothing came back to reads exact
zero.
"""
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.mesh import get_topology, EXPERT_AXIS, MODEL_AXIS


def _tp_size() -> int:
    try:
        return get_topology().mesh.shape[MODEL_AXIS]
    except Exception:
        return 1


def gather_tokens(x, dim: int = 0):
    """All-gather ``dim`` across the tensor-model axis (reference
    mappings.py:95 early-outs the same way when tp==1)."""
    if _tp_size() == 1:
        return x
    mesh = get_topology().mesh
    spec = [None] * x.ndim
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def drop_tokens(x, dim: int = 0):
    """Shard ``dim`` across the tensor-model axis — each TP rank keeps its
    1/tp slice (reference mappings.py:47 ``_drop_tokens``)."""
    if _tp_size() == 1:
        return x
    mesh = get_topology().mesh
    if x.shape[dim] % mesh.shape[MODEL_AXIS]:
        raise ValueError(
            f"drop_tokens: dim {dim} ({x.shape[dim]}) is not divisible by "
            f"tensor parallel world size ({mesh.shape[MODEL_AXIS]})")
    spec = [None] * x.ndim
    spec[dim] = MODEL_AXIS
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


# ------------------------------------------------- the expert exchange
class Slices(NamedTuple):
    """One all-to-all's slices, any whole number a pair of chips (a pair's
    side by side), the same numbers on every chip but ``me``'s own row and
    column of them: slice ``i`` leaves my layout at ``send_at[i]``,
    ``send[i]`` rows long, and lands at ``land_at[i]`` of its chip's; what
    I receive lies at ``held_at``, ``held`` rows each, and came from
    ``home_at`` of its sender's layout — where the way back puts it."""
    send_at: jnp.ndarray        # in my layout
    send: jnp.ndarray           # rows I send
    land_at: jnp.ndarray        # in chip d's layout
    held_at: jnp.ndarray        # in my layout, by sender
    held: jnp.ndarray           # rows sender j sends me
    home_at: jnp.ndarray        # in sender j's layout


class ExchangeSizes(NamedTuple):
    """Who sends whom what, and where it lies.  ``rows``: the activations,
    one slice a pair of chips — a token's row leaves for a chip once,
    whatever number of that chip's experts it chose; sender ``j``'s slice
    lands from ``j * tokens`` on, a place no other sender's can reach, so a
    row always finds room.  ``lanes``: the narrow exchange, one slice a
    (chip, expert), a row a (token, expert): sender ``j``'s slice for
    expert ``e`` of chip ``d`` leaves ``j``'s layout where that expert's
    group begins and lands in ``d``'s plan inside the group of ``e``,
    behind the rows of the senders before ``j``.  A chip's plan has room
    for ``bound`` rows from all chips together, a sender's after those of
    the senders before it; what would pass it is cut from the end of a
    pair's rows — its highest experts' — and counted (``over``)."""
    rows: Slices                # [pairs]
    lanes: Slices               # [pairs * experts_held]
    counts: jnp.ndarray         # [experts_held] plan rows my experts get
    over: jnp.ndarray           # [] my (token, expert) rows that found no room
    #: [blocks, experts_held] each: the plan rows of expert ``e`` whose
    #: landed row lies in block ``b`` of the landing buffer are the run
    #: ``[first[b, e], end[b, e])`` — a group's rows lie in landed order
    first: jnp.ndarray
    end: jnp.ndarray
    #: the sender's own, [tokens, pairs]: whether a token's row goes to a
    #: chip, and the place it has in that pair's slice (its tokens' order)
    to_chip: jnp.ndarray
    at: jnp.ndarray
    #: [pairs], the same on every chip: the (token, expert) rows each
    #: chip's plan receives from all chips together (the table's sums)
    chip_rows: jnp.ndarray


def make_exchange_sizes(chosen: jnp.ndarray, routed: int, bound: int,
                        block_rows: int) -> ExchangeSizes:
    """``chosen`` [tokens, E] bool: the experts each of this chip's tokens
    chose, the sender's two layouts being held plans
    (``grouped_gemm.make_held_group_plan``) — of its ``tokens * pairs``
    (token, chip) elements over the chips for the rows, of its ``routed``
    (token, choice) elements over all experts for the lanes; ``bound``: the
    rows of a chip's receive plan over the experts it holds;
    ``block_rows``: landed rows a block of the receiver's sums
    (``grouped_gemm.landed_block_rows``).  One small all-gather — every
    chip's rows for every expert, counted before each block boundary of
    the landing buffer, and its rows for every chip — and cumulative sums:
    every chip's layouts and every chip's plan follow from the table
    (``grouped_gemm.held_group_starts``)."""
    from deepspeed_tpu.ops.pallas.grouped_gemm import held_group_starts
    n = lax.axis_size(EXPERT_AXIS)
    me = lax.axis_index(EXPERT_AXIS)
    tokens, E = chosen.shape
    held = E // n
    by_chip = chosen.reshape(tokens, n, held)
    to_chip = jnp.any(by_chip, axis=-1)                 # [tokens, pairs]
    # a token's row is the ``at``-th of its slice, and lands there behind
    # ``me * tokens``: the rows before each boundary of the landing
    # buffer's blocks, by expert (the last boundary is behind every row)
    at = jnp.cumsum(to_chip.astype(jnp.int32), axis=0) - to_chip
    blocks = -(-n * tokens // int(block_rows))
    edges = jnp.arange(1, blocks + 1, dtype=jnp.int32) * int(block_rows)
    before = (me * tokens + at)[None] < edges[:, None, None]
    mine = jnp.einsum("btd,tde->bde", before.astype(jnp.float32),
                      by_chip.astype(jnp.float32)).astype(jnp.int32)
    pairs = jnp.sum(to_chip.astype(jnp.int32), axis=0)  # [pairs]
    table = lax.all_gather(jnp.concatenate(
        [mine.reshape(-1), pairs]), EXPERT_AXIS)
    pair_rows = table[:, blocks * E:]                   # [from, to]
    upto = table[:, :blocks * E].reshape(n, blocks, n, held)
    rows = upto[:, -1]                                  # [from, to, expert]
    pair = jnp.sum(rows, axis=-1)
    # a receiver's room goes to the senders in their order, a pair's to its
    # experts in theirs
    room = jnp.clip(jnp.int32(bound) - (jnp.cumsum(pair, axis=0) - pair), 0,
                    pair)
    kept = jnp.clip(room[:, :, None] - (jnp.cumsum(rows, axis=-1) - rows), 0,
                    rows)
    received = jnp.sum(kept, axis=0)                    # [to, expert]
    groups = held_group_starts(received, bound)[0]
    lands = groups[None] + jnp.cumsum(kept, axis=0) - kept
    starts = held_group_starts(rows.reshape(n, E), routed)[0].reshape(
        rows.shape)
    flat = lambda a: a.reshape(-1)                      # noqa: E731
    lanes = Slices(flat(starts[me]), flat(kept[me]), flat(lands[me]),
                   flat(lands[:, me]), flat(kept[:, me]),
                   flat(starts[:, me]))
    leaves = held_group_starts(pair_rows, n * tokens)[0]
    slots = jnp.arange(n, dtype=jnp.int32) * tokens
    wide = Slices(leaves[me], pair_rows[me], jnp.broadcast_to(
        me * tokens, (n,)).astype(jnp.int32), slots, pair_rows[:, me],
        leaves[:, me])
    # a sender's kept rows of an expert are its first, so of those before
    # a boundary the kept ones are the count's first ``kept``
    seen = jnp.sum(jnp.minimum(upto[:, :, me], kept[:, None, me]), axis=0)
    ends = (groups[me][None] + seen).astype(jnp.int32)  # [blocks, held]
    first = jnp.concatenate([jnp.broadcast_to(groups[me], (1, held)),
                             ends[:-1]]).astype(jnp.int32)
    return ExchangeSizes(wide, lanes, received[me],
                         jnp.sum(rows[me] - kept[me]).astype(jnp.int32),
                         first, ends, to_chip, at,
                         jnp.sum(received, axis=-1).astype(jnp.int32))


#: the two ways an exchange's rows travel, as ``tracing.exchange_calls``
#: names them: the collective of the chip, and its stand-in off the chip
RAGGED_ALL_TO_ALL = "ragged_all_to_all"
ALL_TO_ALL = "all_to_all"


def exchange_path() -> str:
    """Which collective :func:`_ragged` traces here: :data:`RAGGED_ALL_TO_ALL`
    on a TPU, :data:`ALL_TO_ALL` elsewhere (jax 0.9.0's CPU backend has no
    ragged one: ``UNIMPLEMENTED: HLO opcode `ragged-all-to-all` is not
    supported by XLA:CPU ThunkEmitter``)."""
    from deepspeed_tpu.ops.attention import _on_tpu
    return RAGGED_ALL_TO_ALL if _on_tpu() else ALL_TO_ALL


def _as_sent(rows):
    """The shape behind the first axis in which the chip's collective moves
    a row of ``rows`` [., width]: ``lax.ragged_all_to_all`` copies rows
    from any offset to any offset, so the compiler gives it arrays whose
    every row is whole tiles of its own — ``[packing, width / packing]``,
    ``packing`` the elements a 32-bit word holds (bf16 ``[rows, 2304]``
    travels as ``[rows, 2, 1152]`` in tiles of 2 x 128, float32 ``[rows,
    128]`` as ``[rows, 1, 128]``) — and re-tiles, a pass over the array,
    what comes in another.  A ``broadcast`` is born in any tiling; a Mosaic
    call's result in its shape's, so the buffer a kernel makes for the
    collective to land in is asked for in this shape (:func:`_forth`).
    Off the chip, and for a width that is no whole tiles: as it is."""
    packing = 4 // rows.dtype.itemsize
    if exchange_path() != RAGGED_ALL_TO_ALL or rows.ndim != 2 \
            or rows.shape[1] % (128 * packing):
        return rows.shape[1:]
    return (packing, rows.shape[1] // packing)


def _rows_at(x, idx):
    return x.at[idx].get(mode="promise_in_bounds")


def _ragged(rows, out, send_at, send, land_at, held):
    """``lax.ragged_all_to_all`` over the ``expert`` axis into ``out``, any
    whole number of slices a chip (a chip's side by side): slice ``i`` is
    ``send[i]`` rows from ``send_at[i]`` on, lands from ``land_at[i]`` on at
    its chip, and ``held[i]`` rows arrive for it; a row of ``out`` that no
    slice covers keeps what it held.  Where the backend has no such
    collective (:func:`exchange_path`: the CPU's test mesh) the same
    movement by ``lax.all_to_all``: every chip's whole buffer and where its
    slices lie, each row of the result then looked up in the slice that
    covers it."""
    if exchange_path() == RAGGED_ALL_TO_ALL:
        return lax.ragged_all_to_all(rows, out, send_at, send, land_at,
                                     held, axis_name=EXPERT_AXIS)
    n, length, out_rows = lax.axis_size(EXPERT_AXIS), rows.shape[0], \
        out.shape[0]
    to_me = lambda a: lax.all_to_all(                   # noqa: E731
        a.reshape(n, -1), EXPERT_AXIS, 0, 0).reshape(-1)
    got = lax.all_to_all(jnp.broadcast_to(rows, (n,) + rows.shape),
                         EXPERT_AXIS, 0, 0)             # [from, length, ..]
    lands, froms = to_me(land_at), to_me(send_at)
    within = jnp.arange(out_rows, dtype=jnp.int32)[:, None] - lands[None, :]
    covers = (within >= 0) & (within < held[None, :])   # [out_rows, slices]
    which = jnp.argmax(covers, axis=1)
    sender = which // (held.shape[0] // n)
    source = sender * length + _rows_at(froms, which) \
        + jnp.take_along_axis(within, which[:, None], axis=1)[:, 0]
    row = _rows_at(got.reshape((n * length,) + rows.shape[1:]),
                   jnp.clip(source, 0, n * length - 1))
    return jnp.where(jnp.any(covers, axis=1).reshape(
        (-1,) + (1,) * (rows.ndim - 1)), row, out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _forth(rows, slices: Slices, counts, out_rows, what):
    """Out, into a buffer born unwritten (``what`` names its use among a
    layer's of one shape, as ``grouped_gemm._unwritten`` takes it), then
    the rows that arrive.  With ``counts`` the buffer is a receive plan's —
    the bound, mostly behind the live prefix — and its groups' padding
    tiles are zeroed first (``grouped_gemm.zeroed_padding``); without, a
    landing buffer nobody reads but where a row landed."""
    from deepspeed_tpu.ops.pallas.grouped_gemm import zeroed_padding
    # the buffer waits for the rows as they leave, not as they came: the
    # array they came in would else be live beside its re-tiled self
    sent = rows.reshape(rows.shape[:1] + _as_sent(rows))
    out = zeroed_padding(counts, (out_rows,) + sent.shape[1:], rows.dtype,
                         sent, what)
    return _ragged(sent, out, slices.send_at, slices.send, slices.land_at,
                   slices.held).reshape((out_rows,) + rows.shape[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _back(rows, slices: Slices, counts, out_rows):
    """Home, into zeros: a sender's layout is nearly all live, and a place
    whose row found no room at its chip has to read exact zero.  (``counts``
    rides along for the transpose, whose buffer it describes.)"""
    return _ragged(rows, jnp.zeros((out_rows,) + rows.shape[1:], rows.dtype),
                   slices.held_at, slices.held, slices.home_at, slices.send)


# each is the other's transpose: a row's cotangent travels the way back
_forth.defvjp(
    lambda rows, slices, counts, out_rows, what: (
        _forth(rows, slices, counts, out_rows, what),
        (slices, counts, rows.shape[0])),
    lambda out_rows, what, res, g: (_back(g, *res), None, None))
_back.defvjp(
    lambda rows, slices, counts, out_rows: (
        _back(rows, slices, counts, out_rows),
        (slices, counts, rows.shape[0])),
    lambda out_rows, res, g: (_forth(g, *res, "cotangents"), None, None))


def exchange_forth(rows: jnp.ndarray, slices: Slices, out_rows: int,
                   what: str = "rows", counts=None):
    """One all-to-all over the ``expert`` axis, inside a ``shard_map`` that
    maps it: this chip's rows ``[., D]`` in its send layout -> ``[out_rows,
    D]`` in its receive layout.  For the activations (``ExchangeSizes.rows``:
    the sender's layout a held plan of its (token, chip) elements over the
    chips) the landing buffer, ``pairs * tokens`` rows: sender ``j``'s from
    ``j * tokens`` on in its token order, and nothing written elsewhere.
    For the lanes (``ExchangeSizes.lanes``, and ``counts`` — the rows each
    held expert receives: the sender's layout a held plan of its routed
    elements over all experts) the group-padded array of the receiver's
    plan: within an expert's group the rows by sender, a sender's in its
    routed order, and the group's padding exact zeros; behind the last
    group — the plan's live prefix, ``grouped_gemm.live_rows`` — nothing
    is written, as in any ``[plan_rows, ·]`` array of a held plan.
    ``what`` names the buffer among a layer's of one shape."""
    return _forth(rows, slices, counts, int(out_rows), what)


def exchange_back(rows: jnp.ndarray, slices: Slices, out_rows: int):
    """The way back of what landed a row a (token, sender), slice for
    slice: the rows ``[., D]`` of the landing buffer, each to the place of
    its sender's layout it came from -> ``[out_rows, D]``; a place nothing
    came back to (a padding row) reads exact zeros."""
    return _back(rows, slices, None, int(out_rows))
