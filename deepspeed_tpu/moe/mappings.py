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
grouped dispatch (moe/layer.py ``_exchanged_grouped_moe``): the all-to-all
carries rows from one grouped layout to another.  A chip sends its routed
rows from a held plan over ALL experts (``grouped_gemm``: sorted by expert,
so by chip too) and the chip that holds an expert receives them at their
place in that expert's group of ITS plan.  One small all-gather — every
chip's rows for every expert — and cumulative sums tell every chip both
layouts of every chip (:func:`make_exchange_sizes`), and the all-to-all
itself moves one slice a (chip, expert) (:func:`exchange_forth`,
:func:`exchange_back`: ``lax.ragged_all_to_all`` — a chip puts on the wire
the rows it has, and what it receives from all chips shares one buffer, so
the bound is on a chip's rows and not on a pair's).  A "row" is whatever
lies behind the first axis: the layer sends its activations ``[rows, D]``
and, through the same sizes, their gates, a few float32 lanes a row — the
narrow exchange, so that a routed row is weighted where its expert is and
the way back carries rows its sender only sums.  Each direction is the
other's transpose (``custom_vjp``), for rows and gates alike: a gate's
cotangent, formed on the expert's chip, comes home by
:func:`exchange_back`'s movement.  What a rematerialised layer runs of
them: forward out, out (gates) and back; recompute out and out (gates) —
nothing that came back is a residual; backward the three transposes.
What a call lands in depends on its side.  Out (:func:`_forth`: rows,
gates, and the cotangents of what came back), the buffer is the
receiver's bound, several times what arrives: it is born unwritten, the
last tile of each held expert's group is zeroed (the group's padding rows,
which the grouped kernels multiply), and behind the plan's live prefix
nothing is written, as nothing there is read.  Home (:func:`_back`), zeros:
a sender's layout is nearly all live, and a place whose row found no room
reads exact zero.
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
class ExchangeSizes(NamedTuple):
    """Who sends whom how many rows of which expert, and where they lie:
    the same numbers on every chip but ``me``'s own row and column of them.
    A slice is the rows one chip has for one expert; the arrays are
    ``[pairs * experts_held]``, a pair's slices side by side in expert
    order.  Sender ``j``'s slice for expert ``e`` of chip ``d`` leaves
    ``j``'s layout where that expert's group begins (``send_at``) and lands
    in ``d``'s layout inside the group of ``e``, behind the rows of the
    senders before ``j`` (``land_at``).  A chip has room for ``bound`` rows
    from all chips together, a sender's after those of the senders before
    it; what would pass it is cut from the end of a pair's rows — its
    highest experts' — and counted (``over``)."""
    send_at: jnp.ndarray        # in my layout, by (chip, its expert)
    send: jnp.ndarray           # rows I send (kept)
    land_at: jnp.ndarray        # in chip d's layout
    held_at: jnp.ndarray        # in my layout, by (sender, my expert)
    held: jnp.ndarray           # rows sender j sends me (kept)
    home_at: jnp.ndarray        # in sender j's layout
    counts: jnp.ndarray         # [experts_held] rows my experts receive
    over: jnp.ndarray           # [] my rows that found no room


def make_exchange_sizes(counts: jnp.ndarray, routed: int,
                        bound: int) -> ExchangeSizes:
    """``counts`` [E]: the rows this chip has for each of ALL experts, laid
    out as a held plan of ``routed`` rows over them
    (``grouped_gemm.make_held_group_plan``); ``bound``: the rows of a
    chip's receive plan over the experts it holds.  One small all-gather
    (the table of every chip's count for every expert) and cumulative
    sums: every chip's send layout and every chip's receive layout follow
    from the table (``grouped_gemm.held_group_starts``)."""
    from deepspeed_tpu.ops.pallas.grouped_gemm import held_group_starts
    table = lax.all_gather(counts.astype(jnp.int32), EXPERT_AXIS)
    n = table.shape[0]
    me = lax.axis_index(EXPERT_AXIS)
    rows = table.reshape(n, n, -1)                      # [from, to, expert]
    pair = jnp.sum(rows, axis=-1)
    # a receiver's room goes to the senders in their order, a pair's to its
    # experts in theirs
    room = jnp.clip(jnp.int32(bound) - (jnp.cumsum(pair, axis=0) - pair), 0,
                    pair)
    kept = jnp.clip(room[:, :, None] - (jnp.cumsum(rows, axis=-1) - rows), 0,
                    rows)
    received = jnp.sum(kept, axis=0)                    # [to, expert]
    lands = held_group_starts(received, bound)[0][None] \
        + jnp.cumsum(kept, axis=0) - kept
    starts = held_group_starts(table, routed)[0].reshape(rows.shape)
    flat = lambda a: a.reshape(-1)                      # noqa: E731
    return ExchangeSizes(flat(starts[me]), flat(kept[me]), flat(lands[me]),
                         flat(lands[:, me]), flat(kept[:, me]),
                         flat(starts[:, me]), received[me],
                         jnp.sum(rows[me] - kept[me]).astype(jnp.int32))


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _forth(rows, sizes: ExchangeSizes, out_rows, what):
    """Out, into a receive plan's buffer — the bound, mostly behind the live
    prefix: born unwritten, its groups' padding tiles zeroed
    (``grouped_gemm.zeroed_padding``; ``what`` names the buffer's use, as
    there), then the rows that arrive."""
    from deepspeed_tpu.ops.pallas.grouped_gemm import zeroed_padding
    # the buffer waits for the rows as they leave, not as they came: the
    # array they came in would else be live beside its re-tiled self
    sent = rows.reshape(rows.shape[:1] + _as_sent(rows))
    out = zeroed_padding(sizes.counts, (out_rows,) + sent.shape[1:],
                         rows.dtype, sent, what)
    return _ragged(sent, out, sizes.send_at, sizes.send, sizes.land_at,
                   sizes.held).reshape((out_rows,) + rows.shape[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _back(rows, sizes: ExchangeSizes, out_rows):
    """Home, into zeros: a sender's layout is nearly all live, and a place
    whose row found no room at its chip has to read exact zero."""
    return _ragged(rows, jnp.zeros((out_rows,) + rows.shape[1:], rows.dtype),
                   sizes.held_at, sizes.held, sizes.home_at, sizes.send)


# each is the other's transpose: a row's cotangent travels the way back
_forth.defvjp(
    lambda rows, sizes, out_rows, what: (_forth(rows, sizes, out_rows, what),
                                         (sizes, rows.shape[0])),
    lambda out_rows, what, res, g: (_back(g, res[0], res[1]), None))
_back.defvjp(
    lambda rows, sizes, out_rows: (_back(rows, sizes, out_rows),
                                   (sizes, rows.shape[0])),
    lambda out_rows, res, g: (_forth(g, res[0], res[1], "cotangents"), None))


def exchange_forth(rows: jnp.ndarray, sizes: ExchangeSizes, plan_rows: int,
                   what: str = "rows"):
    """One all-to-all of rows over the ``expert`` axis, inside a
    ``shard_map`` that maps it: this chip's rows ``[., D]`` in its send
    layout (a held plan over all experts) -> ``[plan_rows, D]`` in its
    receive layout, the group-padded array the grouped kernels read: within
    an expert's group the rows by sender, a sender's in its routed order,
    and the group's padding exact zeros; behind the last group — the plan's
    live prefix, ``grouped_gemm.live_rows`` — nothing is written, as in any
    ``[plan_rows, ·]`` array of a held plan.  ``what`` names the buffer
    among a layer's of one shape (``grouped_gemm.zeroed_padding``)."""
    return _forth(rows, sizes, int(plan_rows), what)


def exchange_back(rows: jnp.ndarray, sizes: ExchangeSizes, plan_rows: int):
    """The way back, slice for slice: the rows ``[., D]`` of the receive
    layout, each to the place of its sender's layout it came from ->
    ``[plan_rows, D]``; a place whose row found no room at its chip, and a
    padding row, reads exact zeros."""
    return _back(rows, sizes, int(plan_rows))
