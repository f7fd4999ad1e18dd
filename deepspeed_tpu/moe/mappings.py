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
grouped dispatch (moe/layer.py ``_exchanged_grouped_moe``): a chip's routed
rows laid out by the chip that holds their expert
(:func:`make_exchange_plan`), the rows into that order and back out of it
by gathers alone, forward and backward (:func:`send_rows`,
:func:`return_rows`), who sends whom how many rows and where they land
(:func:`make_exchange_sizes`), and the all-to-all itself
(:func:`exchange_forth`, :func:`exchange_back`: ``lax.ragged_all_to_all``
— a chip puts on the wire the rows it has, and what it receives from all
chips shares one buffer, so the bound is on a chip's rows and not on a
pair's).
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
class ExchangePlan(NamedTuple):
    """Where this chip's routed rows go: its ``R`` routed elements (flat,
    token-major: ``f = t * top_k + choice``) in the order it sends them —
    by the chip of the ``expert`` axis that holds their expert, a chip's in
    routed order.  The two maps are each other's inverse, so rows move by
    gathers in both directions."""
    pairs: int                  # static: chips of the expert axis
    by_chip: jnp.ndarray        # [R] place in the send order -> element
    place: jnp.ndarray          # [R] element -> place in the send order
    local_expert: jnp.ndarray   # [R] by place: the row's expert on the
    #                             chip it goes to
    sizes: jnp.ndarray          # [pairs] rows for each chip


def make_exchange_plan(expert_ids: jnp.ndarray, experts_held: int,
                       pairs: int) -> ExchangePlan:
    """``expert_ids`` [R] over ALL experts (chip ``d`` holds experts ``[d *
    experts_held, (d + 1) * experts_held)``) -> the plan.  Two stable
    sorts (rows by chip, and that order sorted back by row) and a count: no
    scatter."""
    R = int(expert_ids.shape[0])
    eids = expert_ids.astype(jnp.int32)
    dest = eids // int(experts_held)
    flat = jnp.arange(R, dtype=jnp.int32)
    sizes = jnp.sum((dest[:, None] == jnp.arange(
        int(pairs), dtype=jnp.int32)[None, :]).astype(jnp.int32), axis=0)
    _, by_chip = lax.sort((dest, flat), num_keys=1, is_stable=True)
    _, place = lax.sort((by_chip, flat), num_keys=1, is_stable=True)
    local = _rows_at(eids, by_chip) % int(experts_held)
    return ExchangePlan(int(pairs), by_chip, place, local, sizes)


def _rows_at(x, idx):
    return x.at[idx].get(mode="promise_in_bounds")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _send(xt, by_chip, place, top_k):
    return _rows_at(xt, by_chip // top_k)


def _send_fwd(xt, by_chip, place, top_k):
    return _send(xt, by_chip, place, top_k), place


def _send_bwd(top_k, place, g):
    # a token's top_k cotangent rows summed in float32, rounded once
    rows = _rows_at(g, place).reshape(-1, top_k, *g.shape[1:])
    return (jnp.sum(rows.astype(jnp.float32), axis=1).astype(g.dtype),
            None, None)


_send.defvjp(_send_fwd, _send_bwd)


def send_rows(xt: jnp.ndarray, plan: ExchangePlan, top_k: int):
    """Token-major ``xt`` [T, D] -> the send buffer [T * top_k, D]: place
    ``p`` reads token ``by_chip[p] // top_k``.  Backward: a gather by
    ``place``, summed over ``top_k``."""
    return _send(xt, plan.by_chip, plan.place, top_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _return(back, gates, by_chip, place, top_k):
    rows = _rows_at(back, place).astype(jnp.float32)
    # a gate as the rows' dtype holds it: the product is exact in float32
    g = gates.astype(back.dtype).astype(jnp.float32)
    return jnp.sum((g[:, None] * rows).reshape(-1, top_k, back.shape[1]),
                   axis=1).astype(back.dtype)


def _return_fwd(back, gates, by_chip, place, top_k):
    return (_return(back, gates, by_chip, place, top_k),
            (back, gates, by_chip, place))


def _return_bwd(top_k, res, g):
    back, gates, by_chip, place = res
    dback = (_rows_at(gates.astype(back.dtype), by_chip)[:, None]
             * _rows_at(g, by_chip // top_k))
    rows = _rows_at(back, place).reshape(-1, top_k, back.shape[1])
    dgates = jnp.sum(rows.astype(jnp.float32)
                     * g.astype(jnp.float32)[:, None, :], axis=-1)
    return dback, dgates.reshape(gates.shape).astype(gates.dtype), None, None


_return.defvjp(_return_fwd, _return_bwd)


def return_rows(back: jnp.ndarray, gates: jnp.ndarray, plan: ExchangePlan,
                top_k: int):
    """The buffer that came back [T * top_k, D] (place for place what
    :func:`send_rows` sent, through the experts; exact zeros where a row
    found no room at its chip) and flat ``gates`` [T * top_k] -> [T, D]: a
    token's ``top_k`` rows, each weighted by its gate, in ONE float32 sum
    rounded once.  Backward: ``dback[p] = gates[by_chip[p]] *
    dout[by_chip[p] // top_k]`` and ``dgates[f] = back[place[f]] . dout[f
    // top_k]``."""
    return _return(back, gates, plan.by_chip, plan.place, top_k)


class ExchangeSizes(NamedTuple):
    """Who sends whom how many rows, and where they lie, the same numbers
    on every chip but ``me``'s own row and column of them.  Chip ``j``'s
    rows for chip ``d`` start at ``send_at[d]`` of ``j``'s send buffer and
    land at ``land_at[d]`` of ``d``'s receive buffer: behind those of the
    chips before ``j``, so what a chip receives is one prefix of its
    buffer.  A receive buffer holds ``bound`` rows; what would pass it is
    cut from the end of a pair's rows and counted (``over``)."""
    send_at: jnp.ndarray        # [pairs] in my send buffer, by chip
    send: jnp.ndarray           # [pairs] rows I send each chip (kept)
    land_at: jnp.ndarray        # [pairs] in chip d's receive buffer
    held_at: jnp.ndarray        # [pairs] in my receive buffer, by sender
    held: jnp.ndarray           # [pairs] rows each chip sends me (kept)
    home_at: jnp.ndarray        # [pairs] in sender j's send buffer
    over: jnp.ndarray           # [] my rows that found no room


def make_exchange_sizes(sizes: jnp.ndarray, bound: int) -> ExchangeSizes:
    """``sizes`` [pairs]: the rows this chip has for each chip.  One small
    all-gather (the table of every pair's count) and arithmetic."""
    table = lax.all_gather(sizes, EXPERT_AXIS)          # [from, to]
    me = lax.axis_index(EXPERT_AXIS)
    starts = jnp.cumsum(table, axis=1) - table          # in the sender's
    lands = jnp.cumsum(table, axis=0) - table           # in the receiver's
    kept = jnp.clip(jnp.int32(bound) - lands, 0, table)
    return ExchangeSizes(starts[me], kept[me], lands[me], lands[:, me],
                         kept[:, me], starts[:, me],
                         jnp.sum(table[me] - kept[me]).astype(jnp.int32))


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


def _ragged(rows, out_rows, fill, send_at, send, land_at, held):
    """``lax.ragged_all_to_all`` over the ``expert`` axis into a buffer of
    ``out_rows`` rows of ``fill``: ``send[d]`` rows from ``send_at[d]`` on
    go to chip ``d`` and land from ``land_at[d]`` on; ``held[j]`` rows
    arrive from chip ``j``.  Where the backend has no such collective
    (:func:`exchange_path`: the CPU's test mesh) the same movement by
    ``lax.all_to_all`` of segments as long as the whole send buffer, each
    row then put where the ragged one would have put it."""
    out = jnp.full((out_rows,) + rows.shape[1:], fill, rows.dtype)
    if exchange_path() == RAGGED_ALL_TO_ALL:
        return lax.ragged_all_to_all(rows, out, send_at, send, land_at,
                                     held, axis_name=EXPERT_AXIS)
    length = rows.shape[0]
    at = jnp.arange(length, dtype=jnp.int32)
    segments = jnp.stack([
        _rows_at(rows, jnp.minimum(send_at[d] + at, length - 1))
        for d in range(send.shape[0])])
    got = lax.all_to_all(segments, EXPERT_AXIS, 0, 0)   # [from, length, ..]
    lands = lax.all_to_all(land_at, EXPERT_AXIS, 0, 0)  # in my buffer
    at = jnp.arange(out_rows, dtype=jnp.int32)
    for j in range(got.shape[0]):
        i = at - lands[j]
        taken = (i >= 0) & (i < held[j])
        row = _rows_at(got[j], jnp.clip(i, 0, length - 1))
        out = jnp.where(taken.reshape((-1,) + (1,) * (rows.ndim - 1)), row,
                        out)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _forth(rows, sizes: ExchangeSizes, bound):
    return _ragged(rows, bound, 0, sizes.send_at, sizes.send, sizes.land_at,
                   sizes.held)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _back(rows, sizes: ExchangeSizes, routed):
    return _ragged(rows, routed, 0, sizes.held_at, sizes.held,
                   sizes.home_at, sizes.send)


# each is the other's transpose: a row's cotangent travels the way back
_forth.defvjp(
    lambda rows, sizes, bound: (_forth(rows, sizes, bound),
                                (sizes, rows.shape[0])),
    lambda bound, res, g: (_back(g, res[0], res[1]), None))
_back.defvjp(
    lambda rows, sizes, routed: (_back(rows, sizes, routed),
                                 (sizes, rows.shape[0])),
    lambda routed, res, g: (_forth(g, res[0], res[1]), None))


def exchange_forth(rows: jnp.ndarray, sizes: ExchangeSizes, bound: int):
    """One all-to-all of rows over the ``expert`` axis, inside a
    ``shard_map`` that maps it: the send buffer ``rows`` [R, D] (by chip)
    -> the receive buffer [bound, D], whose first ``sum(sizes.held)`` rows
    are what arrived, by sender; the rest exact zeros."""
    return _forth(rows, sizes, int(bound))


def exchange_back(rows: jnp.ndarray, sizes: ExchangeSizes, routed: int):
    """The way back: the receive buffer's rows [bound, D], each to the
    place of the send buffer it came from -> [routed, D]; a place whose
    row found no room at its chip reads exact zeros."""
    return _back(rows, sizes, int(routed))


def exchange_experts(local_expert: jnp.ndarray, sizes: ExchangeSizes,
                     bound: int, nobody: int):
    """The rows' experts' numbers the way the rows go (no gradient):
    ``nobody`` where the receive buffer holds no row."""
    return _ragged(local_expert, int(bound), nobody, sizes.send_at,
                   sizes.send, sizes.land_at, sizes.held)
