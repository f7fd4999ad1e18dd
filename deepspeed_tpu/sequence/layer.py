"""Ulysses sequence parallelism (reference: deepspeed/sequence/layer.py:37
``DistributedAttention`` with ``_SeqAllToAll`` at :15).

The algorithm is identical to the reference: q/k/v arrive sequence-sharded
[B, S/sp, H, hd]; an all-to-all over the ``seq`` mesh axis scatters heads and
gathers sequence → [B, S, H/sp, hd]; local attention runs over the full
sequence on a subset of heads; a reverse all-to-all restores sequence sharding.
On TPU the all-to-alls are ``lax.all_to_all`` over the ``seq`` axis inside a
``shard_map`` — they ride ICI and XLA overlaps them with the attention matmuls.
"""
from functools import partial

import jax
from jax import lax
from jax.sharding import PartitionSpec as P
from deepspeed_tpu.utils.jax_compat import get_abstract_mesh, shard_map

from deepspeed_tpu.comm.mesh import get_topology, SEQ_AXIS, MODEL_AXIS


def seq_all_to_all(x, scatter_axis: int, gather_axis: int):
    """The reference's _SeqAllToAll: inside shard_map/jit collective."""
    return lax.all_to_all(x, SEQ_AXIS, split_axis=scatter_axis,
                          concat_axis=gather_axis, tiled=True)


def distributed_attention(q, k, v, local_attn, segment_ids=None):
    """q/k/v: [B, S, H, hd] (globally); runs ``local_attn`` inside a
    ``shard_map`` over the whole mesh — batch over the dp axes, heads over
    ``model`` and, when the ``seq`` axis is wide, the full sequence with
    heads scattered across it.

    ``local_attn(q, k, v[, segment_ids]) -> out`` must be shape-preserving.
    ``segment_ids`` [B, S] (packed sequences) enters the shard_map as a
    sharded operand — batch over the dp axes, sequence over seq — and is
    seq-all-gathered so the head-scattered local product sees the full
    sequence's mask.

    At ``sp == 1`` the all-to-alls drop out and what is left is the
    per-device local product: that is how a Pallas kernel, which the
    partitioner refuses to split ("Mosaic kernels cannot be automatically
    partitioned"), runs on a data/model-parallel mesh.
    """
    topo = get_topology()
    mesh = topo.mesh
    if mesh.size == 1:
        return (local_attn(q, k, v) if segment_ids is None
                else local_attn(q, k, v, segment_ids))
    sp = mesh.shape[SEQ_AXIS]
    # axes an enclosing shard_map already maps manually (the quantized
    # gradient exchange tier is manual over data/hpz): operands are
    # already local along them, so only the remaining axes are mapped here
    context = get_abstract_mesh()
    outer = frozenset(context.manual_axes)
    axes = frozenset(mesh.axis_names) - outer
    dp = tuple(a for a in topo.data_parallel_axes if a in axes)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    # a batch the dp group does not divide (single-sequence eval) stays
    # whole on every device
    batch = dp if dp and q.shape[0] % n_dp == 0 else None
    # fully-manual specs: batch over the dp axes, sequence over seq, heads
    # over model (a Mosaic kernel needs every mesh axis manual)
    spec = P(batch, SEQ_AXIS, MODEL_AXIS, None)
    seg_spec = P(batch, SEQ_AXIS)
    wrap = partial(shard_map, mesh=context if outer else mesh,
                   axis_names=axes, out_specs=spec, check_vma=False)

    def gather_seq(x):
        # [b, S/sp, h, hd] -> scatter heads(2), gather seq(1) -> [b, S, h/sp, hd]
        return seq_all_to_all(x, 2, 1) if sp > 1 else x

    if segment_ids is None:
        @partial(wrap, in_specs=(spec, spec, spec))
        def inner(ql, kl, vl):
            out = local_attn(gather_seq(ql), gather_seq(kl), gather_seq(vl))
            # reverse: scatter seq(1), gather heads(2)
            return seq_all_to_all(out, 1, 2) if sp > 1 else out

        return inner(q, k, v)

    @partial(wrap, in_specs=(spec, spec, spec, seg_spec))
    def inner_seg(ql, kl, vl, segl):
        seg = (lax.all_gather(segl, SEQ_AXIS, axis=1, tiled=True)
               if sp > 1 else segl)
        out = local_attn(gather_seq(ql), gather_seq(kl), gather_seq(vl), seg)
        return seq_all_to_all(out, 1, 2) if sp > 1 else out

    return inner_seg(q, k, v, segment_ids)


class DistributedAttention:
    """API-parity shim for the reference's module interface."""

    def __init__(self, local_attention, sequence_process_group=None,
                 scatter_idx: int = 2, gather_idx: int = 1):
        self.local_attn = local_attention

    def __call__(self, query, key, value, *args, **kwargs):
        return distributed_attention(
            query, key, value,
            lambda q, k, v: self.local_attn(q, k, v, *args, **kwargs))
