"""ZeRO stages as sharding policies over parameter/gradient/optimizer pytrees.

Reference semantics being reproduced (SURVEY.md §2.3):

- stage 0 — plain data parallelism: replicated params/opt state, all-reduced grads
  (reference: engine.py:2266 bucketed allreduce).
- stage 1 — optimizer state partitioned over the DP group (reference:
  stage_1_and_2.py:95 with partition_grads=False): grads all-reduced, each rank
  updates its shard, updated params all-gathered (stage_1_and_2.py:1700).
- stage 2 — gradients partitioned too (stage_1_and_2.py:1271 reduce_ipg_grads →
  reduce_scatter).
- stage 3 — parameters partitioned as well; gathered on use (stage3.py:72,
  partition_parameters.py:707).

On TPU there are no hooks or buckets: each stage is a triple of shardings
(param storage, gradient, optimizer state).  The train step is jitted with those
in/out shardings plus ``with_sharding_constraint`` on the grads; XLA's SPMD
partitioner then inserts exactly the collectives the reference issues by hand —
psum for replicated grads, reduce-scatter for sharded grads, all-gather for
sharded params at use sites — and overlaps them with compute (the reference's
``overlap_comm`` side-stream, stage_1_and_2.py:963, is automatic).

Sharding rule per array: add the ZeRO mesh axes to the first dimension that is
divisible by the ZeRO world size and not already sharded by the logical (TP) spec
(an axis of size one shards nothing: with tensor parallelism off, a row-parallel
weight takes the ZeRO axes on its leading weight dimension like any other, which
is where the TPU's fused reduce-scatter can take the gradient) — except dim 0 of
a leaf under the model's layer-stacked subtree (``stacked_key``), which is never
a ZeRO dimension: the layer scan slices that axis, and a ZeRO shard on it makes
XLA gather the whole stack inside the forward and the backward loop (every
iteration gathers all L layers and slices one out).  The shard sits on a weight
dimension instead, the same one for parameters, gradients and optimizer state,
and the engine gathers one layer's slice where the layer is used
(``models/model.py`` ``maybe_stream``, mode ``gather``).
Small params below ``param_persistence_threshold`` (a leaf's total size) stay
replicated, matching the reference's persistence heuristic
(parameter_offload.py:360).
"""
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.mesh import MeshTopology


def _spec_tuple(spec: Optional[P], ndim: int) -> Tuple:
    entries = tuple(spec) if spec is not None else ()
    return entries + (None,) * (ndim - len(entries))


def _canon(entries) -> P:
    """PartitionSpec with trailing Nones stripped (P('x') != P('x', None))."""
    entries = list(entries)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def _axes_of(entry) -> Tuple:
    """Mesh axes of one PartitionSpec entry (None, a name, or several)."""
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)


def _used_axes(entries) -> set:
    return {a for e in entries for a in _axes_of(e)}


def add_zero_axes_to_spec(shape: Tuple[int, ...],
                          logical_spec: Optional[P],
                          zero_axes: Tuple[str, ...],
                          mesh: jax.sharding.Mesh,
                          min_size: int = 0,
                          first_dim: int = 0) -> P:
    """Extend ``logical_spec`` (TP sharding) with the ZeRO axes on a free dim.
    Dimensions before ``first_dim`` never take them (1 for a layer-stacked
    leaf: its layer axis).

    Falls back to the unmodified logical spec (replication over the DP group)
    when no dimension is cleanly divisible — the reference keeps such params
    unpartitioned too (persistence threshold / padding-free policy; we prefer
    replication over padding for correctness at small scale).
    """
    entries = list(_spec_tuple(logical_spec, len(shape)))
    used = _used_axes(entries)
    free_zero = tuple(a for a in zero_axes if a not in used)
    if not free_zero:
        return _canon(entries)

    def world(axes):
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        return size

    zero_world = world(free_zero)
    total = 1
    for s in shape:
        total *= s
    if zero_world <= 1 or total < max(min_size, 1):
        return _canon(entries)
    dims = [(i, dim, _axes_of(entries[i]))
            for i, dim in enumerate(shape)][first_dim:]
    # first a dim the logical spec does not shard (no axes, or axes of size
    # one: tensor parallelism off), then one it does (e.g. a TP-sharded dim
    # whose per-shard size the ZeRO world divides too)
    for actually_sharded in (False, True):
        for i, dim, cur in dims:
            cur_world = world(cur)
            if (cur_world > 1) == actually_sharded and dim \
                    and dim % (cur_world * zero_world) == 0:
                merged = cur + free_zero
                entries[i] = merged if len(merged) > 1 else merged[0]
                return _canon(entries)
    return _canon(_spec_tuple(logical_spec, len(shape)))


def gather_on_use(w, mesh, grad_spec: P, target_spec: P):
    """The ZeRO-3 gather of one layer's slice, stated where the layer is
    used (inside the scan body and the remat boundary): ``w`` arrives in its
    storage layout and leaves in ``target_spec`` (the logical layout, no
    ZeRO axes), so the partitioner must all-gather the weight slice — it may
    not move activations to the shards by cost, and it has nothing of
    whole-stack size to gather outside the loop.  The cotangent is pinned
    to ``grad_spec``, the layout of the slice's gradient: it leaves the
    backward loop as a reduce-scatter, not as an all-reduce of the
    replicated gradient."""
    target = NamedSharding(mesh, target_spec)
    grad = NamedSharding(mesh, grad_spec)

    @jax.custom_vjp
    def f(x):
        return jax.lax.with_sharding_constraint(x, target)

    def fwd(x):
        return f(x), None

    def bwd(_, g):
        return (jax.lax.with_sharding_constraint(g, grad),)

    f.defvjp(fwd, bwd)
    return f(w)


@dataclass
class ZeroShardingPolicy:
    """Computes the (param, grad, optimizer-state) shardings for a ZeRO stage."""
    stage: int
    topology: MeshTopology
    param_persistence_threshold: int = 0
    hpz_partition_size: int = 1
    mics_shard_size: int = -1
    #: top-level key of the params subtree whose leaves carry a leading
    #: layer axis (``model.blocks_key``); None = the model has none
    stacked_key: Optional[str] = None

    def __post_init__(self):
        if self.stage not in (0, 1, 2, 3):
            raise ValueError(f"invalid ZeRO stage {self.stage}")
        if self.mics_shard_size > 0:
            # MiCS (reference mics.py:55): every ZeRO axis collapses to the
            # sub-group axis; state replicates across groups so collectives
            # stay inside the (intra-host-sized) group
            self.zero_axes = self.topology.hpz_axes
        else:
            self.zero_axes = self.topology.zero_shard_axes
        # ZeRO++ hpZ (reference partition_parameters.py:1488 secondary
        # partition + groups.py:473): param STORAGE shards only over the
        # intra-host hpz axis, so the forward all-gather never crosses hosts;
        # grads/optimizer state keep the full zero sharding.
        self.param_axes = (self.topology.hpz_axes
                           if self.stage >= 3 and self.hpz_partition_size > 1
                           else self.zero_axes)
        self.mesh = self.topology.mesh

    # -- per-leaf specs -------------------------------------------------------
    def _sharded_spec(self, shape, logical_spec, axes=None,
                      stacked=False) -> P:
        return add_zero_axes_to_spec(shape, logical_spec,
                                     axes or self.zero_axes,
                                     self.mesh, self.param_persistence_threshold,
                                     first_dim=int(stacked))

    def param_spec(self, shape, logical_spec=None, stacked=False) -> P:
        """Storage sharding of master params between steps.  ``stacked``:
        the leaf's dim 0 is the layer axis (see the module docstring)."""
        if self.stage >= 3:
            return self._sharded_spec(shape, logical_spec,
                                      axes=self.param_axes, stacked=stacked)
        return logical_spec if logical_spec is not None else P()

    def grad_spec(self, shape, logical_spec=None, stacked=False) -> P:
        if self.stage >= 2:
            return self._sharded_spec(shape, logical_spec, stacked=stacked)
        return logical_spec if logical_spec is not None else P()

    def optimizer_spec(self, shape, logical_spec=None, stacked=False) -> P:
        if self.stage >= 1:
            return self._sharded_spec(shape, logical_spec, stacked=stacked)
        return logical_spec if logical_spec is not None else P()

    # -- pytree-level ---------------------------------------------------------
    def _tree_specs(self, params, logical_specs, fn):
        def leaf(path, p, s=None):
            stacked = (self.stacked_key is not None and bool(path)
                       and getattr(path[0], "key", None) == self.stacked_key)
            return fn(getattr(p, "shape", ()), s, stacked=stacked)
        # logical_specs must be a pytree matching params with PartitionSpec
        # leaves (use P() for replicated, not None — None is an empty pytree).
        rest = () if logical_specs is None else (logical_specs,)
        return jax.tree_util.tree_map_with_path(leaf, params, *rest)

    def param_specs(self, params, logical_specs=None):
        return self._tree_specs(params, logical_specs, self.param_spec)

    def grad_specs(self, params, logical_specs=None):
        return self._tree_specs(params, logical_specs, self.grad_spec)

    def optimizer_specs_for_params(self, params, logical_specs=None):
        return self._tree_specs(params, logical_specs, self.optimizer_spec)

    def shardings(self, specs):
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s),
            specs, is_leaf=lambda x: isinstance(x, P))

    def constrain_grads(self, grads, grad_specs):
        """Apply the stage-2 reduce-scatter constraint inside the train step."""
        return jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(
                g, NamedSharding(self.mesh, s)),
            grads, grad_specs, is_leaf=lambda x: isinstance(x, P))
