"""Model protocol for the engine.

The reference wraps an ``nn.Module`` (engine.py:1058); the TPU-native engine
instead consumes a pure (init, apply, loss) triple plus per-parameter logical
PartitionSpecs carrying the tensor-parallel layout.  Anything — flax, haiku, or
hand-rolled pytrees — can be adapted to this.
"""
import contextlib
import contextvars
import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.custom_derivatives import SymbolicZero

# ---------------------------------------------------------------- param stream
# ZeRO-Infinity parameter offload (reference: partitioned_param_swapper.py:36 +
# parameter_offload.py:201).  When enabled, layer-stacked block params are
# *stored* in pinned host memory (engine assigns memory_kind="pinned_host"
# shardings) and each layer's slice is transferred to device inside the
# layer scan — XLA overlaps the host→device DMA with the previous layer's
# compute, so HBM holds O(1 layer) of params instead of the whole model.
_PARAM_STREAM: contextvars.ContextVar = contextvars.ContextVar(
    "ds_param_stream", default=False)


@contextlib.contextmanager
def param_stream_scope(enabled: bool = True, mesh=None, layer_specs=None,
                       mode: str = "stream"):
    """Enable a per-layer param transform for models traced inside this
    scope (the engine wraps its compiled-step invocations with it).

    Modes:
    - ``stream`` — ZeRO-Infinity host→device streaming.  ``layer_specs`` is
      a flat list of per-leaf target PartitionSpecs for ONE layer's slice
      (stacked leading dim stripped; None = leaf skips the transfer),
      aligned with ``jax.tree.leaves(layer_tree)``.
    - ``qwz`` — ZeRO++ quantized weight gather.  ``layer_specs`` is a flat
      list of (storage_spec, target_spec) pairs (None = leaf skips): the
      leaf quantizes to int8, all-gathers in the target layout, and
      dequantizes (runtime/zero/zeropp.py).
    - ``qgz`` — ZeRO++ quantized-gradient shard_map tier: ``layer_specs``
      is a flat list of kwargs dicts for
      ``runtime/zero/zeropp.gather_with_quantized_grad`` (None = leaf
      skips).  Each layer slice all-gathers over the manual zero axes in
      the forward (int8 wire when qwZ is also on) and its cotangent
      reduce-scatters as int8 chunks in the backward.
    - ``gather`` — plain ZeRO-3: ``layer_specs`` is a flat list of
      (grad_spec, target_spec) pairs (None = leaf is not ZeRO-sharded); the
      leaf's slice is all-gathered to the target layout here, where the
      layer is used, and its cotangent leaves in the gradient's layout
      (runtime/zero/policy.gather_on_use)."""
    value = (mode, mesh, layer_specs) if enabled else False
    token = _PARAM_STREAM.set(value)
    try:
        yield
    finally:
        _PARAM_STREAM.reset(token)


def param_stream_active() -> bool:
    return bool(_PARAM_STREAM.get())


@jax.tree_util.register_pytree_node_class
class QuantizedTensor:
    """Weight-only int8 storage for serving (reference capability: inference
    quantization / MoQ, deepspeed/inference config ``quant`` +
    compression/).  Holds per-block symmetric int8 values + fp32 scales
    (ops/pallas/quantization.py layout); ``maybe_stream`` reconstructs the
    compute-dtype weight per layer inside the scan, so HBM holds 1
    byte/param for the stacked blocks."""

    def __init__(self, q, s, dtype: str = "bfloat16"):
        self.q, self.s, self.dtype = q, s, dtype

    def tree_flatten(self):
        return (self.q, self.s), self.dtype

    @classmethod
    def tree_unflatten(cls, dtype, children):
        return cls(children[0], children[1], dtype)


def qdot(x, w):
    """Projection matmul that consumes quantized weights IN PLACE:
    ``QuantizedTensor`` leaves route through the fused-dequant int8 GEMM
    kernel (``ops/pallas/qgemm.ds_qgemm`` — the weight stays int8 in HBM
    and dequantizes tile-wise in VMEM), plain arrays take the ordinary
    ``x @ w.astype(x.dtype)``.  Every model family's QKV / attention-out
    / MLP / head projection calls this, so the serving decode paths can
    skip the layer-granularity ``maybe_stream`` dequant entirely."""
    if isinstance(w, QuantizedTensor):
        from deepspeed_tpu.ops.pallas.qgemm import ds_qgemm
        return ds_qgemm(x, w.q, w.s, out_dtype=x.dtype)
    return x @ w.astype(x.dtype)


def _maybe_dequant(tree, keep_gemm_weights: bool = False,
                   keep_moe_weights: bool = False):
    """Reconstruct ``QuantizedTensor`` leaves in compute dtype.  With
    ``keep_gemm_weights`` the 2-D (already layer-sliced) weights that the
    qgemm path consumes directly stay quantized; with
    ``keep_moe_weights`` the 3-D stacked expert tensors that the grouped
    expert kernel (ops/pallas/grouped_gemm.py) consumes stay quantized
    too — only leaves no kernel can take as-is dequantize."""
    is_q = lambda x: isinstance(x, QuantizedTensor)
    if not any(map(is_q, jax.tree_util.tree_leaves(tree, is_leaf=is_q))):
        return tree
    from deepspeed_tpu.ops.pallas.quantization import block_dequantize_int8

    def dq(x):
        if is_q(x):
            if keep_gemm_weights and x.q.ndim == 2:
                return x
            if keep_moe_weights and x.q.ndim == 3:
                return x
            import jax.numpy as jnp
            return block_dequantize_int8(x.q, x.s).astype(
                jnp.dtype(x.dtype))
        return x

    return jax.tree_util.tree_map(dq, tree, is_leaf=is_q)


def maybe_stream(layer_tree, keep_quantized: bool = False,
                 keep_moe_quantized: bool = False):
    """Inside a layer-scan body: bring this layer's params to where the
    block computes — all-gather its ZeRO-3 shards, move a host-resident
    slice to device memory — and/or reconstruct int8-quantized weights
    (``QuantizedTensor`` leaves) in compute dtype.  No-op otherwise.
    Call *inside* the remat boundary so the backward pass re-gathers or
    re-streams the layer instead of pinning its full copy in HBM.

    ``keep_quantized`` (serving decode paths): leave the layer's 2-D
    quantized projection weights as ``QuantizedTensor`` — the model's
    ``qdot`` call sites feed them to the fused-dequant qgemm kernel, so
    no compute-dtype copy of the layer's weights is ever materialized.
    ``keep_moe_quantized`` extends the same contract to the layer's 3-D
    stacked expert weights, consumed by the grouped expert kernel."""
    layer_tree = _maybe_dequant(layer_tree,
                                keep_gemm_weights=keep_quantized,
                                keep_moe_weights=keep_moe_quantized)
    cfg = _PARAM_STREAM.get()
    if not cfg:
        return layer_tree
    import jax
    mode, mesh, layer_specs = cfg
    leaves, treedef = jax.tree_util.tree_flatten(layer_tree)
    if mode in ("qwz", "gather"):
        if mode == "qwz":
            from deepspeed_tpu.runtime.zero.zeropp import \
                quantized_weight_gather as gather
        else:
            from deepspeed_tpu.runtime.zero.policy import \
                gather_on_use as gather
        assert layer_specs is not None and len(layer_specs) == len(leaves)
        moved = [w if sp is None else gather(w, mesh, sp[0], sp[1])
                 for w, sp in zip(leaves, layer_specs)]
        return jax.tree_util.tree_unflatten(treedef, moved)
    if mode == "qgz":
        from deepspeed_tpu.runtime.zero.zeropp import \
            gather_with_quantized_grad
        assert layer_specs is not None and len(layer_specs) == len(leaves)
        moved = [w if kw is None else gather_with_quantized_grad(w, **kw)
                 for w, kw in zip(leaves, layer_specs)]
        return jax.tree_util.tree_unflatten(treedef, moved)
    if mesh is None or layer_specs is None:
        targets = [jax.memory.Space.Device] * len(leaves)
    else:
        from jax.sharding import NamedSharding
        assert len(layer_specs) == len(leaves), \
            f"param_stream specs/leaves mismatch: {len(layer_specs)} vs {len(leaves)}"
        # None spec = leaf already device-resident (persistent-small): no-op
        targets = [None if s is None
                   else NamedSharding(mesh, s, memory_kind="device")
                   for s in layer_specs]
    moved = [w if t is None else _stream_transfer(w, t)
             for w, t in zip(leaves, targets)]
    return jax.tree_util.tree_unflatten(treedef, moved)


def _stream_transfer(w, target):
    """host→device transfer whose VJP passes the cotangent through untouched
    (the raw transpose would be a device→host transfer annotation that XLA's
    SPMD partitioner mishandles on multi-device meshes; the jit-level
    out_shardings place the grads instead)."""
    import jax

    @jax.custom_vjp
    def f(x):
        return jax.device_put(x, target)

    def fwd(x):
        return jax.device_put(x, target), None

    def bwd(_, g):
        return (g,)

    f.defvjp(fwd, bwd)
    return f(w)


def scan_blocks(block_fn, x, blocks, rng, batch, num_layers: int,
                allow_ltd: bool = True):
    """Layer scan with the engine's data-efficiency hooks applied.

    - **random-LTD**: trace-time keep-token count from the engine's ltd
      scope (runtime/data_pipeline/random_ltd.py).  Models whose block
      closes over per-position state (e.g. an encoder padding mask) pass
      ``allow_ltd=False`` — the gathered token subset would misalign with
      that state.
    - **progressive layer drop** (reference engine.py:1755 PLD theta kwarg):
      when the engine injects ``batch["pld_theta"]`` (a *traced* scalar, so
      the per-step theta schedule never recompiles), layer ``l`` is skipped
      with probability ``(l+1)/L * (1 - theta)`` — the PLD paper's
      depth-scaled schedule; kept outputs are not rescaled, matching the
      reference's convention (LayerNorm absorbs the scale).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from deepspeed_tpu.runtime.data_pipeline.random_ltd import (
        get_ltd_keep, random_ltd_block)

    ltd_keep = get_ltd_keep()
    S = x.shape[1]
    use_ltd = (allow_ltd and bool(ltd_keep) and rng is not None
               and ltd_keep < S)
    if not allow_ltd and bool(ltd_keep) and ltd_keep < S:
        from deepspeed_tpu.utils.logging import warning_once
        warning_once("random-LTD: skipped — this model's blocks close over "
                     "per-position state (padding mask) that a token "
                     "subset would misalign with")
    theta = batch.get("pld_theta") if isinstance(batch, dict) else None
    use_pld = theta is not None and rng is not None

    # activation quantization (reference compression activation_quantization
    # via LinearLayer_Compress; here the block output quantizes through an
    # STE when the engine's compression scope is active)
    from deepspeed_tpu.compression.compress import (
        get_activation_quant_bits, maybe_quantize_activation)
    use_aq = bool(get_activation_quant_bits())

    if not (use_ltd or use_pld):
        def plain(carry, layer):
            out = block_fn(carry, layer)
            return (maybe_quantize_activation(out) if use_aq else out), None
        out, _ = lax.scan(plain, x, blocks)
        return out

    def body(carry, layer):
        h, idx = carry
        layer_rng = jax.random.fold_in(rng, idx)
        if use_ltd:
            out = random_ltd_block(lambda t: block_fn(t, layer), layer_rng,
                                   h, ltd_keep)
        else:
            out = block_fn(h, layer)
        if use_pld:
            keep_p = 1.0 - (idx.astype(jnp.float32) + 1.0) / num_layers * (
                1.0 - theta)
            gate = jax.random.bernoulli(jax.random.fold_in(layer_rng, 1),
                                        keep_p)
            out = jnp.where(gate, out, h)
        if use_aq:
            out = maybe_quantize_activation(out)
        return (out, idx + 1), None

    (out, _), _ = lax.scan(body, (x, jnp.int32(0)), blocks)
    return out


def scan_layer_kinds(x, stacks: dict, pattern: tuple, block_fns: dict):
    """The layer loop of a model whose layers are of several kinds in a
    repeating pattern (three linear-attention layers to one full one,
    say).  ``stacks`` maps a kind to its layers' parameters stacked
    ``[periods, layers of that kind in a period, ...]``; ``pattern`` names
    the kind of each layer of one period, in order; ``block_fns[kind](x,
    layer) -> (x, aux)`` is that kind's block, with its own remat and
    ``maybe_stream`` inside; ``aux`` is a pytree of scalars, the same
    from every kind.  One ``lax.scan`` runs over the periods,
    the layers of a period in ``pattern``'s order inside its body, each
    reading the next layer of its kind's stack.  Returns ``(x, aux summed
    over all layers)``."""
    import jax.numpy as jnp
    from jax import lax

    def period(x, layers):
        taken = dict.fromkeys(layers, 0)
        aux = None
        for kind in pattern:
            i = taken[kind]
            taken[kind] = i + 1
            x, a = block_fns[kind](
                x, jax.tree_util.tree_map(lambda w: w[i], layers[kind]))
            aux = a if aux is None else jax.tree_util.tree_map(
                jnp.add, aux, a)
        return x, aux

    x, aux = lax.scan(period, x, stacks)
    return x, jax.tree_util.tree_map(lambda a: jnp.sum(a, axis=0), aux)


# -------------------------------------------------------------- the scaffold
# What a family whose layers are of several kinds (models/qwen3_next.py,
# nemotron_h.py, joyai.py, laguna.py, mellum.py) does NOT write itself: its file is its
# config, parameters, mixers, blocks and layout, and one call of
# ``held_share_model``.

def remat_policy(name: str):
    """Remat policies for per-layer activation checkpointing (the reference's
    activation_checkpointing tiers become jax.checkpoint policies)."""
    if name in (None, "nothing", "nothing_saveable"):
        return jax.checkpoint_policies.nothing_saveable
    if name in ("save_attn",):
        return jax.checkpoint_policies.save_only_these_names("attn_out")
    if name in ("dots", "dots_saveable"):
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if name in ("offload_attn",):
        # host-offload tier: attention outputs go to pinned host DRAM instead
        # of HBM (reference cpu_checkpointing)
        return jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=["attn_out"],
            offload_src="device", offload_dst="pinned_host")
    raise ValueError(f"unknown remat policy {name!r}")


def layer_block(block, config, **static):
    """``fn(x, layer)`` of one kind of layer, as a layer loop calls it:
    ``block(x, layer, config, **static)`` with the layer's parameters
    brought to where it computes (``maybe_stream``, inside the remat
    boundary) and, where ``config.remat``, rematerialised by
    ``config.remat_policy``."""
    def fn(x, layer):
        return block(x, maybe_stream(layer), config, **static)
    if config.remat:
        fn = jax.checkpoint(fn, policy=remat_policy(config.remat_policy))
    return fn


def refuse_param_stream(family: str, layout: str):
    """Where a per-layer parameter transform is on (ZeRO-3's gather,
    parameter offload's stream) and the family's layers are ``layout``
    and not one stacked tree: the refusal, in the family's words."""
    if param_stream_active():
        raise NotImplementedError(
            f"{family}: ZeRO-3 and parameter offload gather or stream one "
            f"layer of a single stacked tree at a time; this model's layers "
            f"are {layout}, and gathering at that grain is not built — use "
            f"ZeRO stage 0-2")


def embed_tokens(wte, tokens, dtype):
    """The embedding lookup, under the ``ds.embed`` scope."""
    from deepspeed_tpu.telemetry.tracing import SCOPE_EMBED
    with jax.named_scope(SCOPE_EMBED):
        return wte.astype(dtype)[tokens]


def segment_ids_of(batch):
    """The documents of a packed batch ([B, S] int), or None."""
    return batch.get("segment_ids") if isinstance(batch, dict) else None


def expert_branch(x, moe_params, moe_config, norm, train, rng=None):
    """The expert half of a block without its residual: ``MoE(norm(x))``
    under the ``mlp`` scope -> (the branch, (router loss float32, the
    layer's int32 sums: routed rows over ``held_rows_bound``, then its load
    — ``moe/layer.py layer_sums``)), the pair being what a layer adds to a
    loop's sums."""
    import jax.numpy as jnp
    from deepspeed_tpu.moe.layer import layer_sums, moe_layer
    from deepspeed_tpu.telemetry.tracing import SCOPE_MLP
    with jax.named_scope(SCOPE_MLP):
        out, aux, stats = moe_layer(moe_params, norm(x), moe_config,
                                    train=train, rng=rng, return_stats=True)
        return out, (aux.astype(jnp.float32), layer_sums(stats))


def expert_half(x, moe_params, moe_config, norm, train, rng=None):
    """The expert half of a block: ``x + MoE(norm(x))`` -> (x, what
    :func:`expert_branch` returns beside the branch)."""
    from deepspeed_tpu.telemetry.tracing import SCOPE_MLP
    out, sums = expert_branch(x, moe_params, moe_config, norm, train, rng)
    with jax.named_scope(SCOPE_MLP):
        return x + out, sums


def no_experts():
    """What a layer without experts adds to the router loss and to the
    expert layers' sums (:func:`expert_branch`)."""
    import jax.numpy as jnp
    from deepspeed_tpu.moe.layer import STEP_SUMS
    return jnp.float32(0.0), jnp.zeros(len(STEP_SUMS), jnp.int32)


def param_count(init_fn) -> int:
    """Parameters ``init_fn(rng)`` would make, from shapes alone."""
    import math
    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    return int(sum(math.prod(s.shape) for s in jax.tree.leaves(shapes)))


def resolve_size(sizes: dict, size: str, family: str) -> dict:
    """Look up a size preset, refusing typos: an unknown ``size`` silently
    falling through to the dataclass defaults once shipped a 50M-param
    default NeoX into a serving benchmark labelled 160M (round-4 PERF).
    ``size="custom"`` opts into defaults+overrides explicitly."""
    if size in sizes:
        return dict(sizes[size])
    if size == "custom":
        return {}
    raise ValueError(
        f"{family}: unknown size {size!r}; valid sizes: "
        f"{sorted(sizes)} or 'custom' (config defaults + overrides)")


@dataclass
class Model:
    config: Any = None
    #: rng -> params pytree (fp32)
    init_fn: Callable = None
    #: optional host-side initializer (seed=0) -> numpy params pytree with
    #: init_fn's distributions; the offload tier prefers it (fast host init,
    #: no HBM involvement)
    numpy_init_fn: Optional[Callable] = None
    #: optional sliced device init for the offload tier: layer_init_fn(rng,
    #: i) -> ONE layer's block params (no leading L); nonblock_init_fn(rng)
    #: -> everything else.  The engine generates layers on device (fast TPU
    #: RNG) and DMAs each slice to pinned host — O(1 layer) HBM, no
    #: single-core host RNG/cast bottleneck.
    layer_init_fn: Optional[Callable] = None
    nonblock_init_fn: Optional[Callable] = None
    #: (params, batch, rng) -> logits
    apply_fn: Callable = None
    #: (params, batch, rng) -> scalar loss; defaults to causal-LM cross-entropy
    #: over ``apply_fn`` logits and ``batch["input_ids"]`` shifted by one.
    loss_fn: Optional[Callable] = None
    #: optional (params, batch, rng) -> (``loss_fn``'s scalar, {name: int32
    #: scalar}): the loss beside counts of what the model left out of it (the
    #: routed rows past an expert layer's static bound).  The fused train
    #: step returns them summed over its micro-batches
    #: (``metrics["counts"]``); the engine adds them up under their names
    #: (``engine.step_counts()``, the registry's ``train/step_counts``) and
    #: warns of one that is not zero; ``meta["step_counts"]`` = {name: what
    #: it counts} says which names are such counts and words the warning;
    #: any other name is the step's load (``engine.step_load()``).
    loss_with_counts_fn: Optional[Callable] = None
    #: pytree of jax.sharding.PartitionSpec (or None) matching params — the
    #: tensor-parallel ("model" axis) layout. ZeRO axes are layered on top.
    logical_specs: Any = None
    #: approximate FLOPs per token for MFU accounting (6*N for dense LMs)
    flops_per_token: Optional[float] = None
    #: extra metadata (e.g. number of params)
    meta: dict = field(default_factory=dict)
    #: optional pipeline decomposition (see runtime/pipe/pipeline.py):
    #: embed_fn(params, batch) -> x; block_fn(layer_params, x) -> x;
    #: head_fn(params, x) -> logits; blocks_key names the stacked subtree.
    embed_fn: Optional[Callable] = None
    block_fn: Optional[Callable] = None
    head_fn: Optional[Callable] = None
    blocks_key: str = "blocks"
    #: optional pytree of bool matching params: False leaves are FROZEN —
    #: the engine excludes them from the optimizer (no updates, no moment
    #: memory; reference capability: requires_grad=False params /
    #: SimpleFrozenModel coverage).  LoRA sets base=False, adapters=True.
    trainable_mask: Any = None
    #: optional params -> params transform that materialises merged
    #: inference weights (LoRA fuse-for-generate; reference
    #: hybrid_engine.py:138-158 _fuse_lora).  The hybrid/inference view
    #: applies it; training always runs unfused.
    fuse_fn: Optional[Callable] = None
    #: KV-cache serving path (engines use these when present):
    #: init_cache_fn(batch_size, max_len, dtype) -> cache pytree;
    #: prefill_fn(params, batch, cache) -> (logits [B,S,V], cache);
    #: decode_fn(params, tokens [B], cache, lengths [B]) -> (logits [B,V], cache)
    init_cache_fn: Optional[Callable] = None
    prefill_fn: Optional[Callable] = None
    decode_fn: Optional[Callable] = None
    #: verify_fn(params, tokens [B,W], cache, lengths [B]) ->
    #: (logits [B,W,V], cache): speculative-decoding verification —
    #: score a W-token window at positions lengths..lengths+W-1 with ONE
    #: weight pass per layer (serving/spec).  Optional; the spec
    #: verifier falls back to a scan of decode_fn when absent.
    verify_fn: Optional[Callable] = None

    def __post_init__(self):
        if self.loss_fn is None and self.apply_fn is not None:
            self.loss_fn = _default_lm_loss(self.apply_fn)

    def init(self, rng):
        return self.init_fn(rng)

    def apply(self, params, batch, rng=None):
        return self.apply_fn(params, batch, rng)

    def loss(self, params, batch, rng=None):
        return self.loss_fn(params, batch, rng)


def held_share_model(family: str, size: str, config, *, init_params,
                     logical_specs, head_with_aux, expert_layers: int,
                     expert_matrices: int, serving_needs: str,
                     loss_with_counts=None, lookup_params: int = 0,
                     reused_params: int = 0, meta=None) -> Model:
    """The training-only :class:`Model` of a family whose expert layers may
    hold a share of their experts (``config.moe``: ``experts_held`` of
    ``num_experts``).  The family hands over ``init_params(config, rng)``,
    ``logical_specs(config)``, ``head_with_aux(params, batch, config,
    train=, rng=) -> (its :class:`Head`, router loss, the expert layers'
    sums)`` — whose logits are ``apply_fn``'s and whose
    :func:`head_token_loss` the loss's — and, where its loss is more than
    the one head's cross-entropy and the router loss,
    ``loss_with_counts(params, batch, config, rng) -> (loss, {name:
    count})``.  Counted here, for ``flops_per_token = 6 * active``:
    of ``expert_layers`` layers' experts (``expert_matrices`` matrices
    each) a token's weights pass through ``top_k`` of ``num_experts`` of
    those held; ``lookup_params`` are read and not multiplied (an
    embedding), ``reused_params`` multiplied a second time (a head behind
    a second module; a stack that a token passes several times counts its
    uses itself: models/ouro.py ``applied_params``, the same 6 a weight a
    use).  The four serving entry points raise, naming
    ``serving_needs``; ``meta`` is the family's own beside ``name``,
    ``n_params``, ``active_params`` and ``step_counts`` (which of the sums
    the loss left out: the rest is ``engine.step_load()``).  The counts
    always leave the step: rows are bounded where a share is held and
    where the layers exchange rows over an ``expert`` mesh axis, which the
    mesh decides after the model is built; where nothing is bounded the
    count is a zero."""
    from functools import partial
    from deepspeed_tpu.moe.layer import ROWS_OVER_BOUND, named_sums
    moe = config.moe
    n_params = param_count(partial(init_params, config))
    expert = expert_matrices * moe.d_model * moe.d_ff
    active = n_params - lookup_params + reused_params \
        - expert_layers * expert * (
            moe.held - moe.top_k * moe.held / moe.num_experts)

    if loss_with_counts is None:
        def loss_with_counts(params, batch, config, rng=None):
            head, aux, over = head_with_aux(params, batch, config,
                                            train=True, rng=rng)
            # inside a document only, where the batch is packed; aux = the
            # weighted load-balancing loss summed over layers
            return head.token_loss(batch) + aux, named_sums(over)

    def with_counts(params, batch, rng=None):
        return loss_with_counts(params, batch, config, rng)

    def logits(params, batch, rng=None):
        return head_with_aux(params, batch, config, train=False,
                             rng=rng)[0].logits()

    def no_serving(what):
        def refuse(*_, **__):
            raise NotImplementedError(
                f"{family}: {what} is not built — {serving_needs} (ROADMAP)")
        return refuse

    return Model(
        config=config,
        init_fn=partial(init_params, config),
        apply_fn=logits,
        loss_fn=lambda p, b, rng=None: with_counts(p, b, rng)[0],
        # the rows a step's expert layers left out leave the step beside
        # its loss (no host callback: one inside the layer loop does not
        # compile for a TPU on this jaxlib, one outside it keeps the step
        # out of jax's compile cache); the engine counts and warns
        loss_with_counts_fn=with_counts,
        logical_specs=logical_specs(config),
        flops_per_token=6.0 * active,
        meta={"name": f"{family}-{size}", "n_params": n_params,
              "active_params": active,
              "step_counts": {ROWS_OVER_BOUND: (
                  "routed rows past held_rows_bound, left out of the expert "
                  "layers: the router sent the experts held here more than "
                  "held_rows_factor times their even share")},
              **(meta or {})},
        init_cache_fn=no_serving("init_cache"),
        prefill_fn=no_serving("prefill"),
        decode_fn=no_serving("decode"),
        verify_fn=no_serving("verify"),
    )


def token_loss(logits, batch):
    """Mean next-token cross-entropy of ``logits`` [B, S, V] over the
    positions that count: position t is scored against token t+1, not
    where ``attention_mask`` is 0, and not across a document boundary of
    a packed sequence (``segment_ids``).  For callers that have logits
    (evaluation, serving, the references, tests); a training loss hands its
    hidden state and its head to :func:`head_token_loss`, which never
    holds them whole."""
    import jax.numpy as jnp
    import optax
    tokens = batch["input_ids"]
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    mask = batch.get("attention_mask")
    losses = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), targets)
    m = None
    if mask is not None:
        m = mask[:, 1:].astype(jnp.float32)
    seg = batch.get("segment_ids")
    if seg is not None:
        # packed sequences: the last token of one segment must not be
        # scored against the first token of the next
        same = (seg[:, 1:] == seg[:, :-1]).astype(jnp.float32)
        m = same if m is None else m * same
    if m is not None:
        return (losses * m).sum() / jnp.maximum(m.sum(), 1.0)
    return losses.mean()


# ------------------------------------------------- the head, never whole
# Every family's training loss: the head's product, the cross-entropy and
# their two gradients a block of tokens at a time.  Whole, the logits of a
# micro-batch and their gradient are the largest arrays of a step (16,384
# tokens x 25,008 ids: 1.5 GiB in bf16, as much again upcast).

def head_chunk_tokens(tokens: int, vocab: int) -> int:
    """Tokens of one chip whose float32 logits exist at a time: the
    largest power of two whose logits XLA keeps on the chip
    (``ops/pallas/vmem.py xla_keeps``: the chip's, 100 MiB on a v5e) where
    that is 1,024 tokens or more; where it is not (more than 25,600 ids on
    a v5e), four times as many, in HBM, and never under 1,024, and no
    more than there are.  Why, under 25,600 ids: on the chip the softmax's
    passes over the chunk cost no memory traffic and the loop holds nothing
    but its float32 ``dw`` carry — at the ten vocabularies measured there
    the fastest chunk is that one (Phi-4's head alone: 37.9 ms at 1,024,
    46.2 at 2,048, 44.8 at 4,096, 41.4 with whole logits).  **Above it the
    clause is two measured points and no more**: 50,257 / 50,304 ids
    (GPT-2, OLMoE) read fastest at 2,048 (36.7 ms against 43.9 at 1,024
    and 40.4 whole), where the carry ``[d_model, vocab]``, read and
    written once a chunk, asks for wider chunks; 98,304 ids (Mellum2) fit
    at 1,024 alone (0.84 GiB of ``temp``; 2.3 at 2,048).  A vocabulary
    between or beyond them gets what joins the two, untimed.  The width
    does not enter: the carry's bytes and the products' operations both
    grow with it (scripts/head_loss_table.py on a v5e; PERF.md section 6,
    PR 69)."""
    from deepspeed_tpu.ops.pallas.vmem import xla_keeps
    on_chip = 2 ** int(math.log2(xla_keeps() / (4 * vocab)))
    return min(tokens,
               on_chip if on_chip >= 1024 else max(1024, 4 * on_chip))


def next_token_targets(batch, ahead: int = 1):
    """-> (targets [B, S]: token t + ``ahead`` at position t; scored [B, S]
    bool: where that token is inside the sequence, no position from t + 1
    to it has ``attention_mask`` 0, and all of them lie in t's document
    (``segment_ids``)) — :func:`token_loss`'s positions at ``ahead`` 1."""
    ids = batch["input_ids"]
    scored = (jnp.arange(ids.shape[1]) < ids.shape[1] - ahead)[None, :] \
        & jnp.ones(ids.shape, bool)
    steps = range(1, ahead + 1)
    if batch.get("attention_mask") is not None:
        for k in steps:
            scored &= jnp.roll(batch["attention_mask"], -k, axis=1) != 0
    seg = segment_ids_of(batch)
    if seg is not None:
        for k in steps:
            scored &= seg == jnp.roll(seg, -k, axis=1)
    return jnp.roll(ids, -ahead, axis=1), scored


def _head_dot(a, b, contract):
    """``a`` and ``b`` contracted on one axis each, float32 out."""
    return lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                           preferred_element_type=jnp.float32)


def _chunk_nll(h, w, targets, scored, tied, name, per_token=False):
    """One chip's tokens ``h`` [t, D] through the head ``w`` ([D, V], or
    [V, D] where ``tied``: the embedding table contracted on its own
    axis, no transposed copy), a chunk at a time: -> (the positions'
    negative log likelihoods times ``scored`` [t] float32 — ones and
    zeros, or any weights —, summed, float32 []; its gradient in ``h`` [t,
    D]; and in ``w``, float32 in ``w``'s layout; with ``per_token`` also
    every position's negative log likelihood [t] float32, which is the
    sum's gradient in ``scored``).  Only one chunk's logits [chunk, V]
    (float32) exist at a time, and nothing is computed twice: the backward
    pass scales the gradients found here.  The chunk is
    :func:`head_chunk_tokens`'s; tokens it does not divide are padded
    with unscored ones, under one chunk's worth."""
    from deepspeed_tpu.telemetry.tracing import count_in_step
    t, D = h.shape
    V = w.shape[0] if tied else w.shape[1]
    chunks = -(-t // head_chunk_tokens(t, V))
    chunk = -(-t // chunks)
    count_in_step(head_chunks={name: {
        "name": name, "tokens": t, "d_model": D, "vocab": V, "chunk": chunk,
        "chunks": chunks, "whole_logits_bytes": 4 * t * V,
        "chunk_logits_bytes": 4 * chunk * V, "tied": tied}})
    if chunks * chunk > t:
        pad = lambda a: jnp.pad(
            a, ((0, chunks * chunk - t),) + ((0, 0),) * (a.ndim - 1))
        h, targets, scored = pad(h), pad(targets), pad(scored)

    def some_tokens(dw, args):
        hc, target, keep = args
        logits = _head_dot(hc, w, (1, 1)) if tied else jnp.dot(
            hc, w, preferred_element_type=jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        hit = jnp.arange(V, dtype=jnp.int32)[None, :] == target[:, None]
        nll = lse - jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        dlogits = ((jnp.exp(logits - lse[:, None]) - hit)
                   * keep[:, None]).astype(h.dtype)
        dw = dw + (_head_dot(dlogits, hc, (0, 0)) if tied else jnp.dot(
            hc.T, dlogits, preferred_element_type=jnp.float32))
        return dw, (nll if per_token else jnp.sum(nll * keep),
                    jnp.dot(dlogits, w if tied else w.T))

    dw, (nll, dh) = lax.scan(
        some_tokens, jnp.zeros(w.shape, jnp.float32),
        (h.reshape(-1, chunk, D), targets.reshape(-1, chunk),
         scored.reshape(-1, chunk)))
    if per_token:
        nll = nll.reshape(-1)
        return jnp.sum(nll * scored), dh.reshape(-1, D)[:t], dw, nll[:t]
    return jnp.sum(nll), dh.reshape(-1, D)[:t], dw


def _head_parts(h, w, targets, scored, tied, name, per_token=False):
    """:func:`_chunk_nll` on every chip's own tokens (a manual region over
    the data axes of the mesh: the head is gathered once, and its gradient
    summed over the chips ONCE, outside, not chunk by chunk; every other
    axis stays the partitioner's) -> (sums [chips], dh [B, S, D], dw
    [chips, ...] float32: each chip's share; with ``per_token`` also the
    positions' negative log likelihoods [B, S] float32)."""
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.comm.mesh import get_topology
    from deepspeed_tpu.utils.jax_compat import get_abstract_mesh, shard_map
    B, S, D = h.shape

    def on_chip(h, w, targets, scored):
        total, dh, dw, *nll = _chunk_nll(
            h.reshape(-1, D), w, targets.reshape(-1), scored.reshape(-1),
            tied, name, per_token)
        return (total[None], dh.reshape(h.shape), dw[None],
                *(n.reshape(targets.shape) for n in nll))

    topo = get_topology()
    # axes an enclosing manual region already maps (the quantized gradient
    # exchange is manual over the data axes): the tokens are local there
    context = get_abstract_mesh()
    outer = frozenset(context.manual_axes)
    axes = tuple(a for a in topo.data_parallel_axes if a not in outer)
    chips = topo.axis_size(axes)
    if chips == 1 or B % chips:
        return on_chip(h, w, targets, scored)
    rows = P(axes)
    return shard_map(on_chip, mesh=context if outer else topo.mesh,
                     in_specs=(rows, P(), rows, rows),
                     out_specs=(rows,) * (4 if per_token else 3),
                     axis_names=frozenset(axes), check_vma=False)(
                         h, w, targets, scored)


def _sum_of_chips(dw):
    """The chips' float32 shares ``dw`` [chips, rows, columns] summed, in
    float32: one all-reduce.  Rows that are no multiple of eight a chip
    are padded to one for the sum: GPT-2's 50,257 over a v5e 2x2 stop the
    TPU compiler in float32 (``Check failed: s_count.has_value()``, its
    reduce-scatter emitter; 50,272 compile: tests/test_chip_compile.py)."""
    chips, rows = dw.shape[:2]
    whole = -(-rows // (8 * chips)) * 8 * chips
    if chips == 1 or whole == rows:
        return jnp.sum(dw, axis=0)
    return jnp.sum(jnp.pad(dw, ((0, 0), (0, whole - rows), (0, 0))),
                   axis=0)[:rows]


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def head_nll_sum(h, w, targets, scored, tied=False, name="main"):
    """The positions' negative log likelihoods of ``targets`` [B, S] under
    ``softmax(h w)``, each times its ``scored``, summed: float32 [].  ``h``
    [B, S, D] is the normed hidden state, ``w`` the head in ``h``'s dtype
    ([D, V]; the embedding table [V, D] where ``tied``), ``scored`` [B, S]
    float32: ones and zeros (which positions count), or any weights (a
    looped model's probabilities of leaving after this pass:
    models/ouro.py).  The logits are float32 and never whole
    (:func:`_chunk_nll`): at 8,192 tokens a chip and 98,304 ids one pass
    would hold 3.2 GB of them, and as much again for their gradient.
    ``name`` is the call's in the step's account
    (``tracing.head_chunks``).

    Differentiable in ``h``, ``w`` and ``scored``.  The gradient in
    ``scored`` is the positions' negative log likelihoods, [B, S] float32,
    which the forward rule keeps **only where the caller differentiates
    through** ``scored`` (the rule is told: ``symbolic_zeros``).  A caller
    whose ``scored`` does not depend on what it differentiates — every
    family but the looped one: ones and zeros made from the batch — gets
    the forward and backward rules it had before ``scored`` took weights,
    to the letter of its lowered step (tests/test_weighted_head.py)."""
    return jnp.sum(_head_parts(h, w, targets, scored, tied, name)[0])


def _head_nll_fwd(h, w, targets, scored, tied, name):
    # the arguments as custom_vjp hands them under symbolic_zeros: the
    # value, and whether the caller differentiates through it
    total, *kept = _head_parts(h.value, w.value, targets.value,
                               scored.value, tied, name,
                               per_token=scored.perturbed)
    return jnp.sum(total), tuple(kept)


def _head_nll_bwd(tied, name, res, g):
    dh, dw, *nll = res
    if isinstance(g, SymbolicZero):
        g = jnp.zeros(g.shape, g.dtype)
    # the chips' shares are summed and scaled in float32 and rounded once
    grads = ((g * dh).astype(dh.dtype),
             (g * _sum_of_chips(dw)).astype(dh.dtype))
    if tied:
        # the table's gradient is finished only when the embedding's rows
        # arrive, at the end of the backward pass: rounded here, with
        # ``dh``, which the backward pass wants first, the head's share
        # waits for them in the head's dtype — left free, the compiler
        # kept the loop's float32 carry until then, under every layer's
        # backward (the four-chip ZeRO-3 cell's peak + 0.14 GiB; so - 0.24)
        grads = lax.optimization_barrier(grads)
    return (*grads, None, g * nll[0] if nll else None)


head_nll_sum.defvjp(_head_nll_fwd, _head_nll_bwd, symbolic_zeros=True)


def head_token_loss(h, w, batch, *, tied: bool = False, targets=None,
                    name: str = "main"):
    """:func:`token_loss` of ``h w`` without the logits: the mean over the
    scored positions of :func:`head_nll_sum` (``targets``: the (targets,
    scored) of another scoring than :func:`next_token_targets`'s of
    ``batch`` — a prediction module's).  ``h`` [B, S, D] is the normed
    hidden state with whatever the family scales its logits by applied;
    ``w`` the head as its owner stores it, cast here: [D, V], or ``wte``
    [V, D] where ``tied``.  bf16 operands into the three products, float32
    accumulation, logits and loss.  ``scored`` here is ones and zeros made
    from the batch, so this caller is :func:`head_nll_sum`'s without a
    gradient in the weights: the program it was before they could be any
    (a loss that weights its positions calls :func:`head_nll_sum`
    itself)."""
    targets, scored = next_token_targets(batch) if targets is None \
        else targets
    scored = scored.astype(jnp.float32)
    total = head_nll_sum(h, w.astype(h.dtype), targets, scored, tied, name)
    return total / jnp.maximum(jnp.sum(scored), 1.0)


class Head(NamedTuple):
    """What a family's layers hand its head: ``h`` [B, S, D], the normed
    hidden state (a family's scale on it applied), and ``w``, the head's
    weight as stored — [D, V], or the embedding table [V, D] where
    ``tied``."""
    h: Any
    w: Any
    tied: bool = False

    def logits(self):
        """[B, S, V] in ``h``'s dtype, whole: for callers that want
        them (evaluation, the references, tests)."""
        from deepspeed_tpu.telemetry.tracing import SCOPE_HEAD_LOSS
        with jax.named_scope(SCOPE_HEAD_LOSS):
            w = self.w.astype(self.h.dtype)
            return self.h @ (w.T if self.tied else w)

    def token_loss(self, batch, **how):
        """:func:`head_token_loss` of this head, under ``ds.head_loss``."""
        from deepspeed_tpu.telemetry.tracing import SCOPE_HEAD_LOSS
        with jax.named_scope(SCOPE_HEAD_LOSS):
            return head_token_loss(self.h, self.w, batch, tied=self.tied,
                                   **how)


def _default_lm_loss(apply_fn):
    """The causal-LM loss of a model that names none: :func:`token_loss`
    of ``apply_fn``'s logits (a family that trains in a cell hands its
    :class:`Head` over in a ``loss_fn`` of its own: no whole logits)."""
    from deepspeed_tpu.telemetry.tracing import SCOPE_HEAD_LOSS

    def loss_fn(params, batch, rng=None):
        logits = apply_fn(params, batch, rng)
        with jax.named_scope(SCOPE_HEAD_LOSS):
            return token_loss(logits, batch)

    return loss_fn
