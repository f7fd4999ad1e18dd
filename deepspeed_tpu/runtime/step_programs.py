"""The traced step programs, each part written once.

``runtime/engine.py`` decides placement, donation and caching and runs on
the host; everything it hands to ``jax.jit`` is built here, from one
read-only ``StepContext`` the engine fills at the end of ``__init__``.  A
train step is: the loss scale of the state → ``micro_grads`` (loss and
gradient of one micro-batch, ``ds.fwd_bwd``) → ``accumulate`` (cast, ZeRO
layout, add, ``ds.accumulate``), scanned over the micro-batches →
``apply_grads`` (unscale, overflow, update, ``ds.optimizer``).  ``PROGRAMS``
maps each name ``engine._get_compiled`` knows to its builder.
"""
import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.runtime.fp16.loss_scaler import has_overflow, update_scale
from deepspeed_tpu.telemetry.numerics import (
    group_stats, group_stats_of, inject_nonfinite)
from deepspeed_tpu.telemetry.tracing import (
    SCOPE_ACCUMULATE, SCOPE_FWD_BWD, SCOPE_OPTIMIZER, TRAIN_STEP_PROGRAM,
    count_in_step, rows_in_step, step_account)
from deepspeed_tpu.utils.logging import logger


@dataclasses.dataclass(frozen=True)
class StepContext:
    """What the traced code reads of the engine; nothing here is an option
    of its own — every field is computed by ``DeepSpeedEngine.__init__``."""
    model: Any
    optimizer: Any
    zero_policy: Any
    grad_specs: Any
    grad_dtype: Any
    compute_dtype: Any
    fp16: bool
    scaler_config: Any
    gas: int
    compression_plans: Any          # None: no compression training
    use_streamed: bool              # blocks stay fp32 in pinned host
    num_groups: Optional[list]      # numerics leaf groups (None: off)
    num_leaf_group: Optional[list]
    pipe_cfg: dict                  # the config's "pipeline" block


def tree_cast(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree)


def global_norm(tree):
    leaves = [jnp.sum(jnp.square(l.astype(jnp.float32)))
              for l in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves))


# ---------------------------------------------------------------- the parts
def loss_scale(ctx, state):
    return state["scaler"].cur_scale if ctx.fp16 else jnp.float32(1.0)


def compression_step(ctx, state):
    """The step the compression schedule's traced gates read."""
    return state["step"] if ctx.compression_plans is not None else None


def compress(ctx, params, step):
    """Apply the compression-training plans to the compute params with
    traced schedule gates (reference engine.py:2044 scheduler-per-step)."""
    from deepspeed_tpu.compression import compress_params_traced
    return compress_params_traced(params, step, ctx.compression_plans)


def flash_tile_sums(batch):
    """What a packed micro-batch's documents did to the flash kernels'
    tile loops (``ds_flash_attention.step_tile_sums`` over the calls the
    loss above has just traced into the step's account): two more of the
    sums that leave the step beside its loss; ``{}`` for a batch with no
    ``segment_ids`` or a step with no packed flash call."""
    segment_ids = batch.get("segment_ids") if isinstance(batch, dict) \
        else None
    if segment_ids is None:
        return {}
    from deepspeed_tpu.ops.pallas.ds_flash_attention import step_tile_sums
    return step_tile_sums(segment_ids, rows_in_step("flash_calls"))


def scaled_loss(ctx, params, batch, rng, scale, compress_step=None,
                counted=False):
    """The model's loss times ``scale``; ``counted``: beside the model's
    counts (``Model.loss_with_counts_fn``), as ``value_and_grad``'s aux."""
    if ctx.use_streamed and isinstance(params, dict):
        # blocks stay fp32 in pinned host; the models cast each weight at
        # point of use (after the per-layer stream), so the AD transpose
        # stays per-slice — a whole-tree cast here would materialise full
        # stacked fp32 converts on device in the backward pass
        bk = getattr(ctx.model, "blocks_key", "blocks")
        cparams = {k: (v if k == bk else tree_cast(v, ctx.compute_dtype))
                   for k, v in params.items()}
    else:
        cparams = tree_cast(params, ctx.compute_dtype)
    if compress_step is not None:
        # INSIDE the grad: pruning masks zero the pruned positions'
        # gradients (w*mask transpose) and the quantizer's STE backward
        # actually runs — reference QAT/pruning semantics
        cparams = compress(ctx, cparams, compress_step)
    if counted:
        loss, counts = ctx.model.loss_with_counts_fn(cparams, batch, rng)
        return loss.astype(jnp.float32) * scale, {
            **counts, **flash_tile_sums(batch)}
    loss = ctx.model.loss(cparams, batch, rng)
    return loss.astype(jnp.float32) * scale


def micro_grads(ctx, params, batch, rng, scale, n=None, compress_step=None,
                counted=False):
    """(scaled loss, its gradient in the params' dtype) of one micro-batch
    of the ``n`` a step sums; ``n=None``: ``batch`` is the whole step's.
    ``counted``: ((scaled loss, the model's counts), gradient)."""
    with jax.named_scope(SCOPE_FWD_BWD):
        return jax.value_and_grad(
            functools.partial(scaled_loss, ctx, counted=counted),
            has_aux=counted)(
            params, batch, rng, scale if n is None else scale / n,
            compress_step)


def as_grads(ctx, grads):
    """Gradient storage: the accumulation dtype, laid out by the ZeRO
    policy (stage 2's reduce-scatter is this constraint).  Inside a
    step's account the tree states its bytes on one device
    (``tracing.gradient_bytes``): shapes, nothing in the program."""
    grads = tree_cast(grads, ctx.grad_dtype)
    mesh = ctx.zero_policy.mesh
    count_in_step(gradient_bytes_per_device=sum(
        math.prod(NamedSharding(mesh, spec).shard_shape(g.shape))
        * g.dtype.itemsize
        for g, spec in zip(jax.tree.leaves(grads), jax.tree.leaves(
            ctx.grad_specs, is_leaf=lambda x: isinstance(x, PartitionSpec)))))
    return ctx.zero_policy.constrain_grads(grads, ctx.grad_specs)


def accumulate(ctx, grads_acc, grads):
    with jax.named_scope(SCOPE_ACCUMULATE):
        return jax.tree.map(jnp.add, grads_acc, as_grads(ctx, grads))


def zero_grads(ctx, params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, ctx.grad_dtype), params)


def accumulated_grads(ctx, params, batches, rng, scale, compress_step=None):
    """Scan ``micro_grads`` + ``accumulate`` over the leading axis of
    ``batches``: (summed grads, summed loss, the model's counts summed —
    ``{}`` where it has none)."""
    n = jax.tree.leaves(batches)[0].shape[0]
    counted = ctx.model.loss_with_counts_fn is not None

    def micro(carry, mb):
        grads_acc, loss_acc = carry
        loss, grads = micro_grads(ctx, params, mb, rng, scale, n,
                                  compress_step, counted)
        loss, counts = loss if counted else (loss, {})
        return (accumulate(ctx, grads_acc, grads), loss_acc + loss), counts

    (grads, loss_sum), counts = jax.lax.scan(
        micro, (as_grads(ctx, zero_grads(ctx, params)), jnp.float32(0.0)),
        batches)
    return grads, loss_sum, jax.tree.map(lambda c: jnp.sum(c, axis=0), counts)


def _numerics_on(ctx):
    return ctx.num_leaf_group is not None and bool(ctx.num_groups)


def _optax_update(ctx, grads, opt_state, params, scale):
    """Unscale, norms, overflow, ``update`` + ``apply_updates``: the path
    of every transform but a lone ``mp_adamw`` without fp16.  -> (new
    params, new optimizer state, overflow, the gradient's norm, the
    numerics tier's group stats and update ratio or None)."""
    grads = jax.tree.map(lambda g: g / scale, grads)
    grad_norm = global_norm(grads)
    num_stats = None
    if _numerics_on(ctx):
        # in-graph numerics stats (ISSUE 15): per-group grad norms
        # + the non-finite provenance bitmap, device-resident until
        # the bank resolves (no host sync here)
        num_stats = group_stats(grads, ctx.num_leaf_group,
                                len(ctx.num_groups))
    if ctx.fp16:
        overflow = has_overflow(grads)
        safe_grads = jax.tree.map(
            lambda g: jnp.where(overflow, jnp.zeros_like(g), g), grads)
    else:
        overflow = jnp.bool_(False)
        safe_grads = grads
    updates, new_opt = ctx.optimizer.update(safe_grads, opt_state, params)
    new_params = optax.apply_updates(params, updates)
    update_ratio = None
    if num_stats is not None:
        # ||update|| / ||param||: the step-size health signal (a
        # collapsing or exploding ratio flags through the MAD
        # detector as anomaly/num_update_ratio).  Overflow steps
        # report 0.0 — the update was skipped.
        unorm = global_norm(updates)
        pnorm = global_norm(params)
        update_ratio = jnp.where(
            overflow, jnp.float32(0.0),
            unorm / jnp.maximum(pnorm, jnp.float32(1e-12)))
    return new_params, new_opt, overflow, grad_norm, num_stats, update_ratio


def _in_place_update(ctx, in_place, grads, opt_state, params):
    """As :func:`_optax_update` for an optimizer that offers
    ``update_in_place`` (runtime/bf16_optimizer.py, no fp16: the scale is
    1.0 and nothing overflows): it writes the parameters itself — a
    stacked leaf in one pass over its operands — and hands back a sum of
    squares and a non-finite count a leaf, from which the same norms are
    formed."""
    new_params, new_opt, sums = in_place(grads, opt_state, params)
    norm = lambda squares: jnp.sqrt(sum(squares))
    num_stats = update_ratio = None
    if _numerics_on(ctx):
        num_stats = group_stats_of(sums.grad_sq, sums.nonfinite,
                                   ctx.num_leaf_group, len(ctx.num_groups))
        update_ratio = norm(sums.update_sq) / jnp.maximum(
            norm(sums.param_sq), jnp.float32(1e-12))
    return (new_params, new_opt, jnp.bool_(False), norm(sums.grad_sq),
            num_stats, update_ratio)


@jax.named_scope(SCOPE_OPTIMIZER)
def apply_grads(ctx, state, grads, nf_group=None):
    """Shared epilogue: unscale, overflow check, update, skip-on-overflow.
    ``nf_group``: the ``train.nonfinite`` chaos fault's leaf group, set only
    by the builder of a ``train_step@nf<g>`` variant."""
    fp16 = ctx.fp16
    params, opt_state, scaler = (state["params"], state["opt_state"],
                                 state["scaler"])
    scale = loss_scale(ctx, state)
    if nf_group is not None and ctx.num_leaf_group is not None:
        # NaN-poison the chosen leaf group's gradient at TRACE time — a
        # dedicated step variant per injected group, so the healthy
        # compiled step is untouched (ISSUE 15)
        grads = inject_nonfinite(grads, ctx.num_leaf_group, nf_group)
    in_place = None if fp16 else getattr(ctx.optimizer, "update_in_place",
                                         None)
    (new_params, new_opt, overflow, grad_norm, num_stats,
     update_ratio) = (_optax_update(ctx, grads, opt_state, params, scale)
                      if in_place is None else
                      _in_place_update(ctx, in_place, grads, opt_state,
                                       params))
    if fp16:
        new_params = jax.tree.map(
            lambda old, new: jnp.where(overflow, old, new),
            params, new_params)
        new_opt = jax.tree.map(
            lambda old, new: jnp.where(overflow, old, new)
            if hasattr(new, "shape") and old.shape == new.shape else new,
            opt_state, new_opt)
    new_scaler = (update_scale(scaler, overflow, ctx.scaler_config)
                  if fp16 else scaler)
    # skipped (overflow) steps must not advance the LR schedule step
    # (reference: skipped steps leave the scheduler untouched)
    step_inc = jnp.where(overflow, jnp.int32(0), jnp.int32(1))
    # dict(state, ...) keeps auxiliary subtrees (e.g. the 1-bit
    # error-feedback buffers) intact through paths that don't manage
    # them (micro-step apply); train_step overwrites them itself
    new_state = dict(
        state,
        params=new_params,
        opt_state=new_opt,
        step=state["step"] + step_inc,
        scaler=new_scaler,
    )
    metrics = {
        # contract (both execution tiers, see zero/offload.py): a skipped
        # overflow step reports grad_norm 0.0, not the meaningless inf
        "grad_norm": jnp.where(overflow, jnp.float32(0.0), grad_norm),
        "overflow": overflow,
        "loss_scale": new_scaler.cur_scale,
    }
    if num_stats is not None:
        metrics["num_group_norms"] = num_stats[0]
        metrics["num_nonfinite"] = num_stats[1]
        metrics["num_update_ratio"] = update_ratio
    return new_state, metrics


def update(ctx, state, grads, loss_sum, scale, nf_group=None, counts=None):
    """``apply_grads``, reporting the loss with its scaling undone and the
    model's counts, where it has any."""
    new_state, metrics = apply_grads(ctx, state, grads, nf_group)
    metrics["loss"] = loss_sum / scale
    if counts:
        metrics["counts"] = counts
    return new_state, metrics


# ------------------------------------------------- quantized-exchange steps
# ``qgz_fn`` / ``plan``: the engine's ``_qgz_grad_fn()`` / ``_get_qgz_plan()``
# (None when the tier does not engage).
def _qgz_compresses(ctx, plan):
    """Compression plans apply in the quantized-exchange tier unless its
    stage-3 leaves enter as shards (compressing per shard would disagree
    across devices)."""
    wrapped = plan is not None and (
        plan["block_scope"] is not None
        or any(w is not None for w in plan["nonblock_wrap"]))
    return ctx.compression_plans is not None and not wrapped


def _qgz_step(ctx, qgz_fn, plan, nf_group):
    use_compress = _qgz_compresses(ctx, plan)
    onebit = plan["onebit"]

    def step(state, stacked_batch, rng):
        scale = loss_scale(ctx, state)
        cs = state["step"] if use_compress else None
        if onebit is None:
            with jax.named_scope(SCOPE_FWD_BWD):
                loss_sum, grads = qgz_fn(state["params"], stacked_batch, rng,
                                         scale, cs)
            return update(ctx, state, as_grads(ctx, grads), loss_sum, scale,
                          nf_group)
        # dense-vs-1-bit decision per step (reference schedule):
        # OnebitAdam/Lamb sync densely through freeze_step;
        # ZeroOneAdam syncs densely only at variance-update steps
        # (var_schedule_step recurrence, mirrored by the optimizer)
        from deepspeed_tpu.runtime.fp16.onebit.zoadam import \
            var_schedule_step
        ob = state["onebit"]
        count = state["step"] + 1
        if onebit["kind"] == "zerooneadam":
            dense_now, new_vi, new_vc = var_schedule_step(
                count, ob["var_interval"], ob["var_counter"],
                onebit["var_freeze_step"], onebit["var_update_scaler"])
        else:
            dense_now = count <= onebit["freeze_step"]
            new_vi, new_vc = ob["var_interval"], ob["var_counter"]
        with jax.named_scope(SCOPE_FWD_BWD):
            loss_sum, grads, new_ob = qgz_fn(
                state["params"], stacked_batch, rng, scale, cs, dense_now, ob)
        new_state, metrics = apply_grads(ctx, state, as_grads(ctx, grads),
                                         nf_group)
        # overflow steps roll back every 1-bit residual/counter (the
        # reference skips the whole optimizer step, exchange included)
        ov = metrics["overflow"]
        keep = lambda old, new: jnp.where(ov, old, new)
        # the residuals live in the loss-scaled gradient domain; when the
        # dynamic scaler moves (overflow backoff or window growth) they
        # must move with it or error feedback mis-weights the carried
        # correction by the scale ratio
        ratio = (new_state["scaler"].cur_scale / state["scaler"].cur_scale
                 if ctx.fp16 else jnp.float32(1.0))
        rescale = lambda old, new: keep(old, new) * ratio
        new_state["onebit"] = {
            "error": jax.tree.map(rescale, ob["error"], new_ob["error"]),
            "server": jax.tree.map(rescale, ob["server"], new_ob["server"]),
            "var_interval": keep(ob["var_interval"], new_vi),
            "var_counter": keep(ob["var_counter"], new_vc),
        }
        metrics["loss"] = loss_sum / scale
        return new_state, metrics
    return step


# ------------------------------------------------------------ the fused step
def build_train_step(ctx, qgz_fn=None, plan=None, nf_group=None):
    if ctx.model.meta.get("pipeline"):
        return _build_pipeline_train_step(ctx, qgz_fn, plan, nf_group)
    if ctx.compression_plans is not None and not _qgz_compresses(ctx, plan):
        logger.warning(
            "compression_training: plans are not applied in the "
            "stage-3 quantized-exchange tier (compressing per-shard "
            "would disagree across devices); training uncompressed")

    if qgz_fn is not None:
        step_body = _qgz_step(ctx, qgz_fn, plan, nf_group)
    else:
        def step_body(state, stacked_batch, rng):
            """stacked_batch leaves: [gas, global_micro, ...]."""
            scale = loss_scale(ctx, state)
            grads, loss_sum, counts = accumulated_grads(
                ctx, state["params"], stacked_batch, rng, scale,
                compression_step(ctx, state))
            return update(ctx, state, grads, loss_sum, scale, nf_group,
                          counts)

    def train_step(state, stacked_batch, rng):
        # this body runs while the step is traced: what the model's
        # code counts of itself (tracing.count_in_step) is the account
        # of this program.  (The name is the compiled module's.)
        with step_account(TRAIN_STEP_PROGRAM):
            return step_body(state, stacked_batch, rng)
    return train_step


def _build_pipeline_train_step(ctx, qgz_fn, plan, nf_group):
    """Pipelined models consume the [gas, micro, ...] stack (gas ≙ the
    pipeline's microbatch count; reference PipelineEngine.train_batch,
    runtime/pipe/engine.py:297).

    Memory profile: with ``pipeline.num_pipe_buffers = N`` the stack is
    processed in chunks of N microbatches inside a grad-accumulation
    scan, so only one chunk's activations are live for backward — the
    1F1B memory bound (reference schedule.py:176 ``num_pipe_buffers``).
    The trade is the reference's too: each chunk pays its own
    fill/drain bubble, (S-1)/(N+S-1) vs (S-1)/(M+S-1) for the all-live
    schedule (num_pipe_buffers unset/M keeps the old behaviour)."""
    gas, pipe_cfg = ctx.gas, ctx.pipe_cfg
    n_buffers = int(pipe_cfg.get("num_pipe_buffers", 0) or 0)
    n_stages = int(ctx.model.meta.get("num_stages", 1))
    sched = str(pipe_cfg.get("schedule", "") or "").lower()
    if sched not in ("", "1f1b", "gpipe"):
        raise ValueError(
            f"pipeline.schedule={sched!r}: expected '1f1b' or 'gpipe' "
            "(default: all-live/chunked GPipe)")
    if sched == "1f1b" and n_stages > 1:
        if gas < n_stages:
            logger.warning(
                f"pipeline.schedule='1f1b' needs gradient_accumulation_"
                f"steps >= pipeline stages ({n_stages}), got {gas}; "
                "running the all-live schedule")
        else:
            if pipe_cfg.get("num_pipe_buffers"):
                logger.warning(
                    "pipeline.num_pipe_buffers is ignored under "
                    "schedule='1f1b' (the interleaved schedule's ring "
                    "buffers are sized by the stage count)")
            return _build_1f1b_train_step(ctx, n_stages, nf_group)
    chunked = 0 < n_buffers < gas and gas % n_buffers == 0
    if chunked and n_buffers < n_stages:
        logger.warning(
            f"pipeline.num_pipe_buffers={n_buffers} < pipeline stages "
            f"{n_stages}: a chunk cannot fill the pipeline; running "
            f"all-live")
        chunked = False
    elif n_buffers and not chunked and n_buffers < gas:
        logger.warning(
            f"pipeline.num_pipe_buffers={n_buffers} does not divide "
            f"gradient_accumulation_steps={gas}; running all-live")

    if qgz_fn is not None:
        # quantized/sparse exchange tier under GPipe (round-3 VERDICT
        # item 4): the tier's shard_map keeps the pipe axis auto, so the
        # scanned pipeline composes with the int8 gradient wire
        step = _qgz_step(ctx, qgz_fn, plan, nf_group)

        def qgz_train_step(state, stacked_batch, rng):
            return step(state, stacked_batch, rng)
        return qgz_train_step

    def train_step(state, stacked_batch, rng):
        params, cs = state["params"], compression_step(ctx, state)
        scale = loss_scale(ctx, state)
        if not chunked:
            # the pipelined loss averages its microbatches itself
            loss, grads = micro_grads(ctx, params, stacked_batch, rng,
                                      scale, compress_step=cs)
        else:
            # each chunk is weighted by scale/n_chunks, so the sum over
            # chunks is the full-batch mean at full scale
            chunks = jax.tree.map(
                lambda x: x.reshape(gas // n_buffers, n_buffers,
                                    *x.shape[1:]), stacked_batch)
            grads, loss, _ = accumulated_grads(ctx, params, chunks, rng,
                                               scale, cs)
        return update(ctx, state, as_grads(ctx, grads), loss, scale,
                      nf_group)

    return train_step


def _build_1f1b_train_step(ctx, n_stages, nf_group):
    """True one-pass 1F1B pipeline schedule (config ``pipeline.schedule
    = "1f1b"``; reference runtime/pipe/schedule.py:189 TrainSchedule):
    one fill/drain for the whole batch at O(n_stages) live activations
    — see runtime/pipe/pipeline.pipeline_1f1b_loss_and_grad."""
    from deepspeed_tpu.runtime.pipe.pipeline import \
        pipeline_1f1b_loss_and_grad
    model = ctx.model
    if ctx.compression_plans is not None:
        logger.warning(
            "compression_training is not applied under the 1f1b "
            "pipeline schedule (the manual fwd/bwd interleave bypasses "
            "the compression transform); training uncompressed")

    def train_step(state, stacked_batch, rng):
        scale = loss_scale(ctx, state)
        cparams = tree_cast(state["params"], ctx.compute_dtype)

        def head_loss(p, y, b):
            # the pipelined model's single loss definition (shared
            # with the GPipe schedule), scaled per microbatch
            return (model.head_loss_fn(p, y, b).astype(jnp.float32)
                    * (scale / ctx.gas))

        with jax.named_scope(SCOPE_FWD_BWD):
            loss_sum, grads = pipeline_1f1b_loss_and_grad(
                lambda h, lp: model.block_fn(lp, h), model.embed_fn,
                head_loss, cparams, model.blocks_key, stacked_batch,
                n_stages)
        return update(ctx, state, as_grads(ctx, grads), loss_sum, scale,
                      nf_group)

    return train_step


# ------------------------------------------- the micro API and offload tiers
def build_loss(ctx):
    def loss(state, batch, rng):
        return scaled_loss(ctx, state["params"], batch, rng,
                           jnp.float32(1.0), compression_step(ctx, state))
    return loss


def build_grad(ctx):
    def grad_fn(state, batch, rng, grads_acc):
        scale = loss_scale(ctx, state)
        loss, grads = micro_grads(ctx, state["params"], batch, rng, scale,
                                  ctx.gas, compression_step(ctx, state))
        grads = accumulate(ctx, grads_acc, grads)
        return loss / scale * ctx.gas, grads
    return grad_fn


def build_grad_step(ctx):
    """Optimizer offload: scan the gas micro-batches, stop at gradients."""
    def grad_step(state, stacked_batch, rng):
        scale = loss_scale(ctx, state)
        grads, loss_sum, _ = accumulated_grads(ctx, state["params"],
                                               stacked_batch, rng, scale)
        return loss_sum / scale, grads
    return grad_step


def build_grad_micro(ctx):
    """Parameter offload: ONE micro-batch per call, python-level grad
    accumulation on host — the gas-scan would keep full fp32 grads
    resident on device, exactly what param offload must avoid."""
    def grad_micro(state, mb, rng):
        scale = loss_scale(ctx, state)
        loss, grads = micro_grads(ctx, state["params"], mb, rng, scale,
                                  ctx.gas)
        # grads keep the params' storage dtype: a full-tensor fp32
        # convert would materialise each stacked leaf on device (8 GB
        # per MLP leaf at 6.7B); the streamed optimizer upcasts per
        # layer slice instead
        return loss / scale * ctx.gas, grads
    return grad_micro


def build_grad_acc(ctx):
    """gas accumulation for the streamed-optimizer path; leaves bounce
    through device whole-leaf (transient HBM = largest leaf)."""
    @jax.named_scope(SCOPE_ACCUMULATE)
    def acc_fn(a, b):
        return jax.tree.map(jnp.add, a, b)
    return acc_fn


def build_apply(ctx):
    def apply(state, grads):
        return apply_grads(ctx, state, grads)
    return apply


def build_zero_grads(ctx):
    # not laid out by ``as_grads``: the engine's out_shardings do that
    def make_zeros(params):
        return zero_grads(ctx, params)
    return make_zeros


#: every program name ``engine._get_compiled`` knows (a ``train_step@nf<g>``
#: variant is ``train_step`` built with ``nf_group=g``)
PROGRAMS = {
    "train_step": build_train_step,
    "loss": build_loss,
    "grad": build_grad,
    "grad_step": build_grad_step,
    "grad_micro": build_grad_micro,
    "grad_acc": build_grad_acc,
    "apply": build_apply,
    "zero_grads": build_zero_grads,
}
