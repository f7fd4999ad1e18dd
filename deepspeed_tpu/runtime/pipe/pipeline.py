"""Compiled pipeline parallelism (reference: deepspeed/runtime/pipe/engine.py:54
``PipelineEngine`` executing a 1F1B instruction stream with p2p send/recv,
p2p.py:50).

TPU-native formulation — the whole schedule is ONE XLA program:

- layer params stay stacked ``[L, ...]`` and are viewed as
  ``[n_stages, L/n_stages, ...]`` with the stage dim sharded over the ``pipe``
  mesh axis;
- a ``vmap`` over the stage dim applies every stage to its activation slot in
  parallel (each device computes only its stage — the weights are local);
- shifting the activation buffer one slot along the stage dim lowers to an XLA
  ``CollectivePermute`` over ICI — the reference's send/recv pairs;
- a ``lax.scan`` over M + S - 1 ticks runs the GPipe fill/steady/drain; the
  backward pass through the scan is the reversed pipeline (XLA schedules it —
  no hand-written 1F1B instruction interleave needed).

Bubble fraction is (S-1)/(M+S-1), identical to the reference's schedule.
Everything stays inside the automatic SPMD partitioner, so ZeRO/TP/SP compose
with pipelining without manual collectives.
"""
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.mesh import get_topology, PIPE_AXIS


def _pipe_sharding():
    """Pipe-axis sharding against the CURRENT trace context's mesh — when
    the pipeline runs inside the quantized-exchange tier's partially-
    manual shard_map (engine._qgz_grad_fn), the constraint must carry
    that context's axis types (data/hpz Manual, pipe Auto), not the
    all-auto concrete mesh."""
    from deepspeed_tpu.utils.jax_compat import get_abstract_mesh
    cur = get_abstract_mesh()
    if not cur.empty:
        return NamedSharding(cur, P(PIPE_AXIS))
    return NamedSharding(get_topology().mesh, P(PIPE_AXIS))


def stage_params_view(blocks_params, n_stages: int):
    """[L, ...] stacked layer params -> [n_stages, L/S, ...], stage dim
    constrained to the pipe axis."""
    spec = _pipe_sharding()

    def reshape(p):
        L = p.shape[0]
        assert L % n_stages == 0, (
            f"num_layers {L} must divide evenly into {n_stages} stages")
        v = p.reshape(n_stages, L // n_stages, *p.shape[1:])
        return lax.with_sharding_constraint(v, spec)

    return jax.tree.map(reshape, blocks_params)


def make_stage_apply(block_fn: Callable):
    """One pipeline stage: scan ``block_fn`` over the stage's layer stack
    (shared by the GPipe and 1F1B schedules)."""
    def stage_apply(stage_params, x):
        def body(c, lp):
            return block_fn(c, lp), None
        return lax.scan(body, x, stage_params)[0]
    return stage_apply


def pipeline_blocks(block_fn: Callable, blocks_params, x_micro, n_stages: int):
    """Run stacked transformer blocks as an n_stages pipeline.

    Args:
        block_fn: (x, layer_params) -> x, one layer.
        blocks_params: stacked [L, ...] pytree.
        x_micro: [n_micro, B_micro, S, D] microbatched activations.
    Returns:
        [n_micro, B_micro, S, D] outputs after all L layers.
    """
    if n_stages == 1:
        def body(c, lp):
            return block_fn(c, lp), None

        def run_one(x):
            return lax.scan(body, x, blocks_params)[0]
        return jax.vmap(run_one)(x_micro) if x_micro.ndim > 3 else run_one(x_micro)

    n_micro = x_micro.shape[0]
    assert n_micro >= n_stages, (
        f"need >= {n_stages} microbatches to fill the pipeline, got {n_micro} "
        f"(set gradient_accumulation_steps >= pipe_parallel_size)")
    staged = stage_params_view(blocks_params, n_stages)
    state_spec = _pipe_sharding()
    vstages = jax.vmap(make_stage_apply(block_fn))

    state = jnp.zeros((n_stages,) + x_micro.shape[1:], x_micro.dtype)
    state = lax.with_sharding_constraint(state, state_spec)
    outputs = jnp.zeros_like(x_micro)
    n_ticks = n_micro + n_stages - 1

    def tick(carry, t):
        state, outputs = carry
        # ingest microbatch t at stage 0 (clamped after the last microbatch —
        # those ticks only drain the tail stages)
        inp = lax.dynamic_index_in_dim(
            x_micro, jnp.minimum(t, n_micro - 1), axis=0, keepdims=False)
        state = lax.dynamic_update_index_in_dim(state, inp, 0, axis=0)
        state = lax.with_sharding_constraint(state, state_spec)
        state = vstages(staged, state)
        state = lax.with_sharding_constraint(state, state_spec)
        # microbatch t-(S-1) finishes at the last stage this tick
        out_t = t - (n_stages - 1)
        finished = lax.dynamic_index_in_dim(
            state, n_stages - 1, axis=0, keepdims=False)
        updated = lax.dynamic_update_index_in_dim(
            outputs, finished, jnp.maximum(out_t, 0), axis=0)
        outputs = jnp.where(out_t >= 0, updated, outputs)
        # shift: stage i's output becomes stage i+1's input (CollectivePermute)
        state = jnp.roll(state, shift=1, axis=0)
        state = lax.with_sharding_constraint(state, state_spec)
        return (state, outputs), None

    (state, outputs), _ = lax.scan(
        tick, (state, outputs), jnp.arange(n_ticks))
    return outputs


def pipeline_1f1b_loss_and_grad(block_fn, embed_fn, head_loss_fn, params,
                                blocks_key: str, stacked_batch,
                                n_stages: int):
    """One-pass interleaved pipeline schedule (reference: the 1F1B
    ``TrainSchedule``, runtime/pipe/schedule.py:189): ONE fill and ONE
    drain for the whole batch, with backward starting as soon as each
    microbatch finishes — live activations are O(n_stages) stage-input
    buffers regardless of the microbatch count (vs the scanned-GPipe
    path's all-live M residuals).

    Mechanics: a single ``lax.scan`` over M + 2(S-1) ticks.  Every tick,
    every stage (vmapped over the pipe-sharded stage dim) runs one forward
    on its current slot AND one recompute-backward (``jax.vjp`` against
    the ring-buffered stage input) on the microbatch whose cotangent just
    arrived; the head loss + its VJP run in-loop on the last stage's
    finished microbatch, so its gradient enters the backward pipeline the
    same tick.  Activations shift +1 and cotangents -1 per tick — XLA
    lowers both to CollectivePermute over ICI.

    Trade vs the reference's asymmetric schedule: SPMD stages execute in
    lockstep, so fill/drain ticks still execute (masked) both slots —
    the bubble is 2(S-1)/(M+2(S-1)) of ticks, each tick costing one
    forward plus one recomputed backward.  For M comparable to or above
    S this is strictly less idle time than the chunked-GPipe fallback's
    per-chunk fill/drain at the same memory bound.

    Returns (mean_loss * scale_undone, grads) with ``grads`` matching the
    full params tree (blocks grads summed over microbatches, non-block
    grads = embed + head contributions).
    """
    state_spec = _pipe_sharding()
    bk = blocks_key
    M = jax.tree.leaves(stacked_batch)[0].shape[0]
    S = n_stages
    assert M >= S, (f"need >= {S} microbatches to fill the pipeline, "
                    f"got {M}")
    n_buf = 2 * S - 1          # max in-flight stage inputs (stage 0 worst)

    nonblock = {k: v for k, v in params.items() if k != bk}

    def embed_mb(nb, mb_idx):
        # one microbatch's embedding, (re)computed per tick — no [M, ...]
        # embedding/cotangent buffers survive the loop
        b = jax.tree.map(lambda v: v[mb_idx], stacked_batch)
        return embed_fn({**nb, bk: params[bk]}, b)

    stage_apply = make_stage_apply(block_fn)

    def stage_bwd(stage_params, x_in, gout):
        _, vjp = jax.vjp(stage_apply, stage_params, x_in)
        return vjp(gout)                       # (dparams, dx)

    vfwd = jax.vmap(stage_apply)
    vbwd = jax.vmap(stage_bwd)

    staged = stage_params_view(params[bk], S)
    mb_aval = jax.eval_shape(embed_mb, nonblock, 0)
    mb_shape, dt = mb_aval.shape, mb_aval.dtype
    zeros_state = lambda: lax.with_sharding_constraint(
        jnp.zeros((S,) + mb_shape, dt), state_spec)
    saved0 = lax.with_sharding_constraint(
        jnp.zeros((S, n_buf) + mb_shape, dt), state_spec)
    dstaged0 = jax.tree.map(
        lambda p: lax.with_sharding_constraint(
            jnp.zeros(p.shape, jnp.float32), state_spec), staged)
    dnb0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), nonblock)
    stage_ids = jnp.arange(S)
    n_ticks = M + 2 * (S - 1)

    def head_loss_mb(nb, y, mb_idx):
        b = jax.tree.map(lambda v: v[mb_idx], stacked_batch)
        return head_loss_fn({**nb, bk: params[bk]}, y, b)

    def tick(carry, t):
        (act, saved, grad_in, dstaged, dnb, loss_acc) = carry
        # ---------------- forward slot ---------------------------------
        mf = t - stage_ids                      # fwd microbatch per stage
        fvalid = (mf >= 0) & (mf < M)
        inp = embed_mb(nonblock, jnp.clip(t, 0, M - 1))
        act = lax.dynamic_update_index_in_dim(act, inp.astype(dt), 0,
                                              axis=0)
        act = lax.with_sharding_constraint(act, state_spec)
        # ring-buffer this tick's stage inputs (slot = mf % n_buf)
        slot_f = jnp.where(fvalid, mf % n_buf, 0)
        upd = jax.vmap(lambda svd, a, sl, v: jnp.where(
            v, lax.dynamic_update_index_in_dim(svd, a, sl, axis=0), svd))(
                saved, act, slot_f, fvalid)
        saved = lax.with_sharding_constraint(upd, state_spec)
        out = vfwd(staged, act)
        out = lax.with_sharding_constraint(out, state_spec)

        # ---------------- head loss + its vjp on the finishing mb ------
        mh = t - (S - 1)
        hvalid = (mh >= 0) & (mh < M)
        y_last = lax.dynamic_index_in_dim(out, S - 1, axis=0,
                                          keepdims=False)
        mh_c = jnp.clip(mh, 0, M - 1)
        (loss_mb, (dnb_h, dy)) = _head_vjp(head_loss_mb, nonblock, y_last,
                                           mh_c)
        w = jnp.where(hvalid, jnp.float32(1.0), jnp.float32(0.0))
        loss_acc = loss_acc + loss_mb * w
        dnb = jax.tree.map(lambda a, g: a + g * w, dnb, dnb_h)

        # ---------------- backward slot --------------------------------
        mb = t - 2 * (S - 1) + stage_ids        # bwd microbatch per stage
        bvalid = (mb >= 0) & (mb < M)
        # cotangent entering the last stage is this tick's head grad
        gin = lax.dynamic_update_index_in_dim(
            grad_in, (dy * w).astype(dt), S - 1, axis=0)
        gin = lax.with_sharding_constraint(gin, state_spec)
        slot_b = jnp.where(bvalid, mb % n_buf, 0)
        x_saved = jax.vmap(lambda svd, sl: lax.dynamic_index_in_dim(
            svd, sl, axis=0, keepdims=False))(saved, slot_b)
        dp, dx = vbwd(staged, x_saved, gin)
        bmask = bvalid.astype(jnp.float32)
        dstaged = jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32)
            * bmask.reshape((S,) + (1,) * (g.ndim - 1)), dstaged, dp)
        dstaged = jax.tree.map(
            lambda a: lax.with_sharding_constraint(a, state_spec), dstaged)
        # stage 0's dx is the embedding cotangent for microbatch mb[0]:
        # recompute that microbatch's embedding under vjp and charge the
        # non-block params right here (no [M, ...] cotangent buffer)
        dx_embed = lax.dynamic_index_in_dim(dx, 0, axis=0, keepdims=False)
        mb0 = jnp.clip(t - 2 * (S - 1), 0, M - 1)
        _, evjp = jax.vjp(lambda nb: embed_mb(nb, mb0), nonblock)
        (dnb_e,) = evjp(dx_embed.astype(dt))
        w0 = bvalid[0].astype(jnp.float32)
        dnb = jax.tree.map(lambda a, g: a + g.astype(jnp.float32) * w0,
                           dnb, dnb_e)

        # ---------------- shifts (CollectivePermute) -------------------
        act = jnp.roll(out, shift=1, axis=0)
        act = lax.with_sharding_constraint(act, state_spec)
        grad_in = jnp.roll(dx.astype(dt), shift=-1, axis=0)
        grad_in = lax.with_sharding_constraint(grad_in, state_spec)
        return (act, saved, grad_in, dstaged, dnb, loss_acc), None

    carry0 = (zeros_state(), saved0, zeros_state(), dstaged0, dnb0,
              jnp.float32(0.0))
    (act, saved, grad_in, dstaged, dnb,
     loss_sum), _ = lax.scan(tick, carry0, jnp.arange(n_ticks))

    # back to stacked [L, ...] layout
    dblocks = jax.tree.map(
        lambda g: g.reshape((g.shape[0] * g.shape[1],) + g.shape[2:]),
        dstaged)
    grads = dict(dnb)
    grads[bk] = dblocks
    return loss_sum, grads


def _head_vjp(head_loss_mb, nonblock, y, mb_idx):
    """loss + (d_nonblock, d_y) for one microbatch's head/loss."""
    loss, vjp = jax.vjp(lambda nb, yy: head_loss_mb(nb, yy, mb_idx),
                        nonblock, y)
    dnb, dy = vjp(jnp.float32(1.0))
    return loss, (dnb, dy)


def pipeline_model(model, num_stages: int):
    """Wrap a Model exposing (embed_fn, block_fn, head_fn) into a pipelined
    Model (reference: PipelineModule, runtime/pipe/module.py:86; tied
    embeddings live outside the pipelined region — the reference's
    TiedLayerSpec replication, module.py:421 — so no tied-grad all-reduce is
    needed: the embedding computes on every stage and XLA keeps one copy per
    non-pipe mesh position)."""
    from deepspeed_tpu.models.model import Model
    import optax

    assert model.embed_fn is not None and model.block_fn is not None \
        and model.head_fn is not None, \
        "model must expose embed_fn/block_fn/head_fn for pipelining"

    def head_loss_fn(params, y_mb, batch_mb):
        """ONE microbatch's head + causal-LM loss — the single loss
        definition both pipeline schedules (scanned GPipe and 1F1B)
        consume, so they cannot drift apart."""
        logits = model.head_fn(params, y_mb)
        tokens = batch_mb["input_ids"]
        losses = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1].astype(jnp.float32), tokens[:, 1:])
        mask = batch_mb.get("attention_mask")
        if mask is not None:
            m = mask[:, 1:].astype(jnp.float32)
            return (losses * m).sum() / jnp.maximum(m.sum(), 1.0)
        return losses.mean()

    def loss_fn(params, stacked_batch, rng=None):
        x = jax.vmap(lambda b: model.embed_fn(params, b))(stacked_batch)
        x = pipeline_blocks(
            lambda h, lp: model.block_fn(lp, h),
            params[model.blocks_key], x, num_stages)
        per_mb = jax.vmap(lambda y, b: head_loss_fn(params, y, b))(
            x, stacked_batch)
        return per_mb.mean()

    def apply_fn(params, batch, rng=None):
        # single (non-micro) batch: run as one microbatch group of size S
        return model.apply_fn(params, batch, rng)

    # storage layout: the stacked layer dim of every blocks leaf is sharded
    # over the pipe axis (stage-major), so the [n_stages, L/S, ...] view in
    # pipeline_blocks is a local reshape
    specs = model.logical_specs
    if specs is not None:
        def add_pipe(spec):
            entries = list(tuple(spec)) or [None]
            assert entries[0] is None, \
                f"blocks leaf dim0 (layers) already sharded: {spec}"
            entries[0] = PIPE_AXIS
            return P(*entries)

        specs = dict(specs)
        specs[model.blocks_key] = jax.tree.map(
            add_pipe, specs[model.blocks_key],
            is_leaf=lambda x: isinstance(x, P))

    m = Model(
        config=model.config,
        init_fn=model.init_fn,
        apply_fn=apply_fn,
        loss_fn=loss_fn,
        logical_specs=specs,
        flops_per_token=model.flops_per_token,
        meta={**model.meta, "pipeline": True, "num_stages": num_stages},
    )
    m.embed_fn = model.embed_fn
    m.block_fn = model.block_fn
    m.head_fn = model.head_fn
    m.head_loss_fn = head_loss_fn
    m.blocks_key = model.blocks_key
    return m
