"""Mixed-precision Adam/AdamW states (reference capability:
runtime/bf16_optimizer.py — the BF16_Optimizer that decides which training
state lives in which precision; and the fp32-master economics of
runtime/zero/stage_1_and_2.py).

On a 16 GB-HBM chip the optimizer phase is pure HBM streaming: fp32
master + fp32 m/v + fp32 grads cost ~28 bytes/param/step, the diet below
18 (22 ms of the 760M cell's 560 ms step, 72 of the Granite cell's 241 ms
step that runs it after every sequence: PERF.md §3).  This module provides
the diet:

- ``mu_dtype``/``nu_dtype``: store Adam moments in bf16 (halves moment
  traffic and memory; math stays fp32 — bf16 keeps fp32's exponent range,
  so v never under/overflows, it only loses mantissa).
- ``master_dtype="bfloat16"``: Kahan-compensated bf16 master weights.
  Plain bf16 masters silently DROP updates smaller than ~2^-8 of the
  weight (the reason fp32 masters exist); the compensation buffer carries
  the rounding residual so tiny updates accumulate across steps.  Costs
  2 bytes/param (vs 4 for an fp32 master) and makes GPT-2 1.3B ZeRO-2
  fit a single 16 GB chip (BASELINE config 2).

The transform is optax-compatible: ``init``/``update`` with a NamedTuple
state, so the engine's eval_shape/tree_map_params sharding plumbing and
checkpointing apply unchanged.  The Kahan trick under the optax contract
(``apply_updates`` adds ``p + u`` and casts to ``p.dtype``): the update we
return is ``t - p`` for bf16 values t, p — and the compensation is
computed against the applied result by replaying the bf16 cast, so
any rounding in apply lands in the residual, not in lost training signal.

One function of a leaf is the update, and there are two ways in.
``update`` is the optax entry; whatever composes the transform
(``optax.chain`` with clipping or a ``trainable_mask``, the offload tiers,
fp16's skip-on-overflow) takes it.  ``update_in_place`` is the engine's
when the transform stands alone (``step_programs.apply_grads``): the same
update with the parameters written as ``apply_updates`` writes them, and
beside them the sums the step reports (the gradient's, the update's and
the parameters' squares, the non-finite count), so that a leaf's update
and its sums are ONE expression over its five operands.  There a leaf of
three or more axes — a stack over layers or experts — is taken behind
``jax.lax.optimization_barrier``: its gradient is handed over whole (by
the layer loop or a grouped kernel) or as pieces to be joined, and XLA,
left free, duplicates that join into every consumer and lets the update
fall into five passes over the operands (the Granite cell: 16.3 ms a leaf
where one pass is 6.8); behind the barrier it is one fusion that reads
each operand once, 18 B a parameter, in place.  Matrices, vectors and the
embedding table are left to XLA whole: a matrix's update can ride its
matmul's epilogue and never write the gradient (the Phi-4 cell).
"""
from typing import Any, Callable, List, NamedTuple, Optional, Union

import chex
import jax
import jax.numpy as jnp
import optax

from deepspeed_tpu.telemetry.tracing import count_in_step


class MPAdamState(NamedTuple):
    count: chex.Array
    mu: Any
    nu: Any
    comp: Any          # Kahan residuals (zeros-shaped; unused if fp32 master)


class LeafSums(NamedTuple):
    """What a step reports of its gradients, its update and its
    parameters: one float32 scalar (``nonfinite``: int32) a leaf, in the
    parameters' flatten order."""
    grad_sq: List[Any]       # sum of g**2
    nonfinite: List[Any]     # count of g that is NaN or Inf (summed in
                             # float32: exact to 2**24 a leaf)
    update_sq: List[Any]     # sum of u**2, u the applied update in float32
    param_sq: List[Any]      # sum of p**2, p before the update


class MPAdamW(NamedTuple):
    """``optax.GradientTransformation``'s ``init`` / ``update`` and, beside
    them, ``update_in_place``: ``(grads, state, params) -> (new params, new
    state, LeafSums)``.  Whatever composes the transform (``optax.chain``,
    ``optax.masked``) sees ``init`` and ``update`` only."""
    init: Callable
    update: Callable
    update_in_place: Callable


def mp_adamw(learning_rate: Union[float, Any], b1: float = 0.9,
             b2: float = 0.999, eps: float = 1e-8,
             weight_decay: float = 0.0,
             mu_dtype: Optional[str] = None,
             nu_dtype: Optional[str] = None,
             master_dtype: str = "float32") -> MPAdamW:
    """AdamW with per-state storage dtypes and optional Kahan-compensated
    low-precision master weights.  ``learning_rate`` may be a float or an
    optax schedule."""
    mu_dt = jnp.dtype(mu_dtype) if mu_dtype else jnp.float32
    nu_dt = jnp.dtype(nu_dtype) if nu_dtype else jnp.float32
    kahan = jnp.dtype(master_dtype) != jnp.float32
    comp_dt = jnp.dtype(master_dtype) if kahan else jnp.float32

    def init(params):
        zeros = lambda dt: jax.tree.map(
            lambda p: jnp.zeros(p.shape, dt), params)
        # fp32-master mode: scalar placeholders (rank 0 -> the engine's
        # rank-fix replicates them; zero-size arrays would break orbax)
        comp = (zeros(comp_dt) if kahan
                else jax.tree.map(lambda p: jnp.zeros((), jnp.float32),
                                  params))
        return MPAdamState(jnp.zeros((), jnp.int32), zeros(mu_dt),
                           zeros(nu_dt), comp)

    def scalars(state):
        """(the new count, lr, bc1, bc2) of the step ``state`` is about to
        take."""
        count = state.count + 1
        c = count.astype(jnp.float32)
        # optax convention (scale_by_schedule): the schedule is evaluated
        # at the PRE-increment count, so step 0 uses schedule(0) — the
        # bias correction below stays 1-based like Adam's t
        lr = (learning_rate(state.count) if callable(learning_rate)
              else learning_rate)
        return (count, jnp.asarray(lr, jnp.float32), 1.0 - b1 ** c,
                1.0 - b2 ** c)

    def leaf(g, m, v, comp, p, lr, bc1, bc2):
        """-> (u, m, v, comp): ``u`` the update in float32 that
        ``apply_updates`` adds to ``p``."""
        g32 = g.astype(jnp.float32)
        m32 = b1 * m.astype(jnp.float32) + (1.0 - b1) * g32
        v32 = b2 * v.astype(jnp.float32) + (1.0 - b2) * g32 * g32
        p32 = p.astype(jnp.float32)
        step = -(lr * (m32 / bc1) /
                 (jnp.sqrt(v32 / bc2) + eps)
                 + lr * weight_decay * p32)
        if not kahan:
            return step, m32.astype(mu_dt), v32.astype(nu_dt), comp
        # Kahan: y = step - residual; apply; new residual =
        # (applied - p) - y, with "applied" replayed through the same
        # bf16 casts apply_updates performs
        y = step - comp.astype(jnp.float32)
        u = ((p32 + y).astype(p.dtype).astype(jnp.float32) - p32)
        u_cast = u.astype(p.dtype)
        applied = ((p32 + u_cast.astype(jnp.float32))
                   .astype(p.dtype).astype(jnp.float32))
        new_comp = ((applied - p32) - y).astype(comp_dt)
        return u, m32.astype(mu_dt), v32.astype(nu_dt), new_comp

    def flat_operands(grads, state, params):
        flat_g, tdef = jax.tree_util.tree_flatten(grads)
        return tdef, list(zip(
            flat_g, tdef.flatten_up_to(state.mu),
            tdef.flatten_up_to(state.nu), tdef.flatten_up_to(state.comp),
            tdef.flatten_up_to(params)))

    def trees(tdef, rows, n):
        """The first ``n`` columns of per-leaf ``rows`` as trees."""
        return [jax.tree_util.tree_unflatten(tdef, [r[i] for r in rows])
                for i in range(n)]

    def update(grads, state, params=None):
        if params is None:
            raise ValueError("mp_adamw requires params")
        count, *step_scalars = scalars(state)
        tdef, operands = flat_operands(grads, state, params)
        updates, mu, nu, comp = trees(
            tdef, [leaf(*five, *step_scalars) for five in operands], 4)
        return updates, MPAdamState(count, mu, nu, comp)

    def update_in_place(grads, state, params):
        """``update`` and ``optax.apply_updates`` at once, and the sums the
        step reports: -> (new params, new state, LeafSums).

        A leaf of three or more axes (a stack over layers or experts) is
        updated behind a barrier — one pass over its operands whatever
        hands its gradient over (the module's docstring); a matrix or a
        vector is left for XLA to finish where its gradient is made.  The
        step's account says which (``tracing.optimizer_fused``)."""
        count, *step_scalars = scalars(state)
        tdef, operands = flat_operands(grads, state, params)
        fused = {"leaves": 0, "param_bytes": 0, "xla_leaves": 0,
                 "xla_param_bytes": 0}
        out = []
        for five in operands:
            stacked = five[4].ndim >= 3
            if stacked:
                five = jax.lax.optimization_barrier(five)
            g, p = five[0], five[4]
            u, m, v, comp = leaf(*five, *step_scalars)
            f32 = lambda x: x.astype(jnp.float32)
            # counted in float32 like the other three: an integer reduce
            # beside them is a fusion, and a pass over ``g``, of its own
            bad = jnp.sum(jnp.where(jnp.isfinite(g), 0.0, 1.0))
            out.append((
                (p + u).astype(p.dtype),     # as ``apply_updates`` writes it
                m, v, comp,
                jnp.sum(jnp.square(f32(g))), bad.astype(jnp.int32),
                jnp.sum(jnp.square(u)), jnp.sum(jnp.square(f32(p)))))
            by = "" if stacked else "xla_"
            fused[by + "leaves"] += 1
            fused[by + "param_bytes"] += p.size * p.dtype.itemsize
        count_in_step(optimizer_fused=fused)
        new_params, mu, nu, comp = trees(tdef, out, 4)
        return (new_params, MPAdamState(count, mu, nu, comp),
                LeafSums(*([row[i] for row in out] for i in range(4, 8))))

    return MPAdamW(init, update, update_in_place)
