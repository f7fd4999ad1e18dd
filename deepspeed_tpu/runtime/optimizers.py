"""Optimizer registry mapping DeepSpeed config names to optax transforms
(reference: engine.py:1233 ``_configure_basic_optimizer`` — FusedAdam,
DeepSpeedCPUAdam, FusedLamb, OnebitAdam, ...).

FusedAdam and Adam share an implementation: "fused" is what XLA does to an
optax update under jit — which reads a leaf's operands once where it can
finish the update in one fusion, and two to five times where the step's norms
or a gradient handed over in pieces pull it apart (PERF.md section 3).  The
mixed-precision AdamW (runtime/bf16_optimizer.py ``mp_adamw``: any
``bf16.master_weights_dtype`` / ``optimizer_states_dtype``) keeps it at one
for the leaves where that happens: standing alone (no clipping, no
trainable_mask, no fp16) it updates every stacked leaf of three or more axes
behind an ``optimization_barrier`` with the step's sums in the same
expression — one fusion, in place — and leaves matrices, vectors and every
composed transform to XLA whole.
DeepSpeedCPUAdam (ZeRO-Offload's host-side SIMD optimizer,
csrc/adam/cpu_adam_impl.cpp) maps to the host-offload execution tier selected
by the engine, not a different math.
"""
from typing import Optional

import numpy as np
import optax

from deepspeed_tpu.runtime import constants as C


def _adam_args(params: dict):
    betas = params.get("betas", (0.9, 0.999))
    return dict(
        b1=float(betas[0]), b2=float(betas[1]),
        eps=float(params.get("eps", 1e-8)),
    )


def build_optimizer(name: Optional[str], params: Optional[dict],
                    lr_schedule=None, mu_dtype=None, nu_dtype=None,
                    master_dtype: str = "float32"
                    ) -> optax.GradientTransformation:
    """Build the inner (post-ZeRO) optimizer transform.

    ``lr_schedule`` overrides the config's static lr when given (the engine
    wires the "scheduler" section here).  ``mu_dtype``/``nu_dtype``/
    ``master_dtype`` select mixed-precision optimizer states
    (runtime/bf16_optimizer.py) — Adam family only.
    """
    params = dict(params or {})
    lr = lr_schedule if lr_schedule is not None else float(params.get("lr", 1e-3))
    name = (name or C.ADAM_OPTIMIZER).lower()
    wd = float(params.get("weight_decay", 0.0))

    mp_states = (mu_dtype or nu_dtype
                 or np.dtype(master_dtype) != np.dtype("float32"))
    if mp_states:
        adam_family = (C.ADAM_OPTIMIZER, C.FUSED_ADAM, C.CPU_ADAM,
                       C.ADAMW_OPTIMIZER)
        if name not in adam_family:
            raise ValueError(
                "bf16.master_weights_dtype/optimizer_states_dtype require "
                f"an Adam-family optimizer, got {name!r}")
        from deepspeed_tpu.runtime.bf16_optimizer import mp_adamw
        if name != C.ADAMW_OPTIMIZER and not params.get("adam_w_mode", True):
            wd = 0.0
        return mp_adamw(lr, weight_decay=wd, mu_dtype=mu_dtype,
                        nu_dtype=nu_dtype, master_dtype=master_dtype,
                        **_adam_args(params))
    if name in (C.ADAM_OPTIMIZER, C.FUSED_ADAM, C.CPU_ADAM):
        if params.get("adam_w_mode", True) and wd > 0:
            return optax.adamw(lr, weight_decay=wd, **_adam_args(params))
        return optax.adam(lr, **_adam_args(params))
    if name == C.ADAMW_OPTIMIZER:
        return optax.adamw(lr, weight_decay=wd, **_adam_args(params))
    if name in (C.LAMB_OPTIMIZER, C.FUSED_LAMB):
        return optax.lamb(lr, weight_decay=wd, **_adam_args(params))
    if name == C.SGD_OPTIMIZER:
        return optax.sgd(lr, momentum=params.get("momentum", 0.0),
                         nesterov=bool(params.get("nesterov", False)))
    if name == C.ADAGRAD_OPTIMIZER:
        return optax.adagrad(lr, eps=float(params.get("eps", 1e-10)))
    if name == C.LION_OPTIMIZER:
        betas = params.get("betas", (0.9, 0.99))
        return optax.lion(lr, b1=float(betas[0]), b2=float(betas[1]),
                          weight_decay=wd)
    if name == C.ONEBIT_ADAM_OPTIMIZER:
        # two-phase 1-bit Adam: exact Adam through freeze_step, then frozen
        # variance (runtime/fp16/onebit/adam.py).  The sign-compressed
        # exchange itself runs in the engine's shard_map gradient tier
        # (engine._qgz_grad_fn "onebit" epilogue) whenever the mesh has a
        # wide data/hpz axis — selecting this optimizer in a config gets
        # 1-bit wire traffic after freeze_step, like the reference.
        from deepspeed_tpu.runtime.fp16.onebit.adam import onebit_adam
        adam_args = _adam_args(params)
        return onebit_adam(
            learning_rate=lr,   # schedule-aware, like every other branch
            b1=adam_args["b1"], b2=adam_args["b2"], eps=adam_args["eps"],
            weight_decay=wd,
            freeze_step=int(params.get("freeze_step", 100)))
    if name == C.ZERO_ONE_ADAM_OPTIMIZER:
        # real 0/1 Adam (reference zoadam.py:14): exponential
        # variance-update intervals with dense sync only at those steps,
        # 1-bit compressed exchange otherwise (engine tier mirrors the
        # schedule on the wire)
        from deepspeed_tpu.runtime.fp16.onebit.zoadam import zero_one_adam
        adam_args = _adam_args(params)
        return zero_one_adam(
            learning_rate=lr,
            b1=adam_args["b1"], b2=adam_args["b2"], eps=adam_args["eps"],
            weight_decay=wd,
            var_freeze_step=int(params.get("var_freeze_step", 100000)),
            var_update_scaler=int(params.get("var_update_scaler", 16)),
            local_step_scaler=int(params.get("local_step_scaler", 32678)),
            local_step_clipper=int(params.get("local_step_clipper", 16)))
    if name == C.ONEBIT_LAMB_OPTIMIZER:
        # two-phase 1-bit LAMB (runtime/fp16/onebit/lamb.py): exact LAMB with
        # a trust-ratio EMA through freeze_step, then frozen variance +
        # factor-scaled frozen coefficient; compressed momentum exchange
        # engages under shard_map, same contract as OnebitAdam above.
        from deepspeed_tpu.runtime.fp16.onebit.lamb import onebit_lamb
        adam_args = _adam_args(params)
        return onebit_lamb(
            learning_rate=lr,
            b1=adam_args["b1"], b2=adam_args["b2"], eps=adam_args["eps"],
            weight_decay=wd,
            freeze_step=int(params.get("freeze_step", 100)),
            max_coeff=float(params.get("max_coeff", 10.0)),
            min_coeff=float(params.get("min_coeff", 0.01)),
            coeff_beta=float(params.get("coeff_beta", 0.9)),
            factor_max=float(params.get("factor_max", 4.0)),
            factor_min=float(params.get("factor_min", 0.5)),
            factor_threshold=float(params.get("factor_threshold", 0.1)))
    raise ValueError(f"Unknown optimizer {name!r} in DeepSpeed config")
