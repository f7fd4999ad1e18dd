"""DeepSpeedEngine — the training engine (reference: deepspeed/runtime/engine.py:174).

The reference wraps an ``nn.Module`` and orchestrates autograd hooks, bucketed
collectives, and side streams.  Here the whole train step —
micro-batch scan (gradient accumulation) → grad sharding constraint (ZeRO-2
reduce-scatter) → unscale/clip/overflow → sharded optimizer update (ZeRO-1) →
param re-materialisation (ZeRO-3 all-gather at next use) — is a single pure
function compiled under ``jax.jit`` with explicit NamedShardings.  XLA inserts
and overlaps the collectives the reference schedules by hand.

API parity:
- ``train_batch(data_iter)`` — full step incl. gradient accumulation (the
  PipelineEngine-style API, runtime/pipe/engine.py:297).
- ``forward(batch)`` / ``backward(loss)`` / ``step()`` — the micro-step API
  (engine.py:1722/:1863/:2061); gradients accumulate in a sharded device buffer
  and the update fires at the gradient-accumulation boundary exactly like the
  reference's ``is_gradient_accumulation_boundary`` (engine.py:1945).
- ``save_checkpoint`` / ``load_checkpoint`` with tag dirs + ``latest`` file
  (engine.py:2943/:2620).
"""
import collections
import functools
import os
import time
import weakref
from typing import Any, Dict, Optional

import numpy as np
import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.mesh import (MeshTopology, set_topology, SEQ_AXIS)
from deepspeed_tpu.runtime.config import DeepSpeedConfig, MeshConfig
from deepspeed_tpu.runtime import step_programs
from deepspeed_tpu.runtime.optimizers import build_optimizer
from deepspeed_tpu.runtime.lr_schedules import get_lr_schedule
from deepspeed_tpu.runtime.zero.policy import ZeroShardingPolicy
from deepspeed_tpu.runtime.fp16.loss_scaler import (
    create_loss_scaler, update_scale)
from deepspeed_tpu.runtime.step_programs import tree_cast as _tree_cast
from deepspeed_tpu.telemetry import tracing
from deepspeed_tpu.telemetry.memory import (
    attribute_params, fullest_device_bytes, get_memory_ledger,
    live_temporaries, memory_enabled, program_memory,
    set_memory_config_default)
from deepspeed_tpu.telemetry.tracing import (
    TRAIN_STEP_PROGRAM, register_program)
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import (
    SynchronizedWallClockTimer, ThroughputTimer, TRAIN_BATCH_TIMER)


def _np_fast_cast(x: np.ndarray, dtype):
    """Host-side cast for big numpy trees.  ml_dtypes' scalar astype loop
    runs at ~0.01 GB/s on one core — a 6.7B init would sit in the cast for
    the better part of an hour; the vectorised uint round-to-nearest-even
    below does bf16 at memory bandwidth."""
    dtype = jnp.dtype(dtype)
    if x.dtype == dtype or not np.issubdtype(x.dtype, np.floating):
        return x
    if dtype == jnp.bfloat16 and x.dtype == np.float32:
        b = x.view(np.uint32)
        rounded = b + np.uint32(0x7FFF) + ((b >> np.uint32(16))
                                           & np.uint32(1))
        out = (rounded >> np.uint32(16)).astype(np.uint16)
        # the rounding increment wraps for NaN/Inf payloads (a negative NaN
        # like 0xFFFF8001 would come out +0.0); pass non-finite bits through
        # truncated instead of rounded, forcing a quiet bit for NaNs whose
        # payload lives only in the truncated low 16 bits (else they'd
        # become Inf)
        nonfinite = (b & np.uint32(0x7F800000)) == np.uint32(0x7F800000)
        if nonfinite.any():
            trunc = (b >> np.uint32(16)).astype(np.uint16)
            is_nan = nonfinite & ((b & np.uint32(0x007FFFFF)) != 0)
            trunc = np.where(is_nan, trunc | np.uint16(0x0040), trunc)
            out = np.where(nonfinite, trunc, out)
        return out.view(dtype)
    return x.astype(dtype)


def _abstract(x, sharding=None):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _abstract_placed(x):
    """Shape, dtype and the sharding the array already has."""
    return _abstract(x, x.sharding)


class DeepSpeedEngine:
    def __init__(self,
                 config,
                 model,
                 optimizer=None,
                 model_parameters=None,
                 training_data=None,
                 lr_scheduler=None,
                 mesh=None,
                 collate_fn=None,
                 mpu=None,
                 dont_change_device: bool = False):
        raw = config
        if isinstance(raw, str):
            import json
            with open(raw) as f:
                raw_dict = json.load(f)
        else:
            raw_dict = dict(raw)
        # the tracer is armed before the first span, so that engine/init
        # is in the DS_TRACE file too; the set-up account
        # (telemetry/tracing.py) holds it either way
        from deepspeed_tpu.telemetry import configure_tracer
        configure_tracer((raw_dict.get("telemetry") or {}).get("trace"))
        with tracing.setup_span(tracing.SPAN_ENGINE_INIT) as init_span:
            self._init(init_span, raw_dict, model, optimizer,
                       model_parameters, training_data, lr_scheduler, mesh,
                       collate_fn, mpu)

    def _init(self, init_span, raw_dict, model, optimizer, model_parameters,
              training_data, lr_scheduler, mesh, collate_fn, mpu):
        # ---- topology first (batch math needs dp world size) ----------------
        mesh_cfg = MeshConfig(**raw_dict.get("mesh", {}))
        zo_raw = raw_dict.get("zero_optimization", {})
        hpz_size = int(zo_raw.get("zero_hpz_partition_size", 1) or 1)
        # MiCS (reference runtime/zero/mics.py:55): ALL zero state shards
        # within sub-groups of mics_shard_size, replicated across groups —
        # the same sub-axis mechanism as hpZ, applied to params+grads+opt
        mics_size = int(zo_raw.get("mics_shard_size", -1) or -1)
        if mics_size > 0:
            if hpz_size > 1 and hpz_size != mics_size:
                raise ValueError("mics_shard_size and zero_hpz_partition_size "
                                 "cannot differ")
            hpz_size = mics_size
        topo_kwargs = dict(
            data_parallel_size=mesh_cfg.data_parallel_size,
            model_parallel_size=mesh_cfg.model_parallel_size,
            pipe_parallel_size=mesh_cfg.pipe_parallel_size,
            sequence_parallel_size=mesh_cfg.sequence_parallel_size,
            sequence_parallel_impl=mesh_cfg.sequence_parallel_impl,
            expert_parallel_size=mesh_cfg.expert_parallel_size,
            hpz_partition_size=hpz_size)
        if mesh is not None:
            topo_kwargs["devices"] = list(mesh.devices.flat)
        self.topology = MeshTopology(**topo_kwargs)
        set_topology(self.topology)
        self.mesh = self.topology.mesh

        self._config = DeepSpeedConfig(raw_dict, mesh_topology=self.topology)
        self.model = model
        self.client_lr_scheduler = lr_scheduler
        self.training_dataloader = None
        self.collate_fn = collate_fn
        self.mpu = mpu
        # pluggable checkpoint backend (reference engine.py:897
        # _configure_checkpointing: torch vs async nebula engine) — the
        # async Orbax engine overlaps saves with subsequent train steps
        self.checkpoint_engine = None
        self._pending_ckpt = None
        # deterministic fault injection (resilience/faults.py): config
        # specs + DS_FAULTS env; a no-op injector when neither is armed
        from deepspeed_tpu.resilience.faults import resolve_injector
        self.fault_injector = resolve_injector(
            self._config.resilience_config.faults)

        # ---- precision -------------------------------------------------------
        if self._config.fp16.enabled:
            self.compute_dtype = jnp.float16
        elif self._config.bf16.enabled:
            self.compute_dtype = jnp.bfloat16
        else:
            self.compute_dtype = jnp.float32
        # bf16 state-dtype extensions (runtime/bf16_optimizer.py): masters
        # stored in compute dtype with Kahan compensation, and/or Adam
        # moments in bf16 — the HBM diet for the optimizer phase
        self._bf16_master = (
            self._config.bf16.enabled
            and jnp.dtype(self._config.bf16.master_weights_dtype)
            == jnp.bfloat16)
        if not self._config.bf16.enabled and jnp.dtype(
                self._config.bf16.master_weights_dtype) != jnp.float32:
            raise ValueError(
                "bf16.master_weights_dtype="
                f"{self._config.bf16.master_weights_dtype!r} requires "
                "bf16.enabled (Kahan-compensated bf16 masters pair with "
                "bf16 compute; remove the key or enable bf16)")
        self._opt_states_dtype = self._config.bf16.optimizer_states_dtype
        if self._opt_states_dtype is not None \
                and not self._config.bf16.enabled:
            # the byte-diet state dtypes are bf16-training features —
            # silently ignoring them under fp32/fp16 would misreport the
            # optimizer HBM the user configured
            raise ValueError(
                "bf16.optimizer_states_dtype="
                f"{self._opt_states_dtype!r} requires bf16.enabled "
                "(the reduced-precision optimizer states pair with bf16 "
                "compute; remove the key or enable bf16)")
        # reference data_types.grad_accum_dtype: gradient storage /
        # accumulation dtype (default fp32 master accumulation).
        # Whitelisted so a typo (or the unsupported fp16) fails loudly
        # instead of silently accumulating in fp32.
        _gad = self._config.data_types_config.grad_accum_dtype
        if _gad in (None, "fp32", "float32"):
            self.grad_dtype = jnp.float32
        elif _gad in ("bf16", "bfloat16"):
            if not self._config.bf16.enabled:
                raise ValueError(
                    f"data_types.grad_accum_dtype={_gad!r} requires "
                    "bf16.enabled: bf16 gradient accumulation exists to "
                    "halve the bf16 path's gradient-buffer bytes; under "
                    "fp32/fp16 it would silently degrade accumulation")
            self.grad_dtype = jnp.bfloat16
        else:
            raise ValueError(
                f"data_types.grad_accum_dtype={_gad!r}: supported values "
                "are 'fp32' and 'bf16' (fp16 accumulation is not offered "
                "— the fp16 path accumulates into fp32 masters, as the "
                "reference's default does)")

        # memory-ledger process default (ISSUE 14): installed BEFORE
        # the offload tiers construct their swappers, so an init-time
        # master/moment swap-out already honors telemetry.memory: false
        set_memory_config_default(self._config.telemetry_config.memory)

        # ---- ZeRO sharding policy -------------------------------------------
        init_span.phase(tracing.SPAN_INIT_SHARDINGS)
        zc = self._config.zero_config
        self.zero_policy = ZeroShardingPolicy(
            stage=zc.stage, topology=self.topology,
            param_persistence_threshold=(zc.param_persistence_threshold
                                         if zc.stage >= 3 else 0),
            hpz_partition_size=zc.zero_hpz_partition_size,
            mics_shard_size=zc.mics_shard_size,
            stacked_key=getattr(model, "blocks_key", None))
        off = zc.offload_optimizer
        self._offload_device = off.device if off is not None else "none"
        self._offload = self._offload_device in ("cpu", "nvme")
        # ZeRO-Infinity parameter offload (reference:
        # partitioned_param_swapper.py:36 + parameter_offload.py:201): block
        # params are stored in pinned host memory and streamed per layer into
        # the scan (models/model.py maybe_stream); pairs with the host
        # optimizer tier, which owns the fp32 masters anyway.
        offp = zc.offload_param
        self._offload_param_device = offp.device if offp is not None else "none"
        self._offload_param = self._offload_param_device in ("cpu", "nvme")
        if self._offload_param and not self._offload:
            raise ValueError(
                "offload_param requires offload_optimizer (the ZeRO-Infinity "
                "tier pairs parameter offload with the host optimizer)")
        # ZeRO-Infinity completion (ISSUE 17): offload_param.device=nvme
        # streams per-layer param shards through the SwapEngine — only a
        # K-layer working set is ever materialized; the weight pass runs
        # layer-sliced (runtime/zero/param_stream.py)
        self._param_nvme = (self._offload_param
                            and self._offload_param_device == "nvme")
        self._multi_device = len(list(self.mesh.devices.flat)) > 1
        if self._param_nvme:
            if self._multi_device:
                raise ValueError(
                    "offload_param.device=nvme streams layers on a single "
                    "host; shard the mesh down to one device or use "
                    "device=cpu for multi-device pinned-host streaming")
            if self._config.fp16.enabled:
                raise ValueError(
                    "offload_param.device=nvme does not support fp16 "
                    "dynamic loss scaling; use bf16 or fp32 compute")
        if self._offload_param and self._multi_device and zc.stage < 3:
            # multi-device ZeRO-Infinity (reference partitioned_param_swapper
            # .py:36 + parameter_offload.py:201): each device owns a
            # pinned-host shard of the layer stack and the per-layer stream
            # doubles as the stage-3 gather — the param shards must exist,
            # i.e. stage 3
            raise ValueError(
                "offload_param on a multi-device mesh requires ZeRO stage 3 "
                "(per-device pinned-host shards of the layer stack)")

        # ---- parameters ------------------------------------------------------
        # Parameters are *born sharded*: shapes come from eval_shape, the ZeRO
        # policy assigns storage shardings, and init is jitted with those
        # out_shardings — the zero.Init partition-at-creation semantics
        # (reference partition_parameters.py:707) with no post-hoc scatter.
        self._rng = jax.random.PRNGKey(self._config.seed)
        logical = getattr(model, "logical_specs", None)
        self._rng, init_rng = jax.random.split(self._rng)
        if model_parameters is None:
            shapes = jax.eval_shape(model.init, init_rng)
        else:
            shapes = jax.eval_shape(lambda: model_parameters)
        # with host offload, the device keeps only a compute-dtype working
        # copy; fp32 masters live in host DRAM (reference ZeRO-Offload shape).
        # Streamed tier: the pinned-host fp32 master IS the stored params
        # (the loss casts to compute dtype per streamed layer slice).
        opt_name = (self._config.optimizer_name or "adam").lower()
        self._use_streamed = (
            self._offload and self._offload_param
            and self._offload_device == "cpu"
            and not self._param_nvme
            and opt_name in ("adam", "adamw"))
        storage_dtype = (self.compute_dtype
                         if (self._offload or self._bf16_master)
                         else jnp.float32)
        shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, storage_dtype)
            if jnp.issubdtype(s.dtype, jnp.floating) else s, shapes)
        self.param_specs = self.zero_policy.param_specs(shapes, logical)
        bk_ = getattr(model, "blocks_key", "blocks")
        if zc.zero_quantized_gradients and (self._offload
                                            or self._offload_param):
            logger.warning(
                "zero_quantized_gradients engages only in train_batch's "
                "compiled step without optimizer/param offload; this "
                "config reduces gradients in full precision")
        # hpz locality under seq/model parallelism is handled by the mesh
        # factory (comm/mesh.py lays hpz groups tp-adjacent and verifies
        # process locality against the actual device ownership)
        self.param_shardings = self.zero_policy.shardings(self.param_specs)
        if self._offload_param:
            bk = getattr(model, "blocks_key", "blocks")
            if not (isinstance(self.param_shardings, dict)
                    and bk in self.param_shardings):
                raise ValueError(
                    f"offload_param needs a layer-stacked '{bk}' params "
                    f"subtree to stream (model.blocks_key)")
            # only matrix-shaped leaves offload (>=3 dims incl. the layer
            # stack): they are ~99.9% of block params, and libtpu cannot
            # compile dynamic-slice on packed bf16 2-D host buffers (biases /
            # norm scales stay device-resident, like the reference's
            # persistent small params).  The nvme tier skips pinned-host
            # entirely: blocks live in the SwapEngine, not on any device,
            # so the shardings for the blocks subtree are never used.
            if not self._param_nvme:
                self.param_shardings[bk] = jax.tree.map(
                    lambda sh, s: (sh.with_memory_kind("pinned_host")
                                   if len(s.shape) >= 3 else sh),
                    self.param_shardings[bk], shapes[bk])
            if not self._param_nvme and not getattr(
                    getattr(model, "config", None), "remat", False):
                logger.warning(
                    "offload_param without per-layer remat keeps every "
                    "streamed layer's device copy alive for backward — set "
                    "the model's remat=True to bound HBM at O(1 layer)")
        # device-side params tree: the nvme tier uploads only the nonblock
        # leaves (blocks stream from the ParamStore); everything else keeps
        # the full tree
        self._nonblock_shardings = (
            {k: v for k, v in self.param_shardings.items() if k != bk_}
            if self._param_nvme else self.param_shardings)
        init_span.phase(tracing.SPAN_INIT_PARAMS)
        if model_parameters is None:
            if self._offload_param:
                # host-side init: params are *stored* in pinned host memory,
                # so generate them on the host and move once — a device init
                # of e.g. 6.7B holds several multi-GB stacked fp32 leaves in
                # HBM at once and exhausts a 16 GB chip before the host copy
                # can begin
                n_params = model.meta.get("n_params", 0) or 0
                sliced = (getattr(model, "layer_init_fn", None) is not None
                          and getattr(model, "nonblock_init_fn", None)
                          is not None)
                on_tpu = list(self.mesh.devices.flat)[0].platform == "tpu"
                if n_params >= 1e8 and sliced and on_tpu \
                        and not self._param_nvme:
                    # per-layer device init, assembled IN PLACE in the
                    # pinned-host stacked buffers: the TPU RNG generates one
                    # layer's slice (sub-GB HBM) and a donated
                    # dynamic-update-slice writes it into the host-resident
                    # param storage — nothing crosses the host link, no
                    # single-core host RNG/cast bottleneck
                    bk = getattr(model, "blocks_key", "blocks")
                    bshapes = shapes[bk]
                    L = next(iter(jax.tree.leaves(bshapes))).shape[0]
                    blk_sh = self.param_shardings[bk]
                    blocks = jax.jit(
                        lambda: jax.tree.map(
                            lambda s: jnp.zeros(s.shape, storage_dtype),
                            bshapes),
                        out_shardings=blk_sh)()
                    write = jax.jit(
                        lambda b, r, i: jax.tree.map(
                            lambda bb, ss: bb.at[i].set(
                                ss.astype(storage_dtype)),
                            b, model.layer_init_fn(r, i)),
                        donate_argnums=(0,), out_shardings=blk_sh)
                    for i in range(L):
                        blocks = write(blocks, init_rng, i)
                    nb_sh = {k: v for k, v in self.param_shardings.items()
                             if k != bk}
                    params = jax.jit(
                        lambda r: _tree_cast(model.nonblock_init_fn(r),
                                             storage_dtype),
                        out_shardings=nb_sh)(init_rng)
                    params[bk] = blocks
                elif (n_params >= 1e9
                      and getattr(model, "numpy_init_fn", None) is not None):
                    # numpy PCG64 is ~3.5x jax-cpu threefry per core: worth
                    # the init-value difference only at billions of params
                    # (small models keep the rng-exact jax init for parity).
                    # Seeded from config so replicates differ (the fn's
                    # numpy rng cannot consume the jax key directly).
                    params = jax.tree.map(
                        lambda x: _np_fast_cast(x, storage_dtype),
                        model.numpy_init_fn(seed=self._config.seed))
                else:
                    with jax.default_device(jax.devices("cpu")[0]):
                        params = _tree_cast(model.init(init_rng),
                                            storage_dtype)
                if self._param_nvme:
                    # blocks never reach a device: stash the host stack for
                    # the ParamStore fill + host optimizer construction and
                    # upload only the nonblock leaves
                    self._nvme_blocks_host = jax.tree.map(
                        np.asarray, params[bk_])
                    params = {k: v for k, v in params.items() if k != bk_}
                params = jax.device_put(params, self._nonblock_shardings)
            else:
                params = jax.jit(
                    lambda r: _tree_cast(model.init(r), storage_dtype),
                    out_shardings=self.param_shardings)(init_rng)
        else:
            params = _tree_cast(model_parameters, storage_dtype)
            if self._param_nvme:
                params = jax.tree.map(
                    lambda a: np.asarray(jax.device_get(a)), params)
                self._nvme_blocks_host = params[bk_]
                params = {k: v for k, v in params.items() if k != bk_}
            params = jax.device_put(params, self._nonblock_shardings)
        self._param_shapes = shapes
        self._qgz_plan = "unbuilt"
        # nvme tier: grads/optimizer specs follow the device-side tree
        # (nonblock only), so the logical specs must be filtered to match
        logical_eff = ({k: v for k, v in logical.items() if k != bk_}
                       if self._param_nvme and isinstance(logical, dict)
                       else logical)
        self.grad_specs = self.zero_policy.grad_specs(params, logical_eff)
        self.grad_shardings = self.zero_policy.shardings(self.grad_specs)
        devices_flat = list(self.mesh.devices.flat)
        if self._offload_param and not self._param_nvme \
                and devices_flat[0].platform == "tpu":
            # block grads land in pinned host too: the backward scan DMAs each
            # layer's grad slice out as it is produced, so the full fp32 grad
            # never resides in HBM.  TPU only: the CPU runtime has no
            # implementation for host-placement annotations on jit outputs.
            # Same >=3-dim rule as the param storage above.
            bk = getattr(model, "blocks_key", "blocks")
            self.grad_shardings[bk] = jax.tree.map(
                lambda s, shp: (s.with_memory_kind("pinned_host")
                                if len(shp.shape) >= 3 else s),
                self.grad_shardings[bk], shapes[bk])
        opt_param_specs = self.zero_policy.optimizer_specs_for_params(
            params, logical_eff)

        # ---- optimizer -------------------------------------------------------
        init_span.phase(tracing.SPAN_INIT_OPTIMIZER)
        self.lr_schedule = None
        base_lr = float((self._config.optimizer_params or {}).get("lr", 1e-3))
        if self._config.scheduler_name:
            self.lr_schedule = get_lr_schedule(
                self._config.scheduler_name, self._config.scheduler_params,
                base_lr=base_lr)
        elif callable(lr_scheduler):
            self.lr_schedule = lr_scheduler
        self.base_lr = base_lr

        self.host_optimizer = None
        self.streamed_optimizer = None
        self.param_store = None          # nvme param tier (ISSUE 17)
        self.param_runner = None
        self._swap_engine = None
        if self._use_streamed:
            # TPU-native ZeRO-Infinity tier: optimizer state in pinned host
            # DRAM, update streamed on device — no Python/host round trips
            # (the C++ host-Adam path remains for NVMe and non-Adam configs)
            if getattr(model, "trainable_mask", None) is not None:
                raise NotImplementedError(
                    "trainable_mask (frozen params / LoRA) is not supported "
                    "with the offload optimizer tiers — adapter states are "
                    "small; drop offload_optimizer for LoRA runs")
            from deepspeed_tpu.runtime.zero.device_offload import \
                StreamedOptimizer
            self.streamed_optimizer = StreamedOptimizer(
                params, self.param_shardings,
                getattr(model, "blocks_key", "blocks"),
                self._config.optimizer_name, self._config.optimizer_params,
                gradient_clipping=self._config.gradient_clipping,
                lr_schedule=self.lr_schedule, mesh=self.mesh)
            self.optimizer = self.streamed_optimizer
            opt_state = ()
            self.opt_specs = ()
            self.opt_shardings = ()
        elif self._offload:
            if getattr(model, "trainable_mask", None) is not None:
                raise NotImplementedError(
                    "trainable_mask (frozen params / LoRA) is not supported "
                    "with the offload optimizer tiers — adapter states are "
                    "small; drop offload_optimizer for LoRA runs")
            from deepspeed_tpu.runtime.zero.offload import HostOffloadOptimizer
            nvme_swapper = None
            if self._offload_device == "nvme" or self._param_nvme:
                # ONE SwapEngine for every NVMe byte (ISSUE 17): param
                # shards and optimizer state share the read/write aio
                # rings and the queue-depth budget, attributed to separate
                # ledger owner rows (params_nvme / optim_nvme).  The
                # hand-rolled AsyncTensorSwapper remains only as a
                # standalone utility; the engine path rides the
                # SwapTensorClient adapter.
                import tempfile
                from deepspeed_tpu.offload import SwapEngine, SwapTensorClient
                offo_cfg = self._config.zero_config.offload_optimizer
                offp_cfg = self._config.zero_config.offload_param
                swap_dir = ((offo_cfg.nvme_path if offo_cfg is not None
                             else None)
                            or (offp_cfg.nvme_path if offp_cfg is not None
                                else None)
                            or tempfile.mkdtemp(prefix="ds_nvme_"))
                aio = self._config.aio_config
                self._swap_engine = SwapEngine(
                    nvme_dir=os.path.join(str(swap_dir),
                                          "zero_stage_offload"),
                    owner=("params_nvme" if self._param_nvme
                           else "optim_nvme"),
                    aio_threads=aio.thread_count,
                    queue_depth=aio.queue_depth,
                    injector=self.fault_injector,
                    integrity=self._config.resilience_config.offload)
                if self._offload_device == "nvme":
                    nvme_swapper = SwapTensorClient(self._swap_engine,
                                                    owner="optim_nvme")
            opt_params = params
            if self._param_nvme:
                # per-layer keyed optimizer tree: dict-sorted flatten puts
                # each layer's leaves contiguously, so the optimizer's
                # pipelined prefetch loop walks the step layer by layer
                blocks_host = self._nvme_blocks_host
                self._num_layers = int(
                    jax.tree.leaves(blocks_host)[0].shape[0])
                layer_trees = {
                    f"L{i:04d}": jax.tree.map(
                        lambda a, i=i: np.asarray(a[i]), blocks_host)
                    for i in range(self._num_layers)}
                opt_params = dict(params)
                opt_params[bk_] = layer_trees
            self.host_optimizer = HostOffloadOptimizer(
                opt_params, self._config.optimizer_name,
                self._config.optimizer_params,
                gradient_clipping=self._config.gradient_clipping,
                lr_schedule=self.lr_schedule,
                nvme_swapper=nvme_swapper,
                masters_on_nvme=self._offload_device == "nvme")
            self.optimizer = self.host_optimizer
            opt_state = ()
            self.opt_specs = ()
            self.opt_shardings = ()
            if self._param_nvme:
                from deepspeed_tpu.offload import ParamStore
                from deepspeed_tpu.runtime.zero.param_stream import (
                    StreamedParamRunner, uses_default_lm_loss)
                if not uses_default_lm_loss(model):
                    raise ValueError(
                        "offload_param.device=nvme requires the default "
                        "causal-LM loss (the streamed head VJP reproduces "
                        "it exactly); custom loss_fn models must use "
                        "device=cpu")
                resident = int(os.environ.get("DS_PARAM_RESIDENT_LAYERS")
                               or offp_cfg.resident_layers)
                self.param_store = ParamStore(
                    self._swap_engine, self._num_layers,
                    resident_layers=resident,
                    injector=self.fault_injector,
                    reload_fn=self._reload_layer)
                for i in range(self._num_layers):
                    self.param_store.put_layer(i, layer_trees[f"L{i:04d}"])
                self.param_store.flush()
                self._nvme_blocks_host = None    # full stack goes cold
                self.param_runner = StreamedParamRunner(
                    model, self._num_layers, self.param_store)
        else:
            if optimizer is not None and isinstance(
                    optimizer, optax.GradientTransformation):
                if self._bf16_master or self._opt_states_dtype:
                    # a plain optax transform has no Kahan compensation —
                    # bf16 masters without it silently DROP sub-ulp
                    # updates (the failure the feature exists to prevent)
                    raise ValueError(
                        "bf16.master_weights_dtype/optimizer_states_dtype "
                        "cannot be combined with a user-provided optimizer "
                        "instance; configure an Adam-family optimizer by "
                        "name instead (the engine builds the Kahan-"
                        "compensated transform)")
                inner = optimizer
            else:
                inner = build_optimizer(
                    self._config.optimizer_name,
                    self._config.optimizer_params,
                    lr_schedule=self.lr_schedule,
                    mu_dtype=self._opt_states_dtype,
                    nu_dtype=self._opt_states_dtype,
                    master_dtype=("bfloat16" if self._bf16_master
                                  else "float32"))
            mask = getattr(model, "trainable_mask", None)
            if mask is not None:
                # frozen leaves (reference: requires_grad=False params —
                # LoRA bases, frozen embeddings): the inner transform never
                # sees them (optax.masked stores MaskedNode, so no moment
                # memory) and their updates are forced to zero
                inv = jax.tree.map(lambda m: not m, mask)
                inner = optax.chain(
                    optax.masked(inner, mask),
                    optax.masked(optax.set_to_zero(), inv))
                opt_param_specs = jax.tree.map(
                    lambda m, spec: spec if m else optax.MaskedNode(),
                    mask, opt_param_specs,
                    is_leaf=lambda x: isinstance(x, bool))
            chain = []
            if self._config.gradient_clipping > 0:
                chain.append(
                    optax.clip_by_global_norm(self._config.gradient_clipping))
            chain.append(inner)
            self.optimizer = optax.chain(*chain) if len(chain) > 1 else inner

            opt_state = jax.eval_shape(self.optimizer.init, params)
            self.opt_specs = optax.tree_map_params(
                self.optimizer,
                lambda _, spec: spec,
                opt_state, opt_param_specs,
                transform_non_params=lambda _: P())
            # param-shaped specs only apply to param-shaped state; optimizer
            # states may carry per-leaf scalars in params-shaped subtrees
            # (e.g. OnebitLamb's coeff_freeze) — replicate anything whose
            # rank can't carry the param's spec
            treedef = jax.tree.structure(opt_state)
            spec_leaves = treedef.flatten_up_to(self.opt_specs)
            self.opt_specs = jax.tree.unflatten(treedef, [
                spec if len(spec) <= leaf.ndim else P()
                for leaf, spec in zip(jax.tree.leaves(opt_state),
                                      spec_leaves)])
            self.opt_shardings = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), self.opt_specs,
                is_leaf=lambda x: isinstance(x, P))
            with self.mesh:
                opt_state = jax.jit(self.optimizer.init,
                                    out_shardings=self.opt_shardings)(params)

        # ---- loss scaling ----------------------------------------------------
        init_span.phase(None)
        f = self._config.fp16
        scaler, self.scaler_config = create_loss_scaler(
            enabled=f.enabled, loss_scale=f.loss_scale,
            initial_scale_power=f.initial_scale_power,
            loss_scale_window=f.loss_scale_window, hysteresis=f.hysteresis,
            min_loss_scale=f.min_loss_scale,
            consecutive_hysteresis=f.consecutive_hysteresis)

        self.state_shardings = {
            "params": self._nonblock_shardings,
            "opt_state": self.opt_shardings,
            "step": NamedSharding(self.mesh, P()),
            "scaler": jax.tree.map(lambda _: NamedSharding(self.mesh, P()),
                                   scaler),
        }
        # the counter and the scaler are born where every step returns them:
        # left as host scalars, the second call's arguments would differ
        # from the first's in their placement alone, and jit would build
        # the whole step a second time for it
        self.state: Dict[str, Any] = {
            "params": params,
            "opt_state": opt_state,
            "step": jax.device_put(jnp.int32(0),
                                   self.state_shardings["step"]),
            "scaler": jax.device_put(scaler, self.state_shardings["scaler"]),
        }

        # 1-bit optimizer error-feedback buffers (reference zoadam.py /
        # onebit adam worker_error+server_error): per-device residuals of
        # the sign-compressed exchange, stored as [n_manual, ...] arrays
        # sharded over the manual axes so each device owns its own slice
        plan = self._get_qgz_plan()
        if plan is not None and plan["onebit"] is not None:
            n_m, manual = plan["n_manual"], plan["manual"]
            err_shapes, srv_shapes = [], []
            for ep, shp in zip(plan["epilogue"], plan["shapes"]):
                if ep[0] == "onebit":
                    size = 1
                    for s in shp:
                        size *= s
                    err_shapes.append((n_m,) + tuple(shp))
                    # size-1 placeholder when the leaf has no server stage
                    # (orbax cannot checkpoint zero-size arrays)
                    srv_shapes.append((n_m, size // n_m)
                                      if ep[2] else (n_m, 1))
                else:
                    err_shapes.append((n_m, 1))
                    srv_shapes.append((n_m, 1))
            tdef = plan["treedef"]
            ob_shard = NamedSharding(self.mesh, P(manual))
            ob_shardings = {
                "error": jax.tree.unflatten(tdef, [ob_shard] * len(err_shapes)),
                "server": jax.tree.unflatten(tdef, [ob_shard] * len(srv_shapes)),
                "var_interval": NamedSharding(self.mesh, P()),
                "var_counter": NamedSharding(self.mesh, P()),
            }
            self.state["onebit"] = jax.jit(
                lambda: {
                    "error": jax.tree.unflatten(tdef, [
                        jnp.zeros(s, jnp.float32) for s in err_shapes]),
                    "server": jax.tree.unflatten(tdef, [
                        jnp.zeros(s, jnp.float32) for s in srv_shapes]),
                    "var_interval": jnp.ones((), jnp.int32),
                    "var_counter": jnp.zeros((), jnp.int32),
                }, out_shardings=ob_shardings)()
            self.state_shardings["onebit"] = ob_shardings

        # ---- batch sharding --------------------------------------------------
        dp_axes = self.topology.data_parallel_axes
        self.batch_spec = P(dp_axes, SEQ_AXIS)
        self.batch_sharding = NamedSharding(self.mesh, self.batch_spec)

        # ---- compiled functions ---------------------------------------------
        self._compiled: Dict[str, Any] = {}
        self._micro_grads = None      # forward/backward/step path accumulator
        self._micro_count = 0
        self._last_loss = None
        self._pending_grads = None    # grads computed by forward(), applied by backward()
        self._data_iterator = None    # persistent iterator over training_dataloader
        self._client_iter_src = None  # iterable passed to train_batch(data_iter=...)
        self._client_iter = None      # its cached iterator

        # ---- bookkeeping -----------------------------------------------------
        self.global_steps = 0
        self.global_samples = 0
        self._skipped_steps = 0
        self._pending_overflow = []   # unresolved device-side overflow flags
        # (step, {name: device scalar}) of Model.loss_with_counts_fn, oldest
        # first, until they are ready; and their sums so far
        self._pending_counts = collections.deque()
        self._step_counts = {}
        # what came beside them and is not a count of what the loss left
        # out: the steps' load (step_load())
        self._step_load = {"steps": 0, "totals": {},
                           "last": collections.deque(maxlen=64)}
        self.micro_steps = 0
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_batch_size(),
            steps_per_output=self._config.steps_per_print,
            sync_every_step=self._config.wall_clock_breakdown)
        # tokens dispatched since the timer last waited for the device
        self._tokens_since_sync = 0
        self.monitor = self._build_monitor()
        self.last_metrics: Dict[str, float] = {}

        # ---- unified telemetry (ISSUE 4): registry + tracer + MFU ------------
        from deepspeed_tpu.telemetry import (configure_tracer, get_registry,
                                             peak_flops_per_device)
        tcfg = self._config.telemetry_config
        self.telemetry_registry = get_registry()
        self.tracer = configure_tracer(tcfg.trace)
        self.timers.attach_tracer(self.tracer)
        # precedence: DS_PEAK_FLOPS env > telemetry.peak_flops config >
        # device-kind table (None on CPU — MFU gauge simply absent)
        from deepspeed_tpu.telemetry import PEAK_FLOPS_ENV
        if os.environ.get(PEAK_FLOPS_ENV, "").strip():
            peak = peak_flops_per_device()
        else:
            peak = tcfg.peak_flops or peak_flops_per_device()
        #: aggregate peak over this process's local devices (per-host MFU)
        self._peak_flops = (peak * len(jax.local_devices())
                            if peak else None)
        # black-box layer (ISSUE 7): flight recorder (train-step events
        # + the substrate post-mortem bundles drain) and the rolling
        # step-latency anomaly detector
        from deepspeed_tpu.telemetry import (AnomalyMonitor,
                                             configure_flight_recorder)
        from deepspeed_tpu.telemetry.flight_recorder import DEFAULT_CAPACITY
        # a default-valued config must not replace (and empty) a ring
        # another subsystem in this process already sized explicitly —
        # only an explicit non-default capacity rebuilds the global
        self.flightrec = configure_flight_recorder(
            None if tcfg.flightrec_events == DEFAULT_CAPACITY
            else tcfg.flightrec_events)
        self.anomaly = AnomalyMonitor(
            registry=self.telemetry_registry, flightrec=self.flightrec,
            window=tcfg.anomaly_window, threshold=tcfg.anomaly_threshold)
        if self.param_store is not None:
            # constructed before the recorder existed: late-bind so
            # param/swap_fail + param/degraded events land in the ring
            self.param_store.flightrec = self.flightrec
        self.metrics_server = None
        if tcfg.metrics_port is not None and jax.process_index() == 0:
            from deepspeed_tpu.telemetry import MetricsServer
            self.metrics_server = MetricsServer(
                self.telemetry_registry,
                port=tcfg.metrics_port).start()
        # memory observatory (ISSUE 14): attribute the engine's big
        # owners into the tiered ledger once (the state's byte sizes
        # never change); per-step publication + the HBM-fraction
        # anomaly feed ride _record_step_telemetry.  The device tier is
        # ONE chip's: each owner's shards on the fullest local device,
        # counted where the state has just been placed (arithmetic over
        # shard shapes, no device read); the step's gradients and
        # workspace join it when somebody asks for the step's account
        # (_maybe_register_program_map).
        self._mem_on = tcfg.enabled and memory_enabled(tcfg.memory)
        self._program_map_registered = False
        self._state_bytes = None
        if self._mem_on:
            try:
                from deepspeed_tpu.telemetry.iostat import get_iostat
                self._state_bytes = fullest_device_bytes(
                    params=self.state["params"],
                    optimizer=self.state["opt_state"],
                    state_other={k: v for k, v in self.state.items()
                                 if k not in ("params", "opt_state")})
                # swap I/O observations land in this engine's registry
                # and feed its anomaly detector (a collapsing NVMe read
                # rate raises anomaly/mem_swap_read before the offload
                # pipeline stalls a step)
                get_iostat().attach(registry=self.telemetry_registry,
                                    anomaly=self.anomaly)
                led = get_memory_ledger()
                attribute_params(led, self.state["params"],
                                 nbytes=self._state_bytes["params"])
                for owner in ("optimizer", "state_other"):
                    if self._state_bytes[owner]:
                        led.set_bytes("device", owner,
                                      self._state_bytes[owner])
                if self.host_optimizer is not None:
                    led.set_bytes("host", "optimizer",
                                  self.host_optimizer.host_dram_bytes,
                                  masters_on_nvme=self.host_optimizer
                                  .masters_on_nvme)
                if self.streamed_optimizer is not None:
                    # pinned-host Adam state: fp32 master + m + v
                    numel = sum(int(l.size) for l in
                                jax.tree.leaves(self.state["params"]))
                    led.set_bytes("host", "optimizer", 3 * 4 * numel,
                                  pinned=True)
            except Exception as e:  # accounting must never block init
                logger.debug(f"memory ledger: attribution failed ({e})")
                self._mem_on = False

        # numerics observatory (ISSUE 15): per-leaf-group grad stats
        # computed inside the fused step and banked lazily beside the
        # overflow flag (NumericsState), periodic determinism
        # fingerprints, and NaN provenance.  The leaf grouping is built
        # once from the params template; a structure the grouping can't
        # walk disables the tier rather than blocking init.
        from deepspeed_tpu.telemetry.numerics import (
            configure_numerics, leaf_groups, numerics_enabled,
            resolve_fingerprint_interval)
        ncfg = tcfg.numerics
        self._num_on = tcfg.enabled and numerics_enabled(ncfg.enabled)
        self._num_groups = None
        self._num_leaf_group = None
        self._last_save_dir = None
        self.numerics = None
        self._fp_interval = 0
        if self._num_on:
            try:
                names, index = leaf_groups(self.state["params"],
                                           depth=ncfg.group_depth)
                self._num_groups, self._num_leaf_group = names, index
                self._fp_interval = resolve_fingerprint_interval(
                    ncfg.fingerprint_interval)
                self.numerics = configure_numerics(
                    names, history=ncfg.history,
                    registry=self.telemetry_registry,
                    anomaly=self.anomaly, flightrec=self.flightrec,
                    on_nonfinite=self._numerics_postmortem)
            except Exception as e:  # observability must never block init
                logger.debug(f"numerics: leaf grouping failed ({e})")
                self._num_on = False

        self._ltd_keep = None
        self._last_seq_len = 0
        # ---- aux subsystems (reference engine call sites) --------------------
        # flops profiler (reference engine.py:1734 flops_profiler_profile_step)
        fpc = self._config.flops_profiler_config
        self.flops_profiler = None
        if fpc.enabled:
            from deepspeed_tpu.profiling.flops_profiler.profiler import \
                FlopsProfiler
            self.flops_profiler = FlopsProfiler(model, fpc)
            if not getattr(model, "flops_per_token", None):
                logger.warning(
                    "flops_profiler: model.flops_per_token is unset — the "
                    "profile will report 0 FLOPS")
        # comms logger wiring (reference comm.configure(comms_logger=...));
        # the registry hookup makes the per-op totals live labeled
        # counters on /metrics (ISSUE 19 satellite), not just summary
        # events at log_comms_summary time
        if self._config.comms_config.enabled:
            from deepspeed_tpu import comm as _comm
            from deepspeed_tpu.utils.comms_logging import CommsLogger
            _comm.configure(comms_logger=CommsLogger(
                self._config.comms_config,
                registry=self.telemetry_registry))
        # comm observatory (ISSUE 19 tentpole): the process-wide
        # CommStat feeds comm/* histograms, the anomaly/comm_* MAD
        # detectors, the per-step overlap window, and /debug/comm
        self._commstat = None
        ccfg = self._config.telemetry_config.comm
        from deepspeed_tpu.telemetry.commstat import (
            commstat_enabled, get_commstat)
        if commstat_enabled(ccfg.enabled):
            self._commstat = get_commstat()
            self._commstat.attach(registry=self.telemetry_registry,
                                  anomaly=self.anomaly,
                                  flightrec=self.flightrec,
                                  injector=self.fault_injector)
            self._comm_step_window = bool(ccfg.step_window)
        else:
            self._comm_step_window = False
        # compression-aware training (reference engine.py:2044 drives the
        # compression scheduler every step; here the compiled step applies
        # the plans with traced schedule gates — see compression/compress.py)
        self._compression_plans = None
        self._aq = None
        cc = self._config.compression_config
        if cc:
            from deepspeed_tpu.compression import (
                parse_compression_config, parse_activation_quantization)
            plans = parse_compression_config(cc)
            self._compression_plans = plans or None
            self._aq = parse_activation_quantization(cc)
            if self._compression_plans and (self._offload
                                            or self._offload_param):
                logger.warning(
                    "compression_training: weight plans are not applied in "
                    "the offload execution tiers (compressing would gather "
                    "the streamed params); activation quantization still "
                    "applies")
                self._compression_plans = None
            if (cc.get("layer_reduction", {}) or {}).get("enabled"):
                logger.warning(
                    "layer_reduction is an offline transform — call "
                    "deepspeed_tpu.compression.apply_layer_reduction on "
                    "the params BEFORE initialize(); ignoring here")
        # sanitizer tier (SURVEY §5: race detection / sanitizers)
        dbg = self._config.debug_config
        self._sanitize_gradients = dbg.sanitize_gradients
        if dbg.debug_nans:
            jax.config.update("jax_debug_nans", True)
            logger.warning("debug.debug_nans: jax_debug_nans enabled — "
                           "faulting primitives re-run eagerly; expect "
                           "slower failing steps")
        # legacy curriculum learning (reference engine.py:1761 seqlen kwarg)
        self.curriculum_scheduler = None
        cl = self._config.curriculum_learning
        if cl.enabled:
            from deepspeed_tpu.runtime.data_pipeline.curriculum_scheduler \
                import CurriculumScheduler
            self.curriculum_scheduler = CurriculumScheduler(
                self._config.curriculum_params_legacy)
            step = int(cl.schedule_config.get("difficulty_step", 8) or 8)
            if (cl.curriculum_type == "seqlen"
                    and not getattr(cl, "seqlen_bucket", 0) and step < 8):
                logger.warning(
                    f"curriculum_learning: difficulty_step={step} compiles "
                    "a fresh train step per distinct sequence length on "
                    "TPU; set curriculum_learning.seqlen_bucket (e.g. 64) "
                    "to bound recompiles")
        # progressive layer drop (reference engine.py:1755 PLD theta kwarg)
        self.progressive_layer_drop = None
        pld = self._config.pld_config
        if pld.enabled:
            from deepspeed_tpu.runtime.progressive_layer_drop import \
                ProgressiveLayerDrop
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=pld.theta, gamma=pld.gamma)
            # theta reaches the models as a traced batch scalar
            # ("pld_theta", injected in train_batch/forward); in-tree layer
            # scans gate each block on it (models/model.py scan_blocks)
            if not self.model.meta.get("supports_pld"):
                logger.warning(
                    "progressive_layer_drop: this model does not declare "
                    "supports_pld — the injected pld_theta batch scalar "
                    "will be ignored and PLD is a no-op")
        # random-LTD token-drop schedule (reference data_routing; models
        # consume the keep count through the ltd scope in their layer scan)
        self.random_ltd_scheduler = None
        de = self._config.data_efficiency_config or {}
        ltd = de.get("data_routing", {}).get("random_ltd", {})
        if ltd.get("enabled"):
            from deepspeed_tpu.runtime.data_pipeline.random_ltd import \
                RandomLTDScheduler
            sched = ltd.get("random_ltd_schedule", {})
            sched_cfg = sched.get("schedule_config", {})
            self.random_ltd_scheduler = RandomLTDScheduler(
                total_layer_token_steps=int(
                    sched_cfg.get("require_steps",
                                  sched.get("require_steps", 1000))),
                min_tokens=int(sched.get("min_value", 128)),
                max_tokens=int(sched.get("max_value", 2048)),
                step_size=int(sched_cfg.get("seq_per_step", 16)))
            if not getattr(model, "meta", {}).get("supports_random_ltd"):
                logger.warning(
                    "random_ltd: this model does not read the LTD keep scope "
                    "(models/gpt2.py, llama.py do) — token dropping will be "
                    "a no-op")

        # what the traced step programs read of this engine
        # (runtime/step_programs.py), fixed from here on
        self._step_ctx = step_programs.StepContext(
            model=self.model, optimizer=self.optimizer,
            zero_policy=self.zero_policy, grad_specs=self.grad_specs,
            grad_dtype=self.grad_dtype, compute_dtype=self.compute_dtype,
            fp16=self._config.fp16.enabled,
            scaler_config=self.scaler_config,
            gas=self.gradient_accumulation_steps(),
            compression_plans=self._compression_plans,
            use_streamed=self._use_streamed,
            num_groups=self._num_groups,
            num_leaf_group=self._num_leaf_group,
            pipe_cfg=self._config._param_dict.get("pipeline", {}) or {})

        if training_data is not None:
            from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader
            self.training_dataloader = DeepSpeedDataLoader(
                training_data,
                batch_size=self.train_micro_batch_size_per_gpu() *
                self.topology.dp_world_size,
                collate_fn=collate_fn)

        log_dist(
            f"DeepSpeedEngine: ZeRO stage {zc.stage}, dtype {self.compute_dtype}, "
            f"mesh {dict(self.mesh.shape)}, "
            f"batch {self.train_batch_size()} = {self.train_micro_batch_size_per_gpu()}"
            f"×{self.gradient_accumulation_steps()}×{self.topology.dp_world_size}",
            ranks=[0])

    # ------------------------------------------------------------------ config api
    def train_batch_size(self) -> int:
        return self._config.train_batch_size

    def train_micro_batch_size_per_gpu(self) -> int:
        return self._config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self) -> int:
        return self._config.gradient_accumulation_steps

    def zero_optimization_stage(self) -> int:
        return self._config.zero_config.stage

    def get_lr(self):
        step = int(self.state["step"])
        if self.lr_schedule is not None:
            return [float(self.lr_schedule(jnp.int32(step)))]
        return [self.base_lr]

    def get_type(self):
        """(reference engine.py get_type)"""
        return [self._config.optimizer_name or "adam"]

    def get_mom(self):
        """(reference engine.py:2249) momentum for SGD-family optimizers,
        betas otherwise."""
        params = self._config.optimizer_params or {}
        name = (self._config.optimizer_name or "adam").lower()
        if name in ("sgd", "rmsprop"):
            return [float(params.get("momentum", 0.0))]
        betas = params.get("betas", (0.9, 0.999))
        return [tuple(float(b) for b in betas)]

    def get_pld_theta(self):
        """(reference engine.py get_pld_theta)"""
        if self.progressive_layer_drop is not None:
            return float(self.progressive_layer_drop.get_theta())
        return None

    @property
    def lr_scheduler(self):
        return self.lr_schedule

    @property
    def loss_scale(self) -> float:
        return float(self.state["scaler"].cur_scale)

    @property
    def config(self) -> DeepSpeedConfig:
        return self._config

    def is_gradient_accumulation_boundary(self) -> bool:
        return (self.micro_steps + 1) % self.gradient_accumulation_steps() == 0

    # skipped_steps is lazily resolved: per-step overflow flags stay on device
    # (fetching each would cost a host round trip per step) and are summed in
    # one transfer when the counter is actually read
    @property
    def skipped_steps(self) -> int:
        self._resolve_overflows()
        return self._skipped_steps

    @skipped_steps.setter
    def skipped_steps(self, value: int):
        self._pending_overflow = []
        self._skipped_steps = int(value)

    def _resolve_overflows(self):
        if self._pending_overflow:
            flags = jax.device_get(self._pending_overflow)
            self._skipped_steps += int(np.sum(np.asarray(flags)))
            self._pending_overflow = []
        # the numerics bank resolves at the same boundaries the
        # overflow bank does (report boundaries / counter access) —
        # detection is lazy by construction, never per-step
        if self.numerics is not None:
            try:
                self.numerics.resolve()
            except Exception as e:
                logger.debug(f"numerics: resolve failed ({e})")

    def step_counts(self) -> dict:
        """{name: sum over the steps run} of the counts the model's loss
        comes with (``Model.loss_with_counts_fn``; ``{}`` without).  Waits
        for the steps in flight."""
        self._resolve_step_counts(wait=True)
        return dict(self._step_counts)

    def step_load(self) -> dict:
        """What the data did to the steps run, from the sums that leave
        the fused step beside the counts — what the router did
        (``moe/layer.py step_load``: every name a sum over a step's expert
        layer-calls, micro-batches and chips), where a looped model's
        tokens leave (``models/ouro.py STEP_LOAD``: the exit masses); a
        fact no layer of the step made reads 0 and is left out:
        ``{"steps": resolved steps, "totals": {name: sum over them},
        "last": [{name: value} a step, newest last, at most 64]}``.  The
        last resolved step's are the registry's gauges of those names.
        Never a count: nothing here warns.  Waits for the steps in
        flight."""
        self._resolve_step_counts(wait=True)
        load = self._step_load
        return {"steps": load["steps"], "totals": dict(load["totals"]),
                "last": [dict(step) for step in load["last"]]}

    def _resolve_step_counts(self, wait: bool):
        """Add up the banked counts — only those whose step has ended
        unless ``wait`` — and warn of each that is not zero: the model
        left that much out of the step's loss.  A name the model does not
        state among its ``step_counts`` is the step's load."""
        said = self.model.meta.get("step_counts", {})
        while self._pending_counts:
            step, counts = self._pending_counts[0]
            if not wait and not all(c.is_ready() for c in counts.values()):
                return
            self._pending_counts.popleft()
            counts = {n: int(v) for n, v in jax.device_get(counts).items()}
            load = {n: v for n, v in counts.items() if n not in said and v}
            if load:
                self._step_load["steps"] += 1
                self._step_load["last"].append(load)
            totals = self._step_load["totals"]
            for name, value in load.items():
                totals[name] = totals.get(name, 0) + value
                self.telemetry_registry.set_gauge(name, float(value))
            for name in said:
                value = counts.get(name, 0)
                self._step_counts[name] = \
                    self._step_counts.get(name, 0) + value
                if value:
                    self.telemetry_registry.inc("train/step_counts",
                                                float(value), count=name)
                    logger.warning(f"train step {step}: {name} = {value}"
                                   f" ({said[name]})")

    def _build_monitor(self):
        try:
            from deepspeed_tpu.monitor.monitor import MonitorMaster
            return MonitorMaster(self._config.monitor_config)
        except Exception:
            return None

    # ------------------------------------------------------------------ train step
    @staticmethod
    def _restrict_spec(spec, keep) -> P:
        """Drop every axis not in ``keep`` from a PartitionSpec."""
        entries = []
        for e in tuple(spec):
            if e is None:
                entries.append(None)
                continue
            axes = e if isinstance(e, (tuple, list)) else (e,)
            kept = tuple(a for a in axes if a in keep)
            entries.append(kept if kept else None)
        while entries and entries[-1] is None:
            entries.pop()
        return P(*entries)

    @staticmethod
    def _manual_dims(spec, ndim, manual):
        """[(dim, axes)] for every dim of ``spec`` carrying manual axes."""
        out = []
        for d, e in enumerate(tuple(spec)[:ndim]):
            if e is None:
                continue
            axes = e if isinstance(e, (tuple, list)) else (e,)
            hit = tuple(a for a in axes if a in manual)
            if hit:
                out.append((d, hit))
        return out

    def _get_qgz_plan(self):
        """Static plan for the generalized qgZ / sparse-gradient tier
        (reference ZeRO++ qgZ, docs/_tutorials/zeropp.md:15 + stage3.py:84
        ctor args): a partially-manual shard_map — manual over the wide
        ``data``/``hpz`` axes, auto over expert/seq/model/pipe — where

        - stage-3 zero-sharded params enter as shards and all-gather at
          point of use (per layer inside the scan via the model's
          ``maybe_stream`` hook; int8 wire when qwZ is also on), with a
          custom VJP that reduce-scatters the cotangent as int8 chunks —
          gradients accumulate *sharded*;
        - replicated-over-manual leaves reduce once per step in a
          post-accumulation epilogue: touched-rows exchange for declared
          sparse embeddings, hierarchical int8 reduce-scatter for dense
          leaves, exact psum for tiny/ragged ones.

        Reductions over the auto axes (expert/seq/model) stay XLA-inserted
        full-precision collectives.  Returns None when the tier cannot
        engage (no wide data/hpz axis, offload tiers, nothing enabled)."""
        if self._qgz_plan != "unbuilt":
            return self._qgz_plan
        self._qgz_plan = self._build_qgz_plan()
        return self._qgz_plan

    #: optimizer names whose compressed exchange rides the shard_map tier
    _ONEBIT_OPTS = ("onebitadam", "onebitlamb", "zerooneadam")

    def _build_qgz_plan(self):
        from deepspeed_tpu.comm.mesh import DATA_AXIS, HPZ_AXIS
        zc = self._config.zero_config
        declared = self.model.meta.get("sparse_grad_params", {})
        if not isinstance(declared, dict):     # list shorthand -> input_ids
            declared = {k: "input_ids" for k in declared}
        sparse_leaves = (dict(declared)
                         if self._config.sparse_gradients_enabled else {})
        if self._config.sparse_gradients_enabled and not sparse_leaves:
            logger.warning(
                "sparse_gradients: model declares no sparse_grad_params "
                "(tied embeddings get dense head contributions); ignoring")
        qgz = bool(zc.zero_quantized_gradients)
        opt_name = (self._config.optimizer_name or "").lower()
        onebit_kind = opt_name if opt_name in self._ONEBIT_OPTS else None
        if onebit_kind and zc.stage >= 3:
            # reference 1-bit optimizers pair with ZeRO stage <= 1; the
            # stage-3 sharded-param formulation has its own quantized wire
            # (qgZ wrappers) — warn and reduce this config's grads densely
            logger.warning(
                "1-bit optimizers engage their compressed exchange at ZeRO "
                "stages 0-2; stage 3 reduces gradients in full precision "
                "(enable zero_quantized_gradients for an int8 stage-3 wire)")
            onebit_kind = None
        if not qgz and not sparse_leaves and not onebit_kind:
            return None
        if self._offload or self._offload_param:
            return None                      # warned at init (both tiers)
        if self.model.meta.get("pipeline"):
            # scanned/chunked GPipe is plain auto-SPMD over the pipe axis,
            # which stays AUTO inside the tier's partially-manual shard_map
            # (manual = data/hpz only) — the compositions coexist.  The
            # 1F1B interleave's custom VJP does not re-enter the tier's
            # value_and_grad structure; that restriction is load-bearing
            # (asserted in tests/test_zeropp.py).
            pipe_cfg = self._config._param_dict.get("pipeline", {}) or {}
            sched = str(pipe_cfg.get("schedule", "") or "").lower()
            n_stages = int(self.model.meta.get("num_stages", 1))
            gas = self.gradient_accumulation_steps()
            if sched == "1f1b" and n_stages > 1 and gas >= n_stages:
                logger.warning(
                    "zero_quantized_gradients/sparse/1-bit exchanges do not "
                    "compose with the 1f1b pipeline schedule (its manual "
                    "fwd/bwd interleave bypasses the exchange tier); "
                    "reducing dense in full precision — use the chunked "
                    "GPipe schedule for a quantized wire under PP")
                return None
            if onebit_kind:
                logger.warning(
                    "1-bit optimizers do not engage their compressed "
                    "exchange under pipeline schedules; exchanging dense")
                onebit_kind = None
                if not qgz and not sparse_leaves:
                    return None
        mesh = self.mesh
        manual = tuple(a for a in (DATA_AXIS, HPZ_AXIS)
                       if mesh.shape[a] > 1)
        if not manual:
            logger.warning(
                "zero_quantized_gradients/sparse_gradients: no wide "
                "data/hpz mesh axis to exchange over; reducing dense in "
                "full precision")
            return None
        n_manual = 1
        for a in manual:
            n_manual *= mesh.shape[a]
        if sparse_leaves and zc.stage >= 3:
            logger.warning(
                "sparse_gradients: ZeRO stage 3 shards embedding storage; "
                "declared sparse params use the dense quantized exchange")
            sparse_leaves = {}

        shapes = self._param_shapes
        bk = getattr(self.model, "blocks_key", "blocks")
        keyed = jax.tree_util.tree_flatten_with_path(shapes)
        paths = [p for p, _ in keyed[0]]
        shape_leaves = [l for _, l in keyed[0]]
        treedef = keyed[1]
        pspec_leaves = jax.tree.leaves(self.param_specs,
                                       is_leaf=lambda x: isinstance(x, P))
        gspec_leaves = jax.tree.leaves(self.grad_specs,
                                       is_leaf=lambda x: isinstance(x, P))
        mesh_shape = dict(mesh.shape)

        in_spec_leaves, out_spec_leaves = [], []
        wrap_leaves, epilogue = [], []
        for path, shp, pspec, gspec in zip(paths, shape_leaves,
                                           pspec_leaves, gspec_leaves):
            ndim = len(shp.shape)
            top = getattr(path[0], "key", None) if path else None
            is_block = top == bk
            wrapped = self._manual_dims(pspec, ndim, manual)
            in_spec_leaves.append(self._restrict_spec(pspec, manual))
            wrapped_axes = {a for _, axes in wrapped for a in axes}
            remaining = [a for a in manual if a not in wrapped_axes]
            if wrapped:
                wrap_leaves.append(dict(
                    dims_axes=tuple(wrapped),
                    mesh_shape=mesh_shape,
                    quantize_fwd=bool(zc.zero_quantized_weights)))
            else:
                wrap_leaves.append(None)
            # epilogue plan for the axes no wrapper reduced
            produced = [[] for _ in range(ndim)]
            for d, axes in wrapped:
                produced[d] = list(axes)
            local_dims = list(shp.shape)
            for d, axes in wrapped:
                for a in axes:
                    local_dims[d] //= mesh_shape[a]
            plan = ("none", None)
            if remaining:
                total = 1
                for s in shp.shape:
                    total *= s
                if (top in sparse_leaves and ndim == 2
                        and not wrapped_axes):
                    plan = ("sparse", sparse_leaves[top], tuple(remaining))
                elif (onebit_kind and not wrapped_axes
                        and total > n_manual * 8):
                    # 1-bit error-feedback exchange (dense at the schedule's
                    # sync steps, sign+scale otherwise); third field: leaf
                    # splits evenly -> two-phase exchange with server
                    # residual
                    plan = ("onebit", tuple(remaining),
                            total % n_manual == 0)
                elif not qgz or total <= n_manual * 8:
                    plan = ("psum", tuple(remaining))
                else:
                    # place remaining axes where the grad spec wants them
                    # (stage >= 2), else dim 0 with a gather-back (stage
                    # 0/1 keeps replicated grads)
                    target = self._manual_dims(gspec, ndim, remaining)
                    ops, placed = [], set()
                    for d, axes in target:
                        for a in axes:
                            if a in placed:
                                continue
                            if local_dims[d] % mesh_shape[a] == 0 \
                                    and local_dims[d] >= mesh_shape[a]:
                                ops.append((d, a))
                                produced[d].append(a)
                                local_dims[d] //= mesh_shape[a]
                                placed.add(a)
                    leftover = [a for a in remaining if a not in placed]
                    for a in leftover:
                        for d in range(ndim):
                            if local_dims[d] % mesh_shape[a] == 0 \
                                    and local_dims[d] >= mesh_shape[a]:
                                ops.append((d, a))
                                produced[d].append(a)
                                local_dims[d] //= mesh_shape[a]
                                placed.add(a)
                                break
                    still = tuple(a for a in remaining if a not in placed)
                    if ops and not still and not wrapped and \
                            not self._manual_dims(gspec, ndim, manual):
                        # grads replicated over manual (stage 0/1):
                        # exchange int8 but hand back the full leaf
                        plan = ("scatter_gather", tuple(ops))
                        for d, a in ops:
                            produced[d].remove(a)
                    elif ops:
                        plan = ("scatter", tuple(ops), still)
                    else:
                        plan = ("psum", tuple(remaining))
            epilogue.append(plan)
            out_spec_leaves.append(P(*[
                tuple(e) if len(e) > 1 else (e[0] if e else None)
                for e in produced]))

        # block layer slices: scope kwargs with the stacked dim stripped
        block_scope = None
        if isinstance(shapes, dict) and bk in shapes and any(
                w is not None and getattr(p[0], "key", None) == bk
                for w, p in zip(wrap_leaves, paths)):
            blk_keyed = jax.tree_util.tree_flatten_with_path(shapes[bk])
            block_scope = []
            for w, p in zip(wrap_leaves, paths):
                if getattr(p[0], "key", None) != bk:
                    continue
                if w is None:
                    block_scope.append(None)
                else:
                    da = tuple((d - 1, axes) for d, axes in w["dims_axes"]
                               if d >= 1)
                    if any(d == 0 for d, _ in w["dims_axes"]):
                        raise ValueError(
                            "qgZ: stacked layer dim still zero-sharded "
                            "for a blocks leaf — storage spec rewrite "
                            "failed")
                    block_scope.append(dict(
                        dims_axes=da, mesh_shape=mesh_shape,
                        quantize_fwd=w["quantize_fwd"]) if da else None)
            assert len(block_scope) == len(blk_keyed[0])

        nonblock_wrap = [None if (getattr(p[0], "key", None) == bk) else w
                         for w, p in zip(wrap_leaves, paths)]
        onebit_cfg = None
        if onebit_kind and any(e[0] == "onebit" for e in epilogue):
            op = self._config.optimizer_params or {}
            onebit_cfg = dict(
                kind=onebit_kind,
                freeze_step=int(op.get("freeze_step", 100)),
                var_freeze_step=int(op.get("var_freeze_step", 100000)),
                var_update_scaler=int(op.get("var_update_scaler", 16)))
        return dict(
            manual=manual, n_manual=n_manual, qgz=qgz,
            sparse=sparse_leaves, treedef=treedef,
            in_specs=in_spec_leaves, out_specs=out_spec_leaves,
            nonblock_wrap=nonblock_wrap, block_scope=block_scope,
            epilogue=epilogue, paths=paths, onebit=onebit_cfg,
            shapes=[tuple(s.shape) for s in shape_leaves])

    def _qgz_grad_fn(self):
        """(params, stacked_local_batch, rng, scale[, dense_now, ob]) ->
        (loss, grads[, new_ob]) via the generalized quantized/sparse/1-bit
        gradient exchange (see ``_get_qgz_plan``), or None when the tier
        cannot engage."""
        from jax import lax
        from deepspeed_tpu.utils.jax_compat import shard_map
        from deepspeed_tpu.runtime.zero.zeropp import (
            gather_with_quantized_grad, quantized_psum_scatter)
        from deepspeed_tpu.runtime.sparse_tensor import (
            sparse_embedding_allreduce)
        from deepspeed_tpu.runtime.comm.compressed import compressed_allreduce
        plan = self._get_qgz_plan()
        if plan is None:
            return None
        gas = self.gradient_accumulation_steps()
        mesh = self.mesh
        manual, n_manual = plan["manual"], plan["n_manual"]
        onebit = plan["onebit"]
        mesh_shape = dict(mesh.shape)
        treedef = plan["treedef"]
        # pipeline composition (GPipe / chunked GPipe only — the plan
        # builder rejects 1f1b): the pipelined loss consumes the WHOLE
        # microbatch stack at once (microbatches fill the pipeline), so
        # the per-micro accumulation scan collapses to one call per chunk
        pipeline = bool(self.model.meta.get("pipeline"))
        pipe_chunks = 1
        if pipeline:
            pipe_cfg = self._config._param_dict.get("pipeline", {}) or {}
            n_buffers = int(pipe_cfg.get("num_pipe_buffers", 0) or 0)
            n_stages = int(self.model.meta.get("num_stages", 1))
            if (0 < n_buffers < gas and gas % n_buffers == 0
                    and n_buffers >= n_stages):
                pipe_chunks = gas // n_buffers
        dp_axes = tuple(self.topology.data_parallel_axes)
        batch_dp = tuple(a for a in dp_axes if a in manual)
        batch_entries = (None, batch_dp if len(batch_dp) > 1
                         else (batch_dp[0] if batch_dp else None))
        wrap_any = any(w is not None for w in plan["nonblock_wrap"])
        ob_axis = manual if len(manual) > 1 else manual[0]

        def grad_fn(params, stacked_batch, rng, scale, compress_step=None,
                    dense_now=None, ob=None):
            p_specs = jax.tree.unflatten(treedef, plan["in_specs"])
            b_specs = jax.tree.map(
                lambda x: P(*batch_entries[:x.ndim]), stacked_batch)
            g_specs = jax.tree.unflatten(treedef, plan["out_specs"])
            ob_spec = P(manual)

            def body(p, b, r, s, dense, err, srv):
                # independent dropout/noise per manual shard (a replicated
                # key would give every shard an identical mask)
                for a in manual:
                    r = jax.random.fold_in(r, lax.axis_index(a))

                def loss_fn(prm, mb, rng_, sc):
                    cparams = _tree_cast(prm, self.compute_dtype)
                    if compress_step is not None:
                        cparams = step_programs.compress(
                            self._step_ctx, cparams, compress_step)
                    if wrap_any:
                        leaves = jax.tree.leaves(cparams)
                        leaves = [
                            lf if kw is None
                            else gather_with_quantized_grad(lf, **kw)
                            for lf, kw in zip(leaves,
                                              plan["nonblock_wrap"])]
                        cparams = jax.tree.unflatten(treedef, leaves)
                    loss = self.model.loss(cparams, mb, rng_)
                    return loss.astype(jnp.float32) * sc

                def summed(batches):
                    """Scan the leading axis of ``batches``, each entry
                    an n-th of the step."""
                    n = jax.tree.leaves(batches)[0].shape[0]

                    def micro(carry, mb):
                        g_acc, l_acc = carry
                        # loss pre-scaled by 1/n_manual: every exchange
                        # below (and the wrapper VJPs) SUMS over the manual
                        # axes, so the sum lands on the global-batch mean
                        loss, g = jax.value_and_grad(loss_fn)(
                            p, mb, r, s / (n * n_manual))
                        g = _tree_cast(g, self.grad_dtype)
                        return (jax.tree.map(jnp.add, g_acc, g),
                                l_acc + loss), None

                    return jax.lax.scan(
                        micro, (zeros, jnp.float32(0.0)), batches)[0]

                zeros = jax.tree.map(
                    lambda x: jnp.zeros(x.shape, self.grad_dtype), p)
                if pipeline and pipe_chunks == 1:
                    # whole stack through the pipeline in one pass (the
                    # pipelined loss averages microbatches internally)
                    local_l, local_g = jax.value_and_grad(loss_fn)(
                        p, b, r, s / n_manual)
                    local_g = _tree_cast(local_g, self.grad_dtype)
                elif pipeline:
                    local_g, local_l = summed(jax.tree.map(
                        lambda x: x.reshape(pipe_chunks, gas // pipe_chunks,
                                            *x.shape[1:]), b))
                else:
                    local_g, local_l = summed(b)

                g_leaves = jax.tree.leaves(local_g)
                err_leaves = (jax.tree.leaves(err) if err is not None
                              else [None] * len(g_leaves))
                srv_leaves = (jax.tree.leaves(srv) if srv is not None
                              else [None] * len(g_leaves))
                out, new_err, new_srv = [], [], []
                for g, ep, e, sv in zip(g_leaves, plan["epilogue"],
                                        err_leaves, srv_leaves):
                    kind = ep[0]
                    if kind == "onebit":
                        # per-device residual slice: [1, ...] -> [...]
                        e0, sv0 = e[0], sv[0]

                        def dense_branch(gg, ee, ss):
                            # sync step: exact sum (loss pre-scaled 1/n);
                            # residuals pass through untouched (reference
                            # dense steps don't touch worker_error)
                            return lax.psum(gg, ep[1]), ee, ss

                        def compressed_branch(gg, ee, ss):
                            if ep[2]:
                                red, ne, ns = compressed_allreduce(
                                    gg, ee, ob_axis, n=n_manual,
                                    server_error=ss)
                            else:
                                red, ne = compressed_allreduce(
                                    gg, ee, ob_axis, n=n_manual)
                                ns = ss
                            # exchange returns the mean of 1/n-scaled
                            # local grads; x n lands on the global mean
                            return (red * n_manual).astype(gg.dtype), ne, ns

                        gr, ne, ns = lax.cond(dense, dense_branch,
                                              compressed_branch, g, e0, sv0)
                        out.append(gr)
                        new_err.append(ne[None])
                        new_srv.append(ns[None])
                        continue
                    new_err.append(e)
                    new_srv.append(sv)
                    if kind == "none":
                        out.append(g)
                    elif kind == "sparse":
                        _, ids_key, axes = ep
                        na = 1
                        for a in axes:
                            na *= mesh_shape[a]
                        out.append(sparse_embedding_allreduce(
                            g, b[ids_key], axes, na, mean=False))
                    elif kind == "psum":
                        out.append(lax.psum(g, ep[1]))
                    elif kind == "scatter_gather":
                        full = g
                        for d, a in ep[1]:
                            full = quantized_psum_scatter(
                                full, a, n=mesh_shape[a], scatter_dim=d)
                        for d, a in reversed(ep[1]):
                            full = lax.all_gather(full, a, axis=d,
                                                  tiled=True)
                        out.append(full)
                    else:                      # "scatter"
                        _, ops, still = ep
                        for d, a in ops:
                            g = quantized_psum_scatter(
                                g, a, n=mesh_shape[a], scatter_dim=d)
                        if still:
                            g = lax.psum(g, still)
                        out.append(g)
                g_red = jax.tree.unflatten(treedef, out)
                loss = lax.psum(local_l, manual)
                if err is None:
                    return loss, g_red
                return (loss, g_red,
                        jax.tree.unflatten(treedef, new_err),
                        jax.tree.unflatten(treedef, new_srv))

            if onebit is None:
                return shard_map(
                    lambda p, b, r, s: body(p, b, r, s, None, None, None),
                    mesh=mesh,
                    in_specs=(p_specs, b_specs, P(), P()),
                    out_specs=(P(), g_specs),
                    axis_names=set(manual),
                    check_vma=False)(params, stacked_batch, rng, scale)
            ob_specs = jax.tree.map(lambda _: ob_spec, ob["error"],
                                    is_leaf=lambda x: hasattr(x, "shape"))
            loss, grads, new_err, new_srv = shard_map(
                body, mesh=mesh,
                in_specs=(p_specs, b_specs, P(), P(), P(),
                          ob_specs, ob_specs),
                out_specs=(P(), g_specs, ob_specs, ob_specs),
                axis_names=set(manual),
                check_vma=False)(params, stacked_batch, rng, scale,
                                 dense_now, ob["error"], ob["server"])
            return loss, grads, {"error": new_err, "server": new_srv}

        return grad_fn

    def _grad_out_shardings(self):
        """Grad out_shardings for the offload paths.  With pinned-host params
        on a non-TPU backend, explicit out_shardings make JAX emit a host
        placement annotation the CPU runtime cannot execute — omit them there
        (grads then default to device placement)."""
        if (self._offload_param and
                list(self.mesh.devices.flat)[0].platform != "tpu"):
            return None
        return self.grad_shardings

    #: compiled fns that trace the model's layer scan (and therefore read
    #: the random-LTD keep count at trace time); eval ("loss") never enters
    #: the LTD scope, so it must not fork per keep value
    _LTD_SENSITIVE = ("train_step", "grad_step", "grad_micro", "grad")

    def _aq_active(self) -> bool:
        return self._aq is not None and self.global_steps >= self._aq[1]

    def _aq_scope(self):
        """Activation-quantization scope (compression config
        ``activation_quantization``): models' layer scans STE-quantize each
        block output while active.  One recompile at the schedule offset."""
        import contextlib
        if not self._aq_active():
            return contextlib.nullcontext()
        from deepspeed_tpu.compression import activation_quant_scope
        return activation_quant_scope(self._aq[0])

    def _step_program(self, name: str, nf_group=None):
        """The traced function of a step program (runtime/step_programs.py),
        before ``jax.jit``.  ``nf_group``: the leaf group a
        ``train_step@nf<g>`` chaos variant NaN-poisons."""
        if name == "train_step":
            return step_programs.build_train_step(
                self._step_ctx, qgz_fn=self._qgz_grad_fn(),
                plan=self._get_qgz_plan(), nf_group=nf_group)
        if (name in ("grad", "grad_step", "grad_micro")
                and self.model.meta.get("step_counts")):
            from deepspeed_tpu.utils.logging import warning_once
            warning_once(
                f"{name}: only the fused train step returns the model's "
                f"step counts ({sorted(self.model.meta.get('step_counts', ()))}"
                f"); on this path (micro-step API, offload tiers) they are "
                f"not counted and nothing warns of them")
        return step_programs.PROGRAMS[name](self._step_ctx)

    def _get_compiled(self, name: str):
        # model code reads the global topology while it traces (the
        # attention shard_map, MoE, pipeline): with two engines in one
        # process, each traces under its own mesh, not the one built last
        set_topology(self.topology)
        # random-LTD changes the traced keep count: one compile per value,
        # only for functions that actually trace the model
        key = (f"{name}@ltd{self._ltd_keep}"
               if self._ltd_keep and name in self._LTD_SENSITIVE else name)
        if self._aq_active() and name in self._LTD_SENSITIVE + ("loss",):
            key = f"{key}@aq"
        if key in self._compiled:
            return self._compiled[key]
        # @nf<g> variants are the train.nonfinite chaos flavors of the
        # fused step: the same build, handed the group to poison
        program, _, nf = name.partition("@nf")
        # batch args are pre-placed by _shard_batch (per-leaf ndim-aware
        # shardings), so jit infers their shardings from the arguments.
        gos = self._grad_out_shardings()
        loss_and_grads = (None, gos) if gos is not None else None
        placement = {
            "train_step": dict(out_shardings=(self.state_shardings, None),
                               donate_argnums=(0,)),
            "loss": {},
            "grad": dict(out_shardings=loss_and_grads, donate_argnums=(3,)),
            "grad_step": dict(out_shardings=(None, self.grad_shardings)),
            "grad_micro": dict(out_shardings=loss_and_grads),
            "grad_acc": dict(out_shardings=gos, donate_argnums=(0,)),
            "apply": dict(out_shardings=(self.state_shardings, None),
                          donate_argnums=(0, 1)),
            "zero_grads": dict(out_shardings=gos),
        }[program]
        traced = self._step_program(program, int(nf) if nf else None)
        # jax reports what it traces, lowers and compiles by the function's
        # name: the set-up account files it under the program's
        tracing.name_program(traced.__name__, program)
        fn = jax.jit(traced, **placement)
        self._compiled[key] = fn
        return fn

    # ------------------------------------------------------------------ data utils
    def _train_scope(self):
        """Scope for the compiled train step.  When the generalized qgZ
        tier engages with stage-3 block wrappers, models must gather each
        layer slice through the quantized-VJP wrapper (maybe_stream mode
        "qgz") instead of the jit-path qwZ/stream scopes."""
        plan = self._get_qgz_plan()
        if plan is not None and plan["block_scope"] is not None:
            from deepspeed_tpu.models.model import param_stream_scope
            return param_stream_scope(True, mesh=self.mesh,
                                      layer_specs=plan["block_scope"],
                                      mode="qgz")
        return self._stream_scope()

    def _stream_scope(self):
        """param_stream_scope for the per-layer parameter movement of this
        engine's tier: the host→device stream when offload_param is on,
        else the ZeRO-3 gather of each layer's slice (int8 under qwZ);
        tracing of the wrapped compiled fn happens on its first call,
        inside this scope."""
        from deepspeed_tpu.models.model import param_stream_scope
        import contextlib
        if not self._offload_param:
            plan = self._layer_gather_plan
            if plan is None:
                return contextlib.nullcontext()
            return param_stream_scope(True, mesh=self.mesh,
                                      layer_specs=plan[1], mode=plan[0])
        bk = getattr(self.model, "blocks_key", "blocks")
        # stream each layer to its LOGICAL (tensor-parallel) layout: ZeRO
        # storage axes are dropped, so the transfer is also the stage-3
        # per-layer gather (reference fetch_sub_module,
        # partitioned_param_coordinator.py:256)
        is_p = lambda x: isinstance(x, P)
        specs = jax.tree.leaves(self._block_logical_specs(), is_leaf=is_p)
        shardings = jax.tree.leaves(
            self.param_shardings[bk],
            is_leaf=lambda x: isinstance(x, NamedSharding))
        # one layer's slice: the stacked leading dim is stripped by the scan;
        # device-resident (persistent-small) leaves skip the transfer (None)
        layer_specs = [
            P(*tuple(s)[1:]) if sh.memory_kind == "pinned_host" else None
            for s, sh in zip(specs, shardings)]
        return param_stream_scope(True, mesh=self.mesh,
                                  layer_specs=layer_specs)

    def _block_logical_specs(self):
        """Logical (TP-only) specs of the layer-stacked subtree."""
        bk = getattr(self.model, "blocks_key", "blocks")
        logical = getattr(self.model, "logical_specs", None)
        if isinstance(logical, dict) and bk in logical:
            return logical[bk]
        return jax.tree.map(lambda _: P(), self.param_specs[bk],
                            is_leaf=lambda x: isinstance(x, P))

    @functools.cached_property
    def _layer_gather_plan(self):
        """ZeRO-3 gathers one layer at a time: inside the layer scan each
        ZeRO-sharded leaf's slice is all-gathered from its storage layout
        to its logical one where the layer is used (models/model.py
        maybe_stream, reference fetch_sub_module,
        partitioned_param_coordinator.py:256).  Under ZeRO++ qwZ
        (zero_quantized_weights) the slice quantizes to int8 before the
        gather and dequantizes after — 1 byte/param on the wire instead of
        2/4 (reference partition_parameters.py:652 + zeropp.md:13).
        ``(mode, layer_specs)`` for ``param_stream_scope``, or None where
        there is nothing to gather."""
        zc = self._config.zero_config
        qwz = zc.zero_quantized_weights and zc.stage == 3
        bk = getattr(self.model, "blocks_key", "blocks")
        if not (isinstance(self.param_specs, dict)
                and bk in self.param_specs):
            if qwz:
                logger.warning(
                    f"zero_quantized_weights needs a layer-stacked '{bk}' "
                    f"params subtree; model has none — qwZ disabled")
            return None
        is_p = lambda x: isinstance(x, P)
        storage = jax.tree.leaves(self.param_specs[bk], is_leaf=is_p)
        targets = jax.tree.leaves(self._block_logical_specs(), is_leaf=is_p)
        # qwZ re-scatters the cotangent to the storage layout; the plain
        # gather hands it over in the gradient's (the same but under hpZ,
        # where gradients shard over more axes than the stored params)
        back = storage if qwz else jax.tree.leaves(self.grad_specs[bk],
                                                   is_leaf=is_p)
        pairs = []
        for st, tg, bw in zip(storage, targets, back):
            st_l = P(*tuple(st)[1:])     # layer slice: leading dim stripped
            tg_l = P(*tuple(tg)[1:])
            # only leaves where the gather actually moves data (zero-sharded
            # storage) take part
            pairs.append((P(*tuple(bw)[1:]), tg_l) if st_l != tg_l else None)
        if not qwz and not any(pairs):
            # stage < 3 or a ZeRO world of one: nothing to gather
            return None
        if not getattr(getattr(self.model, "config", None), "remat", False):
            logger.warning(
                "ZeRO-3 without per-layer remat keeps every gathered "
                "layer's copy alive for backward — set the model's "
                "remat=True to bound HBM at O(1 layer) of parameters")
        return ("qwz" if qwz else "gather"), pairs

    #: batch keys carrying a trailing sequence dim (safe to truncate)
    _SEQ_KEYS = ("input_ids", "labels", "attention_mask", "position_ids")

    def _apply_curriculum(self, batch):
        """Legacy seqlen curriculum (reference engine.py:1761): truncate the
        batch's sequence dim to the scheduled difficulty.  Each distinct
        truncated length compiles a fresh step, so the difficulty rounds UP
        to a multiple of ``curriculum_learning.seqlen_bucket`` — fine
        schedules cost at most max_difficulty/bucket compiles."""
        if self.curriculum_scheduler is None:
            return batch
        difficulty = self.curriculum_scheduler.update_difficulty(
            self.global_steps + 1)
        cl = self._config.curriculum_learning
        if cl.curriculum_type != "seqlen" or not isinstance(batch, dict):
            return batch
        bucket = int(getattr(cl, "seqlen_bucket", 0) or 0)
        if bucket > 1:
            difficulty = -(-difficulty // bucket) * bucket
        seq = max((np.shape(v)[-1] for k, v in batch.items()
                   if k in self._SEQ_KEYS), default=0)
        if seq <= difficulty:
            return batch                       # schedule saturated: no copies
        return {k: (np.asarray(v)[..., :difficulty]
                    if k in self._SEQ_KEYS else v)
                for k, v in batch.items()}

    def _advance_ltd(self):
        """Advance the random-LTD keep schedule (once per optimizer batch).
        A keep >= the current sequence length is a no-op: clear it so no
        ltd-suffixed recompiles happen."""
        if self.random_ltd_scheduler is None:
            return
        keep = self.random_ltd_scheduler.update_seq(self.global_steps)
        self._ltd_keep = keep if keep < self._last_seq_len else None

    def _ltd_scope(self):
        """Random-LTD token-drop scope: models' layer scans read the keep
        count at trace time (data_pipeline/random_ltd.ltd_scope).  The
        schedule advances once per train_batch, before compile-cache lookup,
        so the cache key and the traced value always agree."""
        import contextlib
        if not self._ltd_keep:
            return contextlib.nullcontext()
        from deepspeed_tpu.runtime.data_pipeline.random_ltd import ltd_scope
        return ltd_scope(self._ltd_keep)

    def _next_rng(self):
        self._rng, out = jax.random.split(self._rng)
        return out

    def _shard_batch(self, batch, stacked: bool):
        spec = (P(None, *self.batch_spec) if stacked else self.batch_spec)

        def put(x):
            x = np.asarray(x)
            nd = x.ndim
            entries = tuple(spec)[:nd]
            s = NamedSharding(self.mesh, P(*entries))
            return jax.device_put(x, s)

        return jax.tree.map(put, batch)

    def _stack_micro_batches(self, data_iter):
        gas = self.gradient_accumulation_steps()
        batches = []
        for _ in range(gas):
            batches.append(next(data_iter))
        return jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]),
                            *batches)

    # ------------------------------------------------------------------ public api
    def train_batch(self, data_iter=None, batch=None):
        """One full training step over ``gradient_accumulation_steps``
        micro-batches (reference: PipelineEngine.train_batch,
        runtime/pipe/engine.py:297; plain-engine equivalent is GAS×
        forward/backward + step).

        Telemetry: the whole step runs inside a ``train/step`` span
        whose correlation id (``train-step-N``) is inherited by every
        nested span/instant — checkpoint stages, timer phases, injected
        faults — so a chaos run reads as one coherent timeline; step
        latency, tokens/s, and MFU land in the metrics registry."""
        step = self.global_steps + 1
        t0 = time.perf_counter()
        with tracing.setup_span(tracing.SPAN_TRAIN_STEP,
                                step=self.global_steps, tracer=self.tracer,
                                cat="train", corr=f"train-step-{step}",
                                args={"step": step}):
            loss = self._train_batch_impl(data_iter=data_iter, batch=batch)
            # still inside the train/step span so an anomaly instant
            # lands between this step's B/E pair (the serve side keeps
            # the same invariant)
            self._record_step_telemetry(time.perf_counter() - t0)
        return loss

    def _train_batch_impl(self, data_iter=None, batch=None):
        self.fault_injector.check("train.step")
        if self._commstat is not None and self._comm_step_window:
            # per-step collective window (ISSUE 19): opens the overlap
            # meter and runs the comm.collective drill gate — an
            # injected stall wedges THIS step exactly where a
            # straggling link would, while /debug/comm keeps answering
            comm_corr = f"train-step-{self.global_steps + 1}"
            self._commstat.step_begin()
            # the step's wire bytes, once somebody has asked for its
            # cost report (tracing.get_program_cost); a drill starts none
            rep = tracing.get_program_cost(create=False)
            wire = rep.comm_wire_bytes() if rep is not None else 0
            with self.tracer.span("comm/step_window", cat="comm",
                                  corr=comm_corr,
                                  args={"wire_bytes": wire}):
                t0c = time.perf_counter()
                self._commstat.fault_gate()
                gate_s = time.perf_counter() - t0c
            self._commstat.observe("step_gate", wire, gate_s,
                                   axis="step", corr=comm_corr)
        self.timers(TRAIN_BATCH_TIMER).start()
        self.tput_timer.start()
        if batch is None:
            if data_iter is None:
                if self.training_dataloader is None:
                    raise ValueError("train_batch needs a data iterator or batch")
                # persistent repeating iterator so successive calls advance
                # through the dataset instead of replaying its head
                if self._data_iterator is None:
                    from deepspeed_tpu.runtime.dataloader import RepeatingLoader
                    self._data_iterator = iter(
                        RepeatingLoader(self.training_dataloader))
                data_iter = self._data_iterator
            if not hasattr(data_iter, "__next__"):
                # non-iterator iterable (list, DataLoader): cache a repeating
                # iterator keyed on the object so successive train_batch calls
                # advance through it instead of replaying its head, and wrap
                # around at the end instead of leaking StopIteration mid-step
                if self._client_iter_src is not data_iter:
                    from deepspeed_tpu.runtime.dataloader import RepeatingLoader
                    self._client_iter_src = data_iter
                    self._client_iter = iter(RepeatingLoader(data_iter))
                data_iter = self._client_iter
            batch = self._stack_micro_batches(data_iter)
        else:
            gas = self.gradient_accumulation_steps()
            lead = jax.tree.leaves(batch)[0].shape[0]
            if lead != gas:
                raise ValueError(
                    f"train_batch(batch=...) leaves must lead with gas={gas}, "
                    f"got {lead}")
        batch = self._apply_curriculum(batch)
        self._last_seq_len = int(jax.tree.leaves(batch)[0].shape[-1])
        self._advance_ltd()
        if self.progressive_layer_drop is not None:
            if isinstance(batch, dict):
                # traced scalar per micro-batch: the theta schedule advances
                # every step without recompiling (reference engine.py:1755)
                batch = dict(batch, pld_theta=np.full(
                    (self.gradient_accumulation_steps(),),
                    self.progressive_layer_drop.get_theta(), np.float32))
            else:
                from deepspeed_tpu.utils.logging import warning_once
                warning_once("progressive_layer_drop: batch is not a dict; "
                             "pld_theta cannot be injected — PLD is a no-op")
        if self.flops_profiler is not None and (
                self.global_steps + 1 ==
                self._config.flops_profiler_config.profile_step):
            self.flops_profiler.start_profile()
        batch = self._shard_batch(batch, stacked=True)
        if self._param_nvme:
            # streamed-param tier (ISSUE 17): the weight pass runs layer by
            # layer out of the ParamStore — no compiled full-model step
            # exists because the full param tree never materializes
            gas = self.gradient_accumulation_steps()
            losses = []
            acc_nb = None
            acc_layers = None
            with self.tracer.span("train/fwd_bwd", cat="train",
                                  args={"micro_batches": gas}):
                for i in range(gas):
                    mb = jax.tree.map(lambda x: x[i], batch)
                    loss, g_nb, g_layers = \
                        self.param_runner.loss_and_grads(
                            self.state["params"], mb, self._next_rng())
                    losses.append(float(loss))
                    if acc_nb is None:
                        acc_nb, acc_layers = g_nb, g_layers
                    else:
                        acc_nb = jax.tree.map(np.add, acc_nb, g_nb)
                        acc_layers = [jax.tree.map(np.add, a, g)
                                      for a, g in zip(acc_layers, g_layers)]
            if gas > 1:
                inv = np.float32(1.0 / gas)
                acc_nb = jax.tree.map(lambda g: g * inv, acc_nb)
                acc_layers = [jax.tree.map(lambda g: g * inv, t)
                              for t in acc_layers]
            mean_loss = jnp.float32(sum(losses) / gas)
            with self.tracer.span("train/optimizer_step", cat="train"):
                metrics = self._nvme_apply(acc_nb, acc_layers, mean_loss)
        elif self._offload_param:
            fn = self._get_compiled("grad_micro")
            gas = self.gradient_accumulation_steps()
            acc = None
            losses = []
            with self.tracer.span("train/fwd_bwd", cat="train",
                                  args={"micro_batches": gas}):
                for i in range(gas):
                    mb = jax.tree.map(lambda x: x[i], batch)
                    with self._stream_scope(), self._ltd_scope(), \
                            self._aq_scope():
                        loss, grads = fn(self.state, mb, self._next_rng())
                    losses.append(loss)
                    if self.streamed_optimizer is not None:
                        # stays on device / pinned host — no Python round
                        # trip
                        acc = (grads if acc is None else
                               self._get_compiled("grad_acc")(acc, grads))
                    else:
                        g = jax.tree.map(np.asarray, grads)
                        acc = g if acc is None else jax.tree.map(
                            np.add, acc, g)
            mean_loss = sum(losses) / gas        # device scalars, async
            with self.tracer.span("train/optimizer_step", cat="train"):
                if self.streamed_optimizer is not None:
                    metrics = self._streamed_apply(acc, mean_loss)
                else:
                    metrics = self._host_apply(acc, mean_loss)
        elif self._offload:
            with self.tracer.span("train/fwd_bwd", cat="train"), \
                    self._stream_scope(), self._ltd_scope(), \
                    self._aq_scope():
                loss, grads = self._get_compiled("grad_step")(
                    self.state, batch, self._next_rng())
            with self.tracer.span("train/optimizer_step", cat="train"):
                metrics = self._host_apply(grads, loss)
        else:
            # train.nonfinite chaos injection (ISSUE 15): a firing
            # fault compiles/reuses a dedicated step variant that
            # NaN-poisons the chosen leaf group's gradient; the healthy
            # cached program is untouched and every non-firing step
            # keeps using it
            nf_group = self._nonfinite_fault_group()
            fn = self._get_compiled(
                "train_step" if nf_group is None
                else f"train_step@nf{nf_group}")
            rng = self._next_rng()
            # one fused program: fwd+bwd+apply dispatch together (the
            # per-phase split lives in the fwd/bwd/step timers when the
            # micro API drives them)
            with tracing.setup_span(tracing.SPAN_FUSED_STEP,
                                    tracer=self.tracer, cat="train"), \
                    self._train_scope(), self._ltd_scope(), \
                    self._aq_scope():
                self.state, metrics = fn(self.state, batch, rng)
            self._maybe_register_program_map(batch)
        self._finish_step(metrics)
        # syncing on the loss every step stalls the async dispatch
        # pipeline; only pay it when the user asked for wall-clock
        # breakdowns
        self.timers(TRAIN_BATCH_TIMER).stop(
            sync_obj=metrics["loss"] if self._config.wall_clock_breakdown
            else None)
        return metrics["loss"]

    def forward(self, batch):
        """Micro-step API: one fused loss+grad computation (reference
        engine.py:1722).  JAX has no separate backward graph, so forward runs
        ``value_and_grad`` once — the loss returned here and the gradients
        ``backward()`` accumulates come from the same evaluation (same RNG,
        no double forward cost)."""
        if self._param_nvme:
            raise NotImplementedError(
                "the forward/backward/step micro API is not available with "
                "offload_param.device=nvme — use train_batch (the streamed "
                "weight pass owns the layer schedule)")
        if self._micro_grads is None and self._pending_grads is None:
            # fresh accumulation window: advance the schedules (reference
            # triggers curriculum/LTD in forward, engine.py:1722/:1761)
            batch = self._apply_curriculum(batch)
            self._last_seq_len = int(jax.tree.leaves(batch)[0].shape[-1])
            self._advance_ltd()
        if self.progressive_layer_drop is not None and isinstance(batch, dict):
            batch = dict(batch, pld_theta=np.float32(
                self.progressive_layer_drop.get_theta()))
        batch = self._shard_batch(batch, stacked=False)
        if self._micro_grads is None:
            self._micro_grads = self._get_compiled("zero_grads")(
                self.state["params"])
        with self._stream_scope(), self._ltd_scope(), self._aq_scope():
            loss, grads = self._get_compiled("grad")(
                self.state, batch, self._next_rng(), self._micro_grads)
        self._micro_grads = None   # donated into grads
        self._pending_grads = grads
        self._last_loss = loss
        return loss

    def backward(self, loss=None):
        """Bank the gradients computed by the paired ``forward`` (reference
        engine.py:1863)."""
        if self._pending_grads is None:
            raise RuntimeError("backward() called without a prior forward()")
        self._micro_grads = self._pending_grads
        self._pending_grads = None
        return self._last_loss

    def step(self):
        """Apply the update at the gradient-accumulation boundary (reference
        engine.py:2061 + :1945 boundary logic)."""
        at_boundary = self.is_gradient_accumulation_boundary()
        self.micro_steps += 1
        if not at_boundary:
            return
        if self._micro_grads is None:
            raise RuntimeError("step() called without accumulated gradients")
        if self.streamed_optimizer is not None:
            metrics = self._streamed_apply(self._micro_grads, self._last_loss)
        elif self._offload:
            metrics = self._host_apply(self._micro_grads, self._last_loss)
        else:
            self.state, metrics = self._get_compiled("apply")(
                self.state, self._micro_grads)
            if self._last_loss is not None:
                metrics["loss"] = self._last_loss
        self._micro_grads = None
        self._finish_step(metrics)

    def _streamed_apply(self, grads, loss):
        """Streamed-optimizer epilogue: the update runs on device over
        pinned-host state; only python-side counters advance here (no device
        sync — overflow/grad-norm stay device scalars, banked lazily)."""
        fp16 = self._config.fp16.enabled
        scaler = self.state["scaler"]
        # device scalars pass straight through as jit arguments — a float()
        # here would block on the previous step's whole update
        scale = scaler.cur_scale if fp16 else 1.0
        new_params, grad_norm, overflow = self.streamed_optimizer.step(
            grads, self.compute_dtype, scale, self.state["step"])
        self.state["params"] = new_params
        # overflow steps don't advance the schedule/bias-correction step
        # (reference skip semantics; matches step_programs.apply_grads)
        self._write_placed("step", self.state["step"] + jnp.where(
            overflow, jnp.int32(0), jnp.int32(1)))
        if fp16:
            self._write_placed("scaler", update_scale(
                scaler, overflow, self.scaler_config))
        return {
            "loss": loss,
            "grad_norm": grad_norm,
            "overflow": overflow,
            "loss_scale": self.state["scaler"].cur_scale,
        }

    def _write_placed(self, key, value):
        """The host-side writers of the counter and the scaler (the offload
        tiers' epilogues) leave them where the step programs return them:
        the jitted program that takes ``self.state`` next then finds the
        placement it was compiled for, whatever placement jax inferred
        for the eager arithmetic that made the new value."""
        self.state[key] = jax.device_put(value, self.state_shardings[key])

    def _reload_layer(self, i: int):
        """Authoritative rebuild of layer ``i``'s compute-dtype shard from
        the host optimizer's fp32 masters — the param.swap degrade path.
        Bit-identical to the streamed payload: the stored shard IS
        ``master.astype(compute_dtype)`` (written by the optimizer sink)."""
        bk = getattr(self.model, "blocks_key", "blocks")
        prefix = f"{bk}/L{i:04d}/"
        ho = self.host_optimizer
        out = {}
        for path in ho.paths:
            if not path.startswith(prefix):
                continue
            parts = path[len(prefix):].split("/")
            node = out
            for part in parts[:-1]:
                node = node.setdefault(part, {})
            node[parts[-1]] = np.asarray(
                ho._get_master(path).reshape(ho.shapes[path])
                .astype(self.compute_dtype))
        return out

    def _nvme_apply(self, g_nonblock, g_layers, loss):
        """Streamed-param epilogue (ISSUE 17): the host optimizer walks the
        per-layer grads in path order; a sink hands each finished layer's
        updated compute-dtype leaves straight to the ParamStore (demoted
        layers ride the fire-and-forget write ring) instead of
        materializing the full tree.  Nonblock leaves upload as usual."""
        bk = getattr(self.model, "blocks_key", "blocks")
        grads_tree = dict(g_nonblock)
        grads_tree[bk] = {f"L{i:04d}": g_layers[i]
                          for i in range(self._num_layers)}
        step_index = int(self.state["step"])
        store = self.param_store
        prefix = f"{bk}/"
        pend = {"layer": None, "leaves": {}}

        def _flush_pending():
            if pend["layer"] is None:
                return
            nest = {}
            for lpath, arr in pend["leaves"].items():
                parts = lpath.split("/")
                node = nest
                for part in parts[:-1]:
                    node = node.setdefault(part, {})
                node[parts[-1]] = arr
            store.put_layer(pend["layer"], nest)
            pend["layer"] = None
            pend["leaves"] = {}

        def sink(path, arr):
            if not path.startswith(prefix):
                return False
            lname, _, leafpath = path[len(prefix):].partition("/")
            i = int(lname[1:])
            if pend["layer"] is not None and pend["layer"] != i:
                # path order groups layers contiguously: a new layer name
                # means the previous one is complete — write it back
                _flush_pending()
            pend["layer"] = i
            pend["leaves"][leafpath] = arr
            return True

        new_tree, grad_norm, overflow = self.host_optimizer.step(
            grads_tree, step_index, self.compute_dtype, sink=sink)
        if not overflow:
            _flush_pending()
            nonblock_new = {k: v for k, v in new_tree.items() if k != bk}
            self.state["params"] = jax.device_put(nonblock_new,
                                                  self._nonblock_shardings)
            self._write_placed("step", self.state["step"] + 1)
        store.publish(self.telemetry_registry)
        return {
            "loss": loss if loss is not None else jnp.float32(0.0),
            "grad_norm": jnp.float32(grad_norm),
            "overflow": jnp.bool_(overflow),
            "loss_scale": self.state["scaler"].cur_scale,
        }

    def _host_apply(self, grads, loss):
        """Offload epilogue: unscale on host, C++ optimizer step in host DRAM
        (or NVMe-streamed moments), upload compute-dtype working params."""
        import numpy as np_
        from deepspeed_tpu.runtime.fp16.loss_scaler import update_scale
        fp16 = self._config.fp16.enabled
        scaler = self.state["scaler"]
        scale = float(scaler.cur_scale) if fp16 else 1.0
        if scale != 1.0:
            grads = jax.tree.map(lambda g: g / scale, grads)
        step_index = int(self.state["step"])
        new_params, grad_norm, overflow = self.host_optimizer.step(
            grads, step_index, self.compute_dtype)
        if not overflow:
            self.state["params"] = jax.device_put(new_params,
                                                  self.param_shardings)
            self._write_placed("step", self.state["step"] + 1)
        if fp16:
            self._write_placed("scaler", update_scale(
                scaler, jnp.bool_(overflow), self.scaler_config))
        return {
            "loss": loss if loss is not None else jnp.float32(0.0),
            "grad_norm": jnp.float32(grad_norm),
            "overflow": jnp.bool_(overflow),
            "loss_scale": self.state["scaler"].cur_scale,
        }

    def eval_batch(self, batch):
        batch = self._shard_batch(batch, stacked=False)
        if self._param_nvme:
            # forward-only streamed weight pass (same double-buffered
            # layer pipeline as training)
            return self.param_runner.loss(self.state["params"], batch)
        with self._stream_scope(), self._aq_scope():
            return self._get_compiled("loss")(self.state, batch,
                                              self._next_rng())

    def _finish_step(self, metrics):
        if "counts" in metrics:
            # the model's counts stay on the device until their step has
            # ended: a later step reads them, or step_counts(); none waits
            self._pending_counts.append(
                (self.global_steps + 1, metrics["counts"]))
            self._resolve_step_counts(wait=False)
        # numerics bank (ISSUE 15): pull the in-graph stats out of the
        # metrics dict and bank them as DEVICE scalars keyed by the
        # step id this step will carry (train-step-N corr) — the same
        # lazy idiom as _pending_overflow, zero host syncs here
        num_group_norms = metrics.pop("num_group_norms", None)
        num_nonfinite = metrics.pop("num_nonfinite", None)
        num_update_ratio = metrics.pop("num_update_ratio", None)
        if self.numerics is not None and num_group_norms is not None:
            self.numerics.bank(
                self.global_steps + 1,
                loss=metrics.get("loss"),
                grad_norm=metrics.get("grad_norm"),
                overflow=metrics.get("overflow", False),
                loss_scale=metrics.get("loss_scale"),
                group_norms=num_group_norms,
                nonfinite=num_nonfinite,
                update_ratio=num_update_ratio)
        if self._sanitize_gradients:
            # debug tier: sync and verify the global grad norm.  A loss-scaler
            # overflow is the *handled* non-finite path (the step was skipped
            # and the scale backed off) — only unexpected NaN/Inf raises.
            overflow = bool(np.asarray(metrics.get("overflow", False)))
            gn = float(np.asarray(metrics["grad_norm"]))
            if not overflow and not np.isfinite(gn):
                # upgraded from a log line to a post-mortem trigger
                # (ISSUE 15): resolve the bank so the provenance record
                # exists, write the terminal bundle (min_interval_s=0 —
                # the raise below may kill the run, so the flap rate
                # limit must not suppress its only bundle), and name
                # the first offending leaf group in the raise
                prov = None
                if self.numerics is not None:
                    try:
                        self.numerics.resolve(emit_postmortem=False)
                        prov = self.numerics.last_nonfinite()
                    except Exception:
                        prov = None
                first = prov["first_group"] if prov else "<unknown>"
                try:
                    from deepspeed_tpu.resilience.postmortem import \
                        write_postmortem
                    write_postmortem(
                        self._postmortem_dir(),
                        f"non-finite gradient norm {gn} at step "
                        f"{self.global_steps + 1} (first group {first})",
                        step=self.global_steps + 1,
                        registry=self.telemetry_registry,
                        flightrec=self.flightrec,
                        min_interval_s=0.0)
                except Exception as e:  # the raise below is the signal
                    logger.warning(f"numerics: terminal bundle failed "
                                   f"({e})")
                raise FloatingPointError(
                    f"sanitize_gradients: non-finite gradient norm {gn} at "
                    f"step {self.global_steps + 1} (first offending leaf "
                    f"group: {first}; loss="
                    f"{float(np.asarray(metrics['loss']))}); enable "
                    "debug.debug_nans to locate the faulting primitive")
        self.global_steps += 1
        if (self._fp_interval and self.numerics is not None
                and self.global_steps % self._fp_interval == 0):
            # determinism fingerprint (ISSUE 15): one bounded host
            # fetch every fingerprint_interval steps, by design
            self._record_fingerprint(loss=metrics.get("loss"))
        self.global_samples += self.train_batch_size()
        if self.progressive_layer_drop is not None:
            # reference engine.py:1755: PLD theta advances per step; models
            # that take a pld kwarg consume engine.progressive_layer_drop
            self.progressive_layer_drop.update_state(self.global_steps)
        if self.flops_profiler is not None and self.flops_profiler.started:
            fpc = self._config.flops_profiler_config
            tokens = self.train_batch_size() * self._last_seq_len
            fpt = self.model.flops_per_token or 0.0
            self.flops_profiler.set_flops(fpt * tokens)
            self.flops_profiler.stop_profile(sync_obj=metrics.get("loss"))
            self.flops_profiler.print_model_profile(
                profile_step=self.global_steps,
                module_depth=fpc.module_depth, top_modules=fpc.top_modules,
                detailed=fpc.detailed, output_file=fpc.output_file)
            # profiler-grade gauges (ISSUE 4): unlike the per-step MFU
            # estimate, this pair is synced on the step outputs — the
            # profile step pays the device round trip anyway
            self.telemetry_registry.set_gauge(
                "train/profiled_flops_per_s",
                self.flops_profiler.achieved_flops_per_s())
            if self._peak_flops:
                pm = self.flops_profiler.mfu(self._peak_flops)
                if pm is not None:
                    self.telemetry_registry.set_gauge(
                        "train/profiled_mfu", pm)
        if self._config.fp16.enabled:
            # don't force a device->host fetch of the overflow flag every
            # step — bank it and resolve at report boundaries / on access
            at_print = (self._config.steps_per_print and
                        self.global_steps % self._config.steps_per_print == 0)
            if at_print or self._config.wall_clock_breakdown:
                self._resolve_overflows()
                if bool(metrics.get("overflow", False)):
                    self._skipped_steps += 1
                    log_dist(
                        f"[step {self.global_steps}] overflow, skipping "
                        f"update; loss scale -> "
                        f"{float(metrics['loss_scale'])}", ranks=[0])
            else:
                self._pending_overflow.append(metrics.get("overflow", False))
        self.last_metrics = {k: v for k, v in metrics.items()}
        # sync on the step outputs so wall-clock covers the async dispatch
        self.tput_timer.stop(sync_obj=metrics.get("loss"))
        if self.monitor is not None and self.monitor.enabled:
            step = self.global_steps
            events = [("Train/Samples/train_loss",
                       float(metrics.get("loss", 0.0)), step)]
            if self.lr_schedule is not None:
                events.append(("Train/Samples/lr", self.get_lr()[0], step))
            if self._config.fp16.enabled:
                events.append(("Train/Samples/loss_scale",
                               float(metrics["loss_scale"]), step))
            self.monitor.write_events(events)
        if (self._config.steps_per_print and
                self.global_steps % self._config.steps_per_print == 0):
            if self.numerics is not None:
                # report boundary: the print below syncs on the metrics
                # anyway, so the banked numerics resolve here for free
                # (non-fp16 runs have no overflow bank to ride)
                try:
                    self.numerics.resolve()
                except Exception as e:
                    logger.debug(f"numerics: resolve failed ({e})")
            loss = metrics.get("loss")
            msg = f"step={self.global_steps}"
            if loss is not None:
                msg += f" loss={float(loss):.4f}"
            msg += f" grad_norm={float(metrics.get('grad_norm', 0.0)):.3f}"
            log_dist(msg, ranks=[0])

    def _maybe_register_program_map(self, batch):
        """On the first fused dispatch, publish the step under
        ``"train/step"`` for ``get_program_map`` (telemetry/tracing.py),
        ``step_memory`` (telemetry/memory.py) and ``get_program_cost``:
        three thunks and the batch's abstract signature, nothing more —
        the executable is fetched, or the step traced once more for its
        cost report, when someone first ASKS (a peek — ``/debug/memory``,
        ``/debug/perf``, a post-mortem bundle — starts nothing) and
        dropped.  Every load leaves its six numbers behind, so the map
        and then the account are ONE load; the text (tens of MB) is
        handed to its asker and not kept, so the account first and the
        map later are two.  The registry calls one thunk at a time.
        The table holds the engine weakly."""
        if self._program_map_registered:
            return
        self._program_map_registered = True
        signature = jax.tree.map(_abstract_placed, batch)
        tokens = self.train_batch_size() * max(self._last_seq_len, 0)
        alive = weakref.ref(self)
        kept = {}               # "program": the first load's numbers

        def load():
            engine = alive()
            if engine is None:
                return None
            executable = engine._compile_train_step(signature)
            kept.setdefault("program", program_memory(executable))
            return executable

        def step_text():
            with tracing.setup_span(tracing.SPAN_PROGRAM_TEXT):
                executable = load()
                return None if executable is None else executable.as_text()

        def step_bytes():
            engine = alive()
            if engine is None or engine._state_bytes is None:
                return None     # the engine is gone, or its ledger is off
            with tracing.setup_span(tracing.SPAN_MEMORY_COMPILED):
                if "program" not in kept:
                    load()
                program = kept.get("program")
            if program is None:
                return None     # a backend with no memory analysis
            gradients = tracing.gradient_bytes(TRAIN_STEP_PROGRAM)
            temporaries = live_temporaries(program, gradients)
            # whoever makes the account feeds the ledger's device tier
            # (a reader of the ledger only ever peeks at it)
            led = get_memory_ledger()
            if gradients is not None:
                led.set_bytes("device", "gradients", gradients)
            if temporaries is not None:
                led.set_bytes("device", "workspace",
                              temporaries - gradients, **program)
            return {
                "state": engine._state_bytes,
                "batch": fullest_device_bytes(batch=signature)["batch"],
                "program": program, "gradients": gradients,
                "temporaries": temporaries}

        def step_cost():
            """Dot FLOPs, boundary HBM bytes (state read + written +
            batch: the step streams its whole state), pallas launch
            sites and collective bytes of the step, from shapes alone;
            whoever asks also publishes the static ``perf/*`` gauges."""
            engine = alive()
            if engine is None:
                return None
            from deepspeed_tpu.telemetry.costmodel import analyze_fn
            from deepspeed_tpu.telemetry.roofline import publish_report
            set_topology(engine.topology)
            with tracing.setup_span(tracing.SPAN_COST_ANALYZE), \
                    engine._train_scope(), engine._ltd_scope(), \
                    engine._aq_scope():
                report = analyze_fn(
                    engine._step_program("train_step"),
                    *engine._abstract_step_args(signature),
                    name=TRAIN_STEP_PROGRAM,
                    detail={"tokens_per_step": tokens})
            publish_report(engine.telemetry_registry, report)
            return report
        register_program(
            TRAIN_STEP_PROGRAM, step_text, step_bytes, step_cost,
            lambda: alive() and alive().step_load())

    def compile_train_step(self, batch):
        """The fused step ``train_batch`` runs for ``batch`` (leaves lead
        with gas), compiled ahead of time from shapes alone — no state is
        read or donated.  For inspection: ``.as_text()`` shows which
        kernels and collectives the backend kept, ``.memory_analysis()``
        the bytes per device.  With the persistent compilation cache on it
        is a cache load once the step has run."""
        return self._compile_train_step(jax.tree.map(
            _abstract_placed, self._shard_batch(batch, stacked=True)))

    def _compile_train_step(self, signature):
        fn = self._get_compiled("train_step")
        with tracing.setup_span(tracing.SPAN_COMPILE_AOT), \
                self._train_scope(), self._ltd_scope(), self._aq_scope():
            return fn.lower(*self._abstract_step_args(signature)).compile()

    def _abstract_step_args(self, signature):
        """The fused step's arguments as shapes: nothing is read from
        the device or donated."""
        return (jax.tree.map(_abstract, self.state, self.state_shardings),
                signature, _abstract(self._rng))

    def _postmortem_dir(self) -> str:
        """Training-side bundle placement (the preemption.py rules):
        an explicit ``resilience.postmortem_dir`` wins ("" disables —
        write_postmortem no-ops on a falsy dir); None means "next to
        the checkpoints".  Before the first save there IS no "next to
        the checkpoints": bundles stay off rather than surprising the
        working directory (a run that never checkpoints is a run that
        opted out of durable state)."""
        configured = self._config.resilience_config.postmortem_dir
        if configured is not None:
            return configured
        if self._last_save_dir:
            return self._last_save_dir
        logger.debug("numerics: no postmortem dir yet (no checkpoint "
                     "save_dir; set resilience.postmortem_dir to "
                     "capture bundles before the first save)")
        return ""

    def _numerics_postmortem(self, prov):
        """NumericsState nonfinite callback: an unexpected non-finite
        step detected at bank resolution writes a forensic bundle
        (numerics.json carries the provenance record).  Default rate
        limit — a diverged run resolves many non-finite steps, and one
        bundle per window is the record that matters."""
        from deepspeed_tpu.resilience.postmortem import write_postmortem
        write_postmortem(
            self._postmortem_dir(),
            f"non-finite gradients at step {prov.get('step')} "
            f"(first group {prov.get('first_group')})",
            step=prov.get("step"),
            registry=self.telemetry_registry,
            flightrec=self.flightrec)

    def _record_fingerprint(self, loss=None):
        """Digest (sampled param leaves, rng chain, step, loss) into
        the fingerprint stream (num/fingerprint flight event).  Costs
        one bounded host fetch — only called at the configured
        interval / checkpoint boundaries; never raises into the step."""
        from deepspeed_tpu.telemetry.numerics import state_fingerprint
        try:
            digest = state_fingerprint(
                self.state["params"], np.asarray(self._rng),
                step=self.global_steps, loss=loss)
        except Exception as e:
            logger.debug(f"numerics: fingerprint failed ({e})")
            return None
        return self.numerics.record_fingerprint(self.global_steps, digest)

    def _nonfinite_fault_group(self):
        """The ``train.nonfinite`` chaos site (ISSUE 15): a ``deny``
        fault whose param names the leaf-group index to NaN-poison this
        step (``train.nonfinite:deny=2@4`` — inject into group 2 at the
        5th step).  Fires only on the fused path with numerics armed
        (the injection rides the in-graph stats' leaf grouping)."""
        inj = self.fault_injector
        if not inj or self._num_leaf_group is None:
            return None
        if not inj.deny("train.nonfinite"):
            return None
        spec = next((s for s in inj.specs
                     if s.site == "train.nonfinite"), None)
        g = int(spec.param) if spec is not None and spec.param is not None \
            else 0
        return g % max(len(self._num_groups), 1)

    def _record_step_telemetry(self, duration_s: float):
        """Per-step registry update + monitor bridge (ISSUE 4).
        ``duration_s`` is what the ``train_batch`` call took to return —
        a dispatch time on an asynchronous device: it feeds the latency
        histogram, the flight recorder and the anomaly detector, which
        watch for a wedged host step.  The rates (tokens/s, model FLOP/s
        as ``flops_per_token × tokens``, the Megatron 6N convention the
        in-tree models declare, and MFU against the local devices' peak)
        are written only on a step whose ``tput_timer.stop`` has just
        waited for the device: the tokens dispatched since the previous
        such stop over the timer's window between the two."""
        tcfg = self._config.telemetry_config
        if not tcfg.enabled:
            return
        reg = self.telemetry_registry
        reg.inc("train/steps")
        reg.histogram("train/step_latency_s").observe(duration_s)
        # flight-recorder step event + rolling anomaly check (ISSUE 7);
        # corr matches the train/step span id so the black-box record,
        # the trace, and any anomaly instant cross-reference
        corr = f"train-step-{self.global_steps}"
        self.flightrec.record("train/step", corr=corr,
                              step=self.global_steps,
                              dur_ms=round(duration_s * 1e3, 3))
        self.anomaly.observe("train.step", duration_s, corr=corr)
        if self._commstat is not None and self._comm_step_window:
            # close the per-step collective window (ISSUE 19): publishes
            # comm/overlap_fraction and the comm/step flight event
            self._commstat.step_end(duration_s, corr=corr)
        if self._mem_on:
            # memory observatory (ISSUE 14): mem/* gauges + the HBM
            # used-fraction anomaly feed (a leak flags before the OOM)
            get_memory_ledger().publish_and_feed(reg, self.anomaly,
                                                 corr=corr)
        self._tokens_since_sync += (self.train_batch_size()
                                    * max(self._last_seq_len, 0))
        window_s = self.tput_timer.synced_window_s
        if window_s:
            tokens, self._tokens_since_sync = self._tokens_since_sync, 0
            flops = tokens * (getattr(self.model, "flops_per_token", None)
                              or 0.0)
            if tokens:
                reg.set_gauge("train/tokens_per_s", tokens / window_s)
            if flops:
                reg.set_gauge("train/model_flops_per_s", flops / window_s)
                if self._peak_flops:
                    from deepspeed_tpu.telemetry import mfu as _mfu
                    val = _mfu(flops, window_s, self._peak_flops)
                    if val is not None:
                        reg.set_gauge("train/mfu", val)
        if (self.monitor is not None and self.monitor.enabled
                and tcfg.monitor_interval
                and self.global_steps % tcfg.monitor_interval == 0):
            self.monitor.write_events(reg.to_events(self.global_steps))

    def log_comms_summary(self, show_straggler: bool = False):
        """Print the comms summary AND write it through the monitor
        sinks (ISSUE 4 satellite: CommsLogger output as monitor events,
        not log-only)."""
        from deepspeed_tpu import comm as _comm
        sink = (self.monitor
                if self.monitor is not None and self.monitor.enabled
                else None)
        _comm.log_summary(monitor=sink, step=self.global_steps,
                          show_straggler=show_straggler)

    # ------------------------------------------------------------------ checkpoint
    def _get_checkpoint_engine(self):
        """Resolve the pluggable backend (reference engine.py:897): a
        client-set ``engine.checkpoint_engine`` wins; else config
        ``checkpoint.async_save`` selects the async Orbax engine (the
        Nebula-equivalent), else the synchronous Orbax default."""
        if self.checkpoint_engine is None:
            from deepspeed_tpu.runtime.checkpoint_engine.engine import (
                AsyncOrbaxCheckpointEngine, OrbaxCheckpointEngine)
            if self._config.checkpoint_config.async_save:
                self.checkpoint_engine = AsyncOrbaxCheckpointEngine()
            else:
                self.checkpoint_engine = OrbaxCheckpointEngine()
        return self.checkpoint_engine

    def wait_pending_checkpoint(self):
        """Block until an in-flight async save is durable, then publish it
        (manifest → atomic tag rename → ``latest`` pointer → retention).
        No-op for sync engines / no pending save.  Called automatically
        before the next save/load, so at most one save overlaps
        training."""
        if self._pending_ckpt is None:
            return
        tag, aux_thread, finalize = self._pending_ckpt
        self._pending_ckpt = None
        if aux_thread is not None:
            aux_thread.join()
        self._get_checkpoint_engine().commit(tag)
        ckpt_dir = finalize()
        log_dist(f"committed checkpoint {ckpt_dir}", ranks=[0])

    def _ckpt_retry(self, fn, *args, describe="", **kwargs):
        """All checkpoint I/O goes through the shared retry policy
        (resilience/retry.py: exponential backoff + jitter + deadline)."""
        from deepspeed_tpu.resilience.retry import retry_call
        r = self._config.resilience_config.retry
        return retry_call(fn, *args, attempts=r.attempts,
                          base_delay_s=r.base_delay_s,
                          max_delay_s=r.max_delay_s,
                          deadline_s=r.deadline_s,
                          describe=describe, **kwargs)

    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True):
        """Crash-safe save (resilience/ckpt.py protocol): everything is
        staged under ``<tag>.tmp`` and published by one atomic rename
        AFTER the fsynced manifest lands, so a crash at any point leaves
        either the previous checkpoint set intact or the new tag fully
        durable — never a torn tag that ``latest`` resolves to."""
        from deepspeed_tpu.runtime.checkpoint_engine.engine import (
            METADATA_FILE, STATE_DIR)
        from deepspeed_tpu.resilience import ckpt as rckpt
        import shutil
        self.wait_pending_checkpoint()
        ckpt_engine = self._get_checkpoint_engine()
        inj = self.fault_injector
        rcfg = self._config.resilience_config
        step = self.global_steps
        tag = tag or f"global_step{step}"
        ckpt_dir = os.path.join(save_dir, str(tag))
        tmp_dir = ckpt_dir + rckpt.TMP_SUFFIX
        extra = {
            "global_steps": step,
            "global_samples": self.global_samples,
            "skipped_steps": self.skipped_steps,
            "micro_steps": self.micro_steps,
            # host-side rng chain: restoring it makes a resumed run
            # bitwise-identical to one that never crashed (dropout and
            # any other trained stochasticity included)
            "rng_key": np.asarray(self._rng).tolist(),
            "client_state": client_state or {},
            "config": self._config._param_dict,
        }
        is_rank0 = jax.process_index() == 0
        is_async = getattr(ckpt_engine, "is_async", False)
        if is_rank0 and os.path.isdir(tmp_dir):
            shutil.rmtree(tmp_dir)          # staging left by a crashed save
        os.makedirs(tmp_dir, exist_ok=True)
        # manifest leaf summary now, while the state snapshot is coherent
        # (the async engine's caller may mutate/donate state immediately
        # after save returns); checksums cost one host fetch — disable via
        # resilience.checkpoint_checksums for bandwidth-bound saves.  On
        # the async path the fetch doubles as the engine's donation-safe
        # snapshot, so manifest + save share ONE device->host transfer
        # (the async engine skips its own copy for an all-numpy tree).
        save_src = self.state
        if is_async and rcfg.checkpoint_checksums:
            import numpy as _np
            save_src = jax.tree.map(lambda a: _np.array(a, copy=True),
                                    self.state)
        self._last_save_dir = save_dir
        if self.numerics is not None:
            # determinism fingerprint stamped into the manifest
            # (ISSUE 15): load_checkpoint recomputes it from the
            # restored state, so a perturbed/corrupted restore is
            # flagged at restore time (num/fingerprint_mismatch)
            try:
                from deepspeed_tpu.telemetry.numerics import \
                    state_fingerprint
                extra["numerics_fingerprint"] = {
                    "step": step,
                    "digest": state_fingerprint(
                        save_src["params"], np.asarray(self._rng),
                        step=step)}
                self.numerics.record_fingerprint(
                    step, extra["numerics_fingerprint"]["digest"],
                    source="checkpoint")
            except Exception as e:
                logger.debug(f"numerics: save fingerprint failed ({e})")
        ckpt_corr = f"ckpt-{tag}"
        ckpt_t0 = time.perf_counter()
        with self.tracer.span("ckpt/stage", cat="ckpt", corr=ckpt_corr,
                              args={"tag": str(tag), "step": step,
                                    "async": bool(is_async)}):
            leaves = rckpt.leaf_summary(
                save_src, checksums=rcfg.checkpoint_checksums)
            ckpt_engine.create(tag)
            inj.check("ckpt.save")
            self._ckpt_retry(ckpt_engine.save, save_src,
                             os.path.join(tmp_dir, STATE_DIR),
                             describe=f"checkpoint save {tag}")
            if is_rank0:
                import json as _json
                with open(os.path.join(tmp_dir, METADATA_FILE), "w") as f:
                    _json.dump(extra, f, indent=2, default=str)
        is_async = getattr(ckpt_engine, "is_async", False)
        # host-side optimizer tiers: snapshot synchronously (their pinned /
        # in-place buffers mutate every step), serialize alongside the
        # Orbax write — in the background when async
        import numpy as np_
        aux_flats = {}
        if self.streamed_optimizer is not None:
            aux_flats["streamed_optimizer.npz"] = \
                self.streamed_optimizer.npz_state()
        if self.host_optimizer is not None:
            sd = self.host_optimizer.state_dict()
            flat = {"step_count": np_.int64(sd["step_count"])}
            for p, arr in sd["master"].items():
                flat[f"master::{p}"] = np_.array(arr, copy=is_async)
            for p, moments in sd["moments"].items():
                for j, mbuf in enumerate(moments):
                    flat[f"moment{j}::{p}"] = np_.array(mbuf, copy=is_async)
            aux_flats["host_optimizer.npz"] = flat

        aux_errs = []

        def _write_aux():
            try:
                inj.check("ckpt.aux")
                for name, payload in aux_flats.items():
                    self._ckpt_retry(
                        np_.savez, os.path.join(tmp_dir, name), **payload,
                        describe=f"checkpoint aux {name}")
            except BaseException as e:       # surfaces at finalize time
                aux_errs.append(e)

        def _finalize():
            """Publish: manifest (fsynced, LAST staged write) → atomic
            tag rename → atomic ``latest`` → retention GC.  Any failure
            before the rename leaves only the .tmp staging dir."""
            if aux_errs:
                raise aux_errs[0]
            with self.tracer.span("ckpt/publish", cat="ckpt",
                                  corr=ckpt_corr,
                                  args={"tag": str(tag), "step": step}):
                return _publish()

        def _publish():
            if is_rank0:
                rckpt.write_manifest(tmp_dir, step, tag, leaves,
                                     injector=inj)
                if os.path.isdir(ckpt_dir):
                    # overwriting an existing tag: the old one moves to
                    # `<tag>.prev` — deliberately NOT a .tmp name, so if
                    # we crash inside the window between the two renames
                    # it is still a discoverable, verifying tag and the
                    # fallback scan restores it (a .tmp name would hide
                    # BOTH checkpoints and the next GC would sweep them)
                    stale = ckpt_dir + ".prev"
                    if os.path.isdir(stale):
                        shutil.rmtree(stale)
                    os.replace(ckpt_dir, stale)
                    inj.check("ckpt.publish")    # the crash window
                    os.replace(tmp_dir, ckpt_dir)
                else:
                    inj.check("ckpt.publish")
                    os.replace(tmp_dir, ckpt_dir)
                # the new tag is durable: drop the displaced old copy —
                # including one left by a previous crashed overwrite
                shutil.rmtree(ckpt_dir + ".prev", ignore_errors=True)
                try:
                    rckpt.fsync_path(save_dir)
                except OSError:
                    pass
                if save_latest:
                    self._ckpt_retry(rckpt.publish_latest, save_dir, tag,
                                     injector=inj,
                                     describe="latest pointer")
                if rcfg.keep_last_k:
                    rckpt.gc_tags(save_dir, rcfg.keep_last_k,
                                  protect=(str(tag),))
            return ckpt_dir

        if is_async:
            # commit + publish are deferred until the background
            # serialization finishes (wait_pending_checkpoint); training
            # continues immediately against the already-snapshotted state
            import atexit
            import threading
            import weakref
            aux_thread = None
            if aux_flats:
                aux_thread = threading.Thread(target=_write_aux,
                                              daemon=False)
                aux_thread.start()
            self._pending_ckpt = (tag, aux_thread, _finalize)
            if not getattr(self, "_ckpt_atexit", False):
                # the last save of a run must still publish even if the
                # script exits without another checkpoint call
                ref = weakref.ref(self)
                atexit.register(
                    lambda: ref() and ref().wait_pending_checkpoint())
                self._ckpt_atexit = True
            log_dist(f"async checkpoint {ckpt_dir} in flight", ranks=[0])
            # for async saves the histogram records what training
            # actually blocked on: the synchronous staging portion
            self.telemetry_registry.histogram(
                "ckpt/save_duration_s").observe(
                    time.perf_counter() - ckpt_t0)
            self.telemetry_registry.inc("ckpt/saves")
            return True
        _write_aux()
        ckpt_engine.commit(tag)
        _finalize()
        self.telemetry_registry.histogram("ckpt/save_duration_s").observe(
            time.perf_counter() - ckpt_t0)
        self.telemetry_registry.inc("ckpt/saves")
        log_dist(f"saved checkpoint {ckpt_dir}", ranks=[0])
        return True

    def load_checkpoint(self, load_dir, tag=None,
                        load_optimizer_states=True,
                        load_lr_scheduler_states=True,
                        load_module_only=False):
        from deepspeed_tpu.runtime.checkpoint_engine.engine import (
            METADATA_FILE, STATE_DIR)
        from deepspeed_tpu.resilience import ckpt as rckpt
        from deepspeed_tpu.resilience.ckpt import CheckpointCorruptError
        self.wait_pending_checkpoint()
        ckpt_engine = self._get_checkpoint_engine()
        verify = self._config.resilience_config.verify_checkpoint
        if tag is None:
            if verify == "off":
                tag = rckpt.read_latest(load_dir)
            else:
                # crash-safe resolution: the `latest` pointer when it
                # names a verifying tag, else the newest valid tag (a
                # torn pointer or corrupted tag never fails the restore
                # while any valid tag exists)
                tag = rckpt.find_valid_tag(load_dir)
            if tag is None:
                log_dist(f"no restorable checkpoint in {load_dir}",
                         ranks=[0])
                return None, {}
        elif verify != "off":
            ok, reason = rckpt.verify_tag(os.path.join(load_dir, str(tag)))
            if not ok:
                raise CheckpointCorruptError(
                    f"requested tag {tag!r} in {load_dir} failed "
                    f"verification: {reason}")
        ckpt_dir = os.path.join(load_dir, str(tag))
        restore_t0 = time.perf_counter()
        with self.tracer.span("ckpt/restore", cat="ckpt",
                              corr=f"ckpt-{tag}",
                              args={"tag": str(tag), "verify": verify}):
            state = self._ckpt_retry(
                ckpt_engine.load, os.path.join(ckpt_dir, STATE_DIR),
                template=self.state, shardings=self.state_shardings,
                describe=f"checkpoint load {tag}")
            if verify == "full":
                mismatches = rckpt.verify_restored(
                    state, rckpt.read_manifest(ckpt_dir))
                if mismatches:
                    raise CheckpointCorruptError(
                        f"tag {tag!r} failed checksum verification: "
                        f"{mismatches[:5]}")
        self.telemetry_registry.histogram(
            "ckpt/restore_duration_s").observe(
                time.perf_counter() - restore_t0)
        self.telemetry_registry.inc("ckpt/restores")
        if not (load_optimizer_states and not load_module_only):
            state = {**state, "opt_state": self.state["opt_state"]}
        extra = {}
        meta_path = os.path.join(ckpt_dir, METADATA_FILE)
        if os.path.exists(meta_path):
            import json as _json
            with open(meta_path) as f:
                extra = _json.load(f)
        self.state = state
        streamed_path = os.path.join(ckpt_dir, "streamed_optimizer.npz")
        if (self.streamed_optimizer is not None
                and os.path.exists(streamed_path)
                and load_optimizer_states and not load_module_only):
            self.streamed_optimizer.load_npz(streamed_path)
        host_path = os.path.join(ckpt_dir, "host_optimizer.npz")
        if self.host_optimizer is not None and os.path.exists(host_path) \
                and load_optimizer_states and not load_module_only:
            import numpy as np_
            flat = np_.load(host_path)
            sd = {"master": {}, "moments": {},
                  "step_count": int(flat["step_count"])}
            for key in flat.files:
                if key.startswith("master::"):
                    sd["master"][key[len("master::"):]] = flat[key]
                elif key.startswith("moment"):
                    j, p = key.split("::", 1)
                    sd["moments"].setdefault(p, {})[int(j[len("moment"):])] = \
                        flat[key]
            sd["moments"] = {p: [d[j] for j in sorted(d)]
                             for p, d in sd["moments"].items()}
            self.host_optimizer.load_state_dict(sd)
            if self._param_nvme:
                # rebuild the NVMe shard store from the restored fp32
                # masters — bit-identical to the saved payloads (stored
                # shards are master.astype(compute_dtype))
                for i in range(self._num_layers):
                    self.param_store.put_layer(i, self._reload_layer(i))
                self.param_store.flush()
        self.global_steps = extra.get("global_steps", 0)
        self.global_samples = extra.get("global_samples", 0)
        self.skipped_steps = extra.get("skipped_steps", 0)
        self.micro_steps = extra.get("micro_steps", 0)
        if extra.get("rng_key") is not None:
            self._rng = jnp.asarray(extra["rng_key"],
                                    dtype=self._rng.dtype)
        fp = extra.get("numerics_fingerprint")
        if fp and self.numerics is not None and not load_module_only:
            # fingerprint audit (ISSUE 15): recompute the digest from
            # the restored state and compare against the manifest stamp
            # — restore==uninterrupted becomes a checked claim, and a
            # deliberately perturbed restore is flagged loudly
            try:
                from deepspeed_tpu.telemetry.numerics import \
                    state_fingerprint
                actual = state_fingerprint(
                    self.state["params"], np.asarray(self._rng),
                    step=self.global_steps)
                ok = self.numerics.record_restore_audit(
                    self.global_steps, fp.get("digest", ""), actual)
                if not ok:
                    logger.warning(
                        f"numerics: restored state fingerprint MISMATCH "
                        f"for tag {tag!r} at step {self.global_steps} — "
                        f"the restored state is not the state that was "
                        f"saved (expected {fp.get('digest')}, got "
                        f"{actual})")
            except Exception as e:
                logger.debug(f"numerics: restore audit failed ({e})")
        log_dist(f"loaded checkpoint {ckpt_dir}", ranks=[0])
        return ckpt_dir, extra.get("client_state", {})

    # ------------------------------------------------------------------ misc api
    def compute_eigenvalue(self, batch, rng=None):
        """Top Hessian eigenvalue of the loss (reference engine.py:2085,
        scheduled by the eigenvalue config for MoQ)."""
        from deepspeed_tpu.runtime.eigenvalue import Eigenvalue
        ec = self._config.eigenvalue_config
        ev = Eigenvalue(verbose=ec.verbose, max_iter=ec.max_iter, tol=ec.tol,
                        stability=ec.stability,
                        gas_boundary_resolution=ec.gas_boundary_resolution)
        batch = self._shard_batch(batch, stacked=False)
        rng = rng if rng is not None else self._next_rng()

        def loss_fn(p):
            return step_programs.scaled_loss(self._step_ctx, p, batch, rng,
                                             jnp.float32(1.0))

        return ev.compute_eigenvalue(loss_fn, self.state["params"])

    def get_global_grad_norm(self):
        gn = self.last_metrics.get("grad_norm")
        return float(gn) if gn is not None else None

    def module_state_dict(self):
        return self.state["params"]

    def deepspeed_io(self, dataset, batch_size=None, collate_fn=None, **kw):
        from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader
        return DeepSpeedDataLoader(
            dataset,
            batch_size=batch_size or (self.train_micro_batch_size_per_gpu() *
                                      self.topology.dp_world_size),
            collate_fn=collate_fn or self.collate_fn)
