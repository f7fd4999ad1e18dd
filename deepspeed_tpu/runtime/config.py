"""DeepSpeed-style JSON config system (reference: deepspeed/runtime/config.py:666
``DeepSpeedConfig`` aggregating ~30 subsystem configs at :773-876, plus the
batch-size triangulation at :911-933).

The same JSON keys are accepted; TPU-specific additions live under the ``"mesh"``
section (parallel dimension sizes), since the reference delegates TP/PP topology to
the client mpu / PipelineModule rather than the JSON.
"""
import json
import os
from typing import Any, Dict, Optional, Union

from pydantic import Field

from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigModel
from deepspeed_tpu.runtime import constants as C
from deepspeed_tpu.utils.logging import logger


# --------------------------------------------------------------------------- fp16/bf16
class FP16Config(DeepSpeedConfigModel):
    """reference: runtime/fp16 config keys (config.py fp16 section)."""
    enabled: bool = False
    auto_cast: bool = False
    loss_scale: float = 0.0           # 0 = dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    consecutive_hysteresis: bool = False
    min_loss_scale: float = 1.0


class BF16Config(DeepSpeedConfigModel):
    enabled: bool = False
    # accumulate gradients in fp32 master buffers (reference bf16_optimizer)
    immediate_grad_update: bool = False
    # TPU-native extensions (runtime/bf16_optimizer.py): the optimizer
    # phase is HBM-streaming-bound, so state dtypes are the lever.
    # "bfloat16" masters are Kahan-compensated (no silent update loss);
    # moments in bf16 keep fp32 math and fp32's exponent range.
    master_weights_dtype: str = "float32"      # float32 | bfloat16 (Kahan)
    optimizer_states_dtype: Optional[str] = None   # None=float32 | bfloat16


# --------------------------------------------------------------------------- zero
class OffloadParamConfig(DeepSpeedConfigModel):
    """reference: runtime/zero/offload_config.py DeepSpeedZeroOffloadParamConfig."""
    device: str = "none"              # none | cpu | nvme
    nvme_path: Optional[str] = None
    buffer_count: int = 5
    buffer_size: int = 100_000_000
    max_in_cpu: int = 1_000_000_000
    pin_memory: bool = False
    # nvme tier only (ISSUE 17): K-layer resident working set for the
    # streamed-param pipeline (double buffer needs >= 2: compute layer +
    # prefetch target).  DS_PARAM_RESIDENT_LAYERS overrides at runtime.
    resident_layers: int = 2


class OffloadOptimizerConfig(DeepSpeedConfigModel):
    device: str = "none"              # none | cpu | nvme
    nvme_path: Optional[str] = None
    buffer_count: int = 4
    pin_memory: bool = False
    pipeline_read: bool = False
    pipeline_write: bool = False
    fast_init: bool = False
    ratio: float = 1.0


class ZeroConfig(DeepSpeedConfigModel):
    """reference: runtime/zero/config.py:81 DeepSpeedZeroConfig."""
    stage: int = 0
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = 500_000_000
    allgather_partitions: bool = True
    allgather_bucket_size: int = 500_000_000
    overlap_comm: Optional[bool] = None
    load_from_fp32_weights: bool = True
    elastic_checkpoint: bool = False
    offload_param: Optional[OffloadParamConfig] = None
    offload_optimizer: Optional[OffloadOptimizerConfig] = None
    sub_group_size: int = 1_000_000_000
    cpu_offload: Optional[bool] = None   # deprecated bool; migrated below
    prefetch_bucket_size: int = 50_000_000
    param_persistence_threshold: int = 100_000
    model_persistence_threshold: int = 2 ** 62
    max_live_parameters: int = 1_000_000_000
    max_reuse_distance: int = 1_000_000_000
    gather_16bit_weights_on_model_save: bool = False
    ignore_unused_parameters: bool = True
    round_robin_gradients: bool = False
    # ZeRO++ (reference engine.py:825-834)
    zero_hpz_partition_size: int = 1
    zero_quantized_weights: bool = False
    zero_quantized_nontrainable_weights: bool = False
    zero_quantized_gradients: bool = False
    mics_shard_size: int = -1
    mics_hierarchical_params_gather: bool = False
    memory_efficient_linear: bool = True

    def __init__(self, **data):
        # reference deprecation: cpu_offload=True ≙ offload_optimizer.device=cpu
        if data.get("cpu_offload") and "offload_optimizer" not in data:
            logger.warning("zero_optimization.cpu_offload is deprecated; use "
                           "offload_optimizer: {device: cpu}")
            data["offload_optimizer"] = {"device": "cpu"}
        # reference JSON spells the stage-3 knobs with a stage3_ prefix
        # (runtime/zero/config.py aliases)
        for ref_key in ("prefetch_bucket_size", "param_persistence_threshold",
                        "model_persistence_threshold", "max_live_parameters",
                        "max_reuse_distance",
                        "gather_16bit_weights_on_model_save"):
            alias = f"stage3_{ref_key}"
            if alias in data and ref_key not in data:
                data[ref_key] = data.pop(alias)
            else:
                data.pop(alias, None)
        super().__init__(**data)


# --------------------------------------------------------------------------- mesh (TPU)
class MeshConfig(DeepSpeedConfigModel):
    """TPU-native addition: named-axis parallel dims for the device mesh."""
    model_parallel_size: int = 1
    pipe_parallel_size: int = 1
    sequence_parallel_size: int = 1
    sequence_parallel_impl: str = "ulysses"    # "ulysses" | "ring"
    expert_parallel_size: int = 1
    data_parallel_size: Optional[int] = None   # inferred from device count


# --------------------------------------------------------------------------- aux
class ActivationCheckpointingConfig(DeepSpeedConfigModel):
    """reference: runtime/activation_checkpointing/checkpointing.py:789 configure."""
    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU-native knob: jax.checkpoint policy name
    policy: str = "nothing_saveable"


class FlopsProfilerConfig(DeepSpeedConfigModel):
    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


class CommsLoggerConfig(DeepSpeedConfigModel):
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: list = Field(default_factory=list)


class TensorBoardConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


class WandbConfig(DeepSpeedConfigModel):
    enabled: bool = False
    group: Optional[str] = None
    team: Optional[str] = None
    project: str = "deepspeed"


class CSVConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


class MonitorConfig(DeepSpeedConfigModel):
    tensorboard: TensorBoardConfig = Field(default_factory=TensorBoardConfig)
    wandb: WandbConfig = Field(default_factory=WandbConfig)
    csv_monitor: CSVConfig = Field(default_factory=CSVConfig)


class AioConfig(DeepSpeedConfigModel):
    block_size: int = 1048576
    queue_depth: int = 8
    thread_count: int = 1
    single_submit: bool = False
    overlap_events: bool = True


class CurriculumParams(DeepSpeedConfigModel):
    min_difficulty: int = 8
    max_difficulty: int = 1024
    schedule_type: str = "fixed_linear"
    schedule_config: Dict[str, Any] = Field(default_factory=dict)


class CurriculumLearningConfig(DeepSpeedConfigModel):
    enabled: bool = False
    curriculum_type: str = "seqlen"
    min_difficulty: int = 8
    max_difficulty: int = 1024
    schedule_type: str = "fixed_linear"
    schedule_config: Dict[str, Any] = Field(default_factory=dict)
    #: TPU-specific, opt-in: every distinct truncated sequence length
    #: compiles a fresh step; a bucket > 1 rounds the effective seqlen UP
    #: to a multiple, bounding compiles at max_difficulty/bucket while the
    #: schedule moves in fine steps.  0 (default) keeps the reference's
    #: exact truncation semantics — the engine warns when a fine schedule
    #: would compile per difficulty value.
    seqlen_bucket: int = 0


class EigenvalueConfig(DeepSpeedConfigModel):
    enabled: bool = False
    verbose: bool = False
    max_iter: int = 100
    tol: float = 1e-2
    stability: float = 1e-6
    gas_boundary_resolution: int = 1
    layer_name: str = "bert.encoder.layer"
    layer_num: int = 0


class PLDConfig(DeepSpeedConfigModel):
    enabled: bool = False
    theta: float = 1.0
    gamma: float = 0.001


class DebugConfig(DeepSpeedConfigModel):
    """Sanitizer tier (SURVEY §5 race-detection/sanitizers row): TPU has no
    CUDA memcheck equivalent; the failure class that matters under XLA is
    numerics (NaN/Inf born inside a fused kernel).  ``debug_nans`` flips
    ``jax_debug_nans`` — every primitive re-checks and the faulting op is
    reported (compile-time cost: functions re-run eagerly on failure).
    ``sanitize_gradients`` adds a per-step device-side finite check on the
    global grad norm and raises with step context on failure."""
    debug_nans: bool = False
    sanitize_gradients: bool = False


class ElasticityConfig(DeepSpeedConfigModel):
    enabled: bool = False
    max_train_batch_size: int = 2000
    micro_batch_sizes: list = Field(default_factory=lambda: [2, 4, 6])
    min_gpus: int = 1
    max_gpus: int = 10000
    min_time: int = 0
    version: float = 0.2
    ignore_non_elastic_batch_info: bool = False
    num_gpus_per_node: int = 1
    model_parallel_size: int = 1


class CheckpointConfig(DeepSpeedConfigModel):
    tag_validation: str = "Warn"
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write: Dict[str, Any] = Field(default_factory=dict)
    # TPU-native: async orbax-style checkpointing
    async_save: bool = False


class DataTypesConfig(DeepSpeedConfigModel):
    grad_accum_dtype: Optional[str] = None


class RetryConfig(DeepSpeedConfigModel):
    """Backoff policy for checkpoint I/O (resilience/retry.py
    retry_call: exponential backoff + full jitter + deadline)."""
    attempts: int = 4
    base_delay_s: float = 0.05
    max_delay_s: float = 2.0
    #: wall-clock budget across all attempts; None = attempts-bounded only
    deadline_s: Optional[float] = None

    def __init__(self, **data):
        super().__init__(**data)
        if self.attempts < 1:
            raise ValueError(
                f"resilience.retry.attempts={self.attempts}: must be >= 1")
        if self.base_delay_s < 0 or self.max_delay_s < 0:
            raise ValueError("resilience.retry delays must be >= 0")
        if self.deadline_s is not None and self.deadline_s <= 0:
            raise ValueError(
                f"resilience.retry.deadline_s={self.deadline_s}: must be "
                "> 0 (omit for no deadline)")


class OffloadIntegrityConfig(DeepSpeedConfigModel):
    """``resilience.offload`` — storage integrity for the offload
    substrate (ISSUE 18): payload checksums, aio retry policy, and the
    per-tier circuit breaker the SwapEngine runs (offload/engine.py,
    offload/breaker.py)."""
    #: compute + store a crc32 per payload at swap-out (both tiers)
    checksums: bool = True
    #: verify the stored crc32 on every fetch; False is the hot-path
    #: escape hatch (checksums still stored) if the measured tax on the
    #: prefetch path matters — see PERF.md PR 18
    verify_fetch: bool = True
    #: bounded-backoff resubmission of failed aio submits/reaps
    #: (resilience/retry.retry_call); only post-retry verdicts feed the
    #: breaker.  Delays are aio-scale, not checkpoint-scale.
    retry_attempts: int = 3
    retry_base_delay_s: float = 0.002
    retry_max_delay_s: float = 0.05
    retry_deadline_s: Optional[float] = None
    #: rolling-window breaker: OPEN when >= error_rate of the last
    #: `window` terminal outcomes failed (after at least min_ops);
    #: HALF_OPEN after cooldown_s admits `probes` real ops
    breaker_window: int = 16
    breaker_error_rate: float = 0.5
    breaker_min_ops: int = 4
    breaker_cooldown_s: float = 30.0
    breaker_probes: int = 1

    def __init__(self, **data):
        super().__init__(**data)
        if self.retry_attempts < 1:
            raise ValueError(
                f"resilience.offload.retry_attempts={self.retry_attempts}: "
                "must be >= 1")
        if self.retry_base_delay_s < 0 or self.retry_max_delay_s < 0:
            raise ValueError("resilience.offload retry delays must be >= 0")
        if not 0.0 < self.breaker_error_rate <= 1.0:
            raise ValueError(
                f"resilience.offload.breaker_error_rate="
                f"{self.breaker_error_rate}: must be in (0, 1]")
        if self.breaker_window < 1 or self.breaker_min_ops < 1 \
                or self.breaker_probes < 1:
            raise ValueError("resilience.offload breaker window/min_ops/"
                             "probes must be >= 1")
        if self.breaker_cooldown_s < 0:
            raise ValueError(
                f"resilience.offload.breaker_cooldown_s="
                f"{self.breaker_cooldown_s}: must be >= 0")


class ResilienceConfig(DeepSpeedConfigModel):
    """Fault tolerance (deepspeed_tpu/resilience/): crash-safe
    checkpoint protocol knobs + deterministic fault injection.  TPU-
    native framing of the reference's nebula/elasticity durability
    features."""
    #: fault-injection spec string (resilience/faults.py grammar);
    #: DS_FAULTS env specs are appended to these
    faults: str = ""
    #: retain only the newest k VALID checkpoint tags after each publish
    #: (0 = keep everything); the fallback tag is never deleted
    keep_last_k: int = 0
    #: record per-leaf crc32s in the checkpoint manifest (costs one host
    #: fetch of the state at save time; shapes/dtypes are always recorded)
    checkpoint_checksums: bool = True
    #: where crash/stall post-mortem bundles land (ISSUE 7):
    #: ``postmortem-<step|ts>/`` directories with the flight-recorder
    #: drain, metrics snapshot, thread stacks, scheduler state, and the
    #: flushed trace.  None = subsystem default placement (serving:
    #: ``./postmortems``; training: next to the checkpoints in
    #: ``save_dir``).  "" disables bundle writing entirely.
    postmortem_dir: Optional[str] = None
    #: load-time verification: "off", "manifest" (structural: the
    #: manifest parses and its file inventory matches on disk), or
    #: "full" (also re-checksums every restored leaf)
    verify_checkpoint: str = "manifest"
    retry: RetryConfig = Field(default_factory=RetryConfig)
    #: offload-substrate integrity (checksums / aio retry / tier
    #: breaker) — consumed by the SwapEngine (ISSUE 18)
    offload: OffloadIntegrityConfig = Field(
        default_factory=OffloadIntegrityConfig)

    def __init__(self, **data):
        if isinstance(data.get("retry"), dict):
            data["retry"] = RetryConfig(**data["retry"])
        if isinstance(data.get("offload"), dict):
            data["offload"] = OffloadIntegrityConfig(**data["offload"])
        super().__init__(**data)
        # parse eagerly so a typo'd spec fails at config time, not at the
        # fault site mid-run
        from deepspeed_tpu.resilience.faults import parse_spec
        parse_spec(self.faults)
        if self.keep_last_k < 0:
            raise ValueError(
                f"resilience.keep_last_k={self.keep_last_k}: must be >= 0 "
                "(0 = keep all tags)")
        if self.verify_checkpoint not in ("off", "manifest", "full"):
            raise ValueError(
                f"resilience.verify_checkpoint={self.verify_checkpoint!r}: "
                "choose from 'off', 'manifest', 'full'")


class NumericsConfig(DeepSpeedConfigModel):
    """``telemetry.numerics`` — the training-health observatory
    (ISSUE 15): in-graph per-leaf-group grad norms + non-finite
    provenance banked lazily beside the overflow flag, MAD anomaly
    feeds over grad-norm/loss/update-ratio, and periodic determinism
    fingerprints (``num/*`` gauges, ``/debug/numerics``, post-mortem
    ``numerics.json``)."""
    #: master switch for the in-graph stats + banking; DS_NUMERICS env
    #: wins.  Off restores the bare grad_norm/overflow scalar pair.
    enabled: bool = True
    #: record a blake2 state fingerprint (sampled param leaves + rng
    #: chain + loss) every N steps as a ``num/fingerprint`` flight
    #: event; 0 disables the periodic stream (checkpoint manifests are
    #: always stamped while numerics is on).  DS_FINGERPRINT_INTERVAL
    #: env wins.
    fingerprint_interval: int = 0
    #: leaf-grouping depth: param-tree path components that name a
    #: group ("blocks/attn_w"); deeper = finer provenance, more
    #: in-graph scatter-adds
    group_depth: int = 2
    #: resolved per-step entries retained for the /debug/numerics
    #: timeline (loss / grad_norm / loss_scale / update_ratio)
    history: int = 512

    def __init__(self, **data):
        super().__init__(**data)
        if self.fingerprint_interval < 0:
            raise ValueError(
                f"telemetry.numerics.fingerprint_interval="
                f"{self.fingerprint_interval}: must be >= 0 (0 disables "
                "the periodic fingerprint)")
        if self.group_depth < 1:
            raise ValueError(
                f"telemetry.numerics.group_depth={self.group_depth}: "
                "must be >= 1")
        if self.history < 16:
            raise ValueError(
                f"telemetry.numerics.history={self.history}: must be "
                ">= 16")


class CommConfig(DeepSpeedConfigModel):
    """``telemetry.comm`` — the communication observatory (ISSUE 19):
    process-wide CommStat (per-op latency/GB-s accounting, MAD anomaly
    feed ``anomaly/comm_*``), the engine's per-step collective window
    with comm/compute overlap attribution, ``/debug/comm``, and the
    post-mortem ``comm.json``.  ``DS_COMMSTAT`` env wins."""
    #: master switch for the CommStat accounting + the comm debug
    #: surfaces; off leaves only the CommsLogger summary path
    enabled: bool = True
    #: per-train-step collective window (overlap meter + the
    #: ``comm.collective`` fault gate); requires ``enabled``
    step_window: bool = True


class TelemetryConfig(DeepSpeedConfigModel):
    """Unified telemetry (deepspeed_tpu/telemetry/): metrics registry +
    Prometheus exposition, Chrome-trace span tracer, MFU/goodput gauges.
    TPU-native framing of the reference's monitor/comms/flops trio as
    ONE cross-cutting layer (docs/tutorials/monitoring-profiling.md)."""
    #: master switch for the per-step registry updates (spans still obey
    #: the trace path: an armed DS_TRACE traces even with metrics off)
    enabled: bool = True
    #: Chrome-trace output path; the DS_TRACE env var overrides (the
    #: repo's env-wins convention).  None/"" = no tracing.
    trace: Optional[str] = None
    #: opt-in training-side /metrics HTTP endpoint: None = off,
    #: 0 = ephemeral port (tests), N = fixed port.  Serving already
    #: exposes the same exposition through ds_serve /metrics.
    metrics_port: Optional[int] = None
    #: steps between draining the registry into the Monitor sinks
    #: (tensorboard/wandb/csv); 0 disables the bridge
    monitor_interval: int = 1
    #: per-device peak FLOPs for the MFU gauge; 0 = auto-detect from the
    #: device kind (DS_PEAK_FLOPS env overrides either)
    peak_flops: float = 0.0
    #: flight-recorder ring capacity in events (ISSUE 7): the bounded
    #: black-box buffer of per-request/per-step lifecycle events behind
    #: /debug/flightrec and post-mortem bundles.  0 disables recording.
    flightrec_events: int = 8192
    #: rolling median+MAD step-latency anomaly detector (ISSUE 7):
    #: MAD-score threshold above which a step is flagged (counter +
    #: trace instant + flight-recorder event).  0 disables detection.
    anomaly_threshold: float = 5.0
    #: detector window (recent step latencies the median/MAD run over)
    anomaly_window: int = 64
    #: compiled-program cost model (ISSUE 13).  The trainer no longer
    #: reads it: the fused train step's report is made for whoever asks
    #: (telemetry/tracing.py ``get_program_cost``); the serving
    #: scheduler's eager analysis follows DS_PERF_COSTMODEL alone.
    costmodel: bool = True
    #: tiered memory ledger (ISSUE 14): per-step byte attribution by
    #: tier/owner (mem/* gauges, /debug/memory, post-mortem
    #: memory.json, OOM forensics).  DS_MEM_LEDGER env wins.
    memory: bool = True
    #: training-health observatory (ISSUE 15): in-graph grad-norm
    #: groups, NaN provenance, determinism fingerprints (num/* gauges,
    #: /debug/numerics, post-mortem numerics.json)
    numerics: NumericsConfig = Field(default_factory=NumericsConfig)
    #: communication observatory (ISSUE 19): CommStat per-op stats,
    #: per-step overlap window, /debug/comm, post-mortem comm.json.
    #: DS_COMMSTAT env wins.
    comm: CommConfig = Field(default_factory=CommConfig)

    def __init__(self, **data):
        if isinstance(data.get("numerics"), bool):
            # bool shorthand, matching telemetry.memory's spelling
            data["numerics"] = NumericsConfig(enabled=data["numerics"])
        elif isinstance(data.get("numerics"), dict):
            data["numerics"] = NumericsConfig(**data["numerics"])
        if isinstance(data.get("comm"), bool):
            data["comm"] = CommConfig(enabled=data["comm"])
        elif isinstance(data.get("comm"), dict):
            data["comm"] = CommConfig(**data["comm"])
        super().__init__(**data)
        if self.flightrec_events < 0:
            raise ValueError(
                f"telemetry.flightrec_events={self.flightrec_events}: "
                "must be >= 0 (0 disables the flight recorder)")
        if self.anomaly_threshold < 0:
            raise ValueError(
                f"telemetry.anomaly_threshold={self.anomaly_threshold}: "
                "must be >= 0 (0 disables anomaly detection)")
        if self.anomaly_window < 4:
            raise ValueError(
                f"telemetry.anomaly_window={self.anomaly_window}: "
                "must be >= 4")
        if self.metrics_port is not None and self.metrics_port < 0:
            raise ValueError(
                f"telemetry.metrics_port={self.metrics_port}: must be "
                ">= 0 (0 = ephemeral; omit for no endpoint)")
        if self.monitor_interval < 0:
            raise ValueError(
                f"telemetry.monitor_interval={self.monitor_interval}: "
                "must be >= 0 (0 disables the monitor bridge)")
        if self.peak_flops < 0:
            raise ValueError(
                f"telemetry.peak_flops={self.peak_flops}: must be >= 0 "
                "(0 = auto-detect)")


class SpecDecodeConfig(DeepSpeedConfigModel):
    """``serving.spec`` — speculative decoding (ISSUE 5): a proposer
    drafts up to ``max_draft_tokens`` per request per iteration, the
    target model verifies the whole window in one weight pass, and
    rejected suffixes roll back through the paged block tables."""
    #: off | ngram (prompt-lookup self-drafting, no second model) |
    #: draft (a smaller checkpoint sharing the tokenizer — the scheduler
    #: needs a DraftModelProposer handed in, see bin/ds_serve --spec)
    mode: str = "off"
    #: per-request draft-length cap k; each verify window scores k+1
    #: positions (the drafts plus one bonus token from the verify logits)
    max_draft_tokens: int = 4
    #: per-request auto-disable: once a request's rolling acceptance-rate
    #: EMA sits below this after a few verify passes, it decodes plain
    #: for the rest of its life (0 = never disable)
    min_accept_rate: float = 0.0
    #: prompt-lookup n-gram sizes: match the last n tokens (longest
    #: first) against the request's own prompt+output history
    ngram_max: int = 3
    ngram_min: int = 1
    #: draft-model arch:size spec for ds_serve --spec draft
    draft_model: Optional[str] = None
    #: draft proposer's own (small) paged KV pool
    draft_num_blocks: int = 64
    draft_block_size: int = 16

    def __init__(self, **data):
        super().__init__(**data)
        if self.mode not in ("off", "ngram", "draft"):
            raise ValueError(f"serving.spec.mode={self.mode!r}: choose "
                             "off | ngram | draft")
        if self.max_draft_tokens < 1:
            raise ValueError("serving.spec.max_draft_tokens="
                             f"{self.max_draft_tokens}: must be >= 1")
        if not 0.0 <= self.min_accept_rate <= 1.0:
            raise ValueError("serving.spec.min_accept_rate="
                             f"{self.min_accept_rate}: must be in [0, 1]")
        if self.ngram_min < 1 or self.ngram_max < self.ngram_min:
            raise ValueError(
                f"serving.spec ngram sizes min={self.ngram_min} "
                f"max={self.ngram_max}: need 1 <= min <= max")
        if self.draft_num_blocks < 2:
            raise ValueError("serving.spec.draft_num_blocks="
                             f"{self.draft_num_blocks}: need >= 2")
        if self.draft_block_size < 1:
            raise ValueError("serving.spec.draft_block_size="
                             f"{self.draft_block_size}: must be >= 1")


class PrefixCacheConfig(DeepSpeedConfigModel):
    """``serving.prefix_cache`` — cross-request prefix caching (ISSUE 6):
    full KV blocks become hash-addressed immutable entries shared between
    requests; a new request's prompt is matched block-by-block against
    the cache and prefill starts at the first uncached token."""
    #: off by default: with it on, greedy output is token-identical but
    #: not bitwise in the logits (suffix prefill rides the verify-window
    #: path, ~1-ulp from the one-shot causal prefill)
    enabled: bool = False
    #: minimum matched blocks worth attaching — below this the request
    #: full-prefills (tiny matches don't pay for the suffix-program
    #: dispatch + ref bookkeeping)
    min_prefix_blocks: int = 1
    #: cap on RETAINED refcount-0 cached blocks (0 = bounded only by the
    #: pool); cap it when serving wildly heterogeneous traffic so stale
    #: prefixes can't crowd the free list into constant LRU churn
    max_cached_blocks: int = 0

    def __init__(self, **data):
        super().__init__(**data)
        if self.min_prefix_blocks < 1:
            raise ValueError(
                "serving.prefix_cache.min_prefix_blocks="
                f"{self.min_prefix_blocks}: must be >= 1")
        if self.max_cached_blocks < 0:
            raise ValueError(
                "serving.prefix_cache.max_cached_blocks="
                f"{self.max_cached_blocks}: must be >= 0 (0 = pool-bounded)")


class KvTieringConfig(DeepSpeedConfigModel):
    """``serving.kv_tiering`` — tiered KV-cache spill (ISSUE 16): LRU
    pressure demotes refcount-0 hashed blocks HBM→host→NVMe through
    the generic ``deepspeed_tpu/offload`` async swap engine instead of
    dropping them, preemption parks a victim's committed KV on NVMe,
    and a cold-tier prefix hit swaps back in asynchronously (overlapped
    with the current decode iteration) instead of re-prefilling.
    Requires ``serving.prefix_cache.enabled`` — tiers are keyed by the
    prefix cache's chained block hashes.  The DS_KV_TIERING env var
    overrides ``enabled`` either way (env-wins convention)."""
    enabled: bool = False
    #: host-RAM tier capacity in KV blocks; overflow spills the oldest
    #: entries to the NVMe tier (0 = unbounded host tier, never spill)
    host_blocks: int = 256
    #: NVMe tier capacity in KV blocks; overflow drops the oldest
    #: entries outright (0 = unbounded)
    nvme_blocks: int = 0
    #: directory for the NVMe tier's payload files; None = a fresh
    #: process-private temp dir (removed with the engine)
    nvme_dir: Optional[str] = None
    #: park a preemption victim's committed KV straight on NVMe so its
    #: resume is a swap-in instead of a re-prefill
    park_on_preempt: bool = True
    #: aio worker threads per direction for the tier files (io_uring
    #: rings when the kernel allows it, thread pools otherwise)
    aio_threads: int = 2
    #: double-buffering depth: max in-flight async reads/writes per
    #: direction before the engine reaps the oldest
    queue_depth: int = 2

    def __init__(self, **data):
        super().__init__(**data)
        if self.host_blocks < 0:
            raise ValueError(
                f"serving.kv_tiering.host_blocks={self.host_blocks}: "
                "must be >= 0 (0 = unbounded)")
        if self.nvme_blocks < 0:
            raise ValueError(
                f"serving.kv_tiering.nvme_blocks={self.nvme_blocks}: "
                "must be >= 0 (0 = unbounded)")
        if self.aio_threads < 1:
            raise ValueError(
                f"serving.kv_tiering.aio_threads={self.aio_threads}: "
                "must be >= 1")
        if self.queue_depth < 1:
            raise ValueError(
                f"serving.kv_tiering.queue_depth={self.queue_depth}: "
                "must be >= 1")


class SLOClassConfig(DeepSpeedConfigModel):
    """One request class's latency targets (``serving.slo.classes``).
    0 = no target for that dimension (requests still counted)."""
    #: time-to-first-token target, milliseconds
    ttft_ms: float = 0.0
    #: time-per-output-token target, milliseconds (mean inter-token)
    tpot_ms: float = 0.0
    #: QoS rank (ISSUE 9): higher = more important.  Admission and
    #: chunked-prefill service order by it, preemption victimizes the
    #: lowest first, and overload shedding drops classes strictly BELOW
    #: a burning class's priority (shed-lowest-first)
    priority: int = 0

    def __init__(self, **data):
        super().__init__(**data)
        if self.ttft_ms < 0 or self.tpot_ms < 0:
            raise ValueError(
                f"serving.slo class targets ttft_ms={self.ttft_ms} "
                f"tpot_ms={self.tpot_ms}: must be >= 0 (0 = no target)")


class SLOConfig(DeepSpeedConfigModel):
    """``serving.slo`` — per-class latency-target accounting (ISSUE 7)
    plus burn-driven admission control (ISSUE 9): each finished request
    is scored against its class's TTFT/TPOT targets, feeding violation
    counters and rolling burn-rate gauges; with ``shed_enabled`` the
    scheduler consumes those burn rates at submit time and sheds the
    lowest-priority classes 429-style (with Retry-After) instead of
    letting the queue grow without bound."""
    enabled: bool = False
    #: class name -> SLOClassConfig (dict-in-JSON, validated below);
    #: unknown request classes fall back to "default"
    classes: Any = None
    #: rolling burn-rate window, in requests per class
    window: int = 256
    #: overload shedding (ISSUE 9): at saturation, reject submissions of
    #: the lowest-priority classes with a 429 + Retry-After instead of
    #: queueing them (requires ``enabled``)
    shed_enabled: bool = False
    #: a class whose rolling TTFT/TPOT burn rate exceeds this sheds
    #: every class with strictly lower priority (the burning class
    #: itself keeps queueing — queue pressure handles the bottom class)
    shed_burn_threshold: float = 0.5
    #: queue depth, as a fraction of ``serving.max_queued``, beyond
    #: which the lowest-priority class sheds outright
    shed_queue_fraction: float = 0.75
    #: minimum requests in a class's burn window before its burn rate
    #: can trigger shedding (one unlucky first request must not drop a
    #: whole class)
    shed_min_requests: int = 4
    #: Retry-After seconds returned with shed 429s
    retry_after_s: float = 1.0

    def __init__(self, **data):
        super().__init__(**data)
        raw = self.classes or {}
        if not isinstance(raw, dict):
            raise ValueError("serving.slo.classes must be an object of "
                             "class-name -> {ttft_ms, tpot_ms, priority}")
        self.classes = {
            str(name): (c if isinstance(c, SLOClassConfig)
                        else SLOClassConfig(**(c or {})))
            for name, c in raw.items()}
        self.classes.setdefault("default", SLOClassConfig())
        if self.window < 1:
            raise ValueError(f"serving.slo.window={self.window}: must "
                             "be >= 1")
        if not 0.0 < self.shed_burn_threshold <= 1.0:
            raise ValueError(
                "serving.slo.shed_burn_threshold="
                f"{self.shed_burn_threshold}: must be in (0, 1]")
        if not 0.0 < self.shed_queue_fraction <= 1.0:
            raise ValueError(
                "serving.slo.shed_queue_fraction="
                f"{self.shed_queue_fraction}: must be in (0, 1]")
        if self.shed_min_requests < 1:
            raise ValueError(
                "serving.slo.shed_min_requests="
                f"{self.shed_min_requests}: must be >= 1")
        if self.retry_after_s < 0:
            raise ValueError(f"serving.slo.retry_after_s="
                             f"{self.retry_after_s}: must be >= 0")


class ChunkedPrefillConfig(DeepSpeedConfigModel):
    """``serving.chunked_prefill`` — Sarathi-style chunked prefill
    (ISSUE 9): prompts whose prefill exceeds the per-iteration chunk
    allowance are admitted into a persistent PREFILLING state and their
    prefill runs as budget-sized chunks (the PR 6 suffix-prefill
    verify-window programs, driven from a progress cursor) interleaved
    with decode across scheduler iterations — one 32k-token prompt can
    no longer monopolize an iteration and spike every active stream's
    TPOT."""
    enabled: bool = False
    #: max prefill tokens executed per scheduler iteration, shared by
    #: every admission + PREFILLING row (decode rows consume the rest of
    #: ``max_num_batched_tokens``); the scheduler floors effective
    #: progress at one suffix bucket so prefill can never stall outright
    chunk_tokens: int = 256

    def __init__(self, **data):
        super().__init__(**data)
        if self.chunk_tokens < 1:
            raise ValueError(
                "serving.chunked_prefill.chunk_tokens="
                f"{self.chunk_tokens}: must be >= 1")


class FleetConfig(DeepSpeedConfigModel):
    """``serving.fleet`` — replica-fleet serving (ISSUE 11): a Router
    dispatching requests across N in-process replicas (each its own
    ContinuousBatchingScheduler + HealthMonitor + metrics registry)
    with a weighted policy stack — least-loaded by outstanding token
    budget, session affinity, and prefix-cache-aware scoring against a
    bounded per-replica cache digest.  Membership is health-gated: a
    DRAINING/DEGRADED replica stops receiving new work and its in-flight
    requests are resubmitted to a healthy replica through the existing
    evict/resume machinery."""
    #: replicas ``bin/ds_router`` / ``ds_serve --replicas N`` build over
    #: one shared model+params; 1 = the plain single-scheduler server
    num_replicas: int = 1
    #: "scored" combines the weighted policy stack below; "round_robin"
    #: ignores it (the serve_bench A/B baseline)
    policy: str = "scored"
    #: weight of the normalized outstanding-token load penalty
    least_loaded_weight: float = 1.0
    #: bonus for the replica a live session last decoded on (its KV /
    #: prefix blocks are still warm there)
    affinity_weight: float = 1.0
    #: weight of the matched-prefix fraction from the replica cache
    #: digest (PR 6 chained block hashes — the routing key)
    prefix_weight: float = 1.0
    #: bonus for a replica whose AdapterStore already holds the
    #: request's adapter (ISSUE 20): dispatching there skips the
    #: swap-in; scaled by the residency tier (HBM full, host/NVMe by
    #: the tier discounts below)
    adapter_weight: float = 1.0
    #: prefix-score multiplier when the deepest digest hit sits in the
    #: replica's host-RAM tier (ISSUE 16): warm beats cold, HBM beats
    #: warm — attaching it costs a host→HBM swap-in
    host_tier_discount: float = 0.6
    #: same for an NVMe-cold deepest hit: still worth routing toward
    #: for long prefixes, but the swap-in pays NVMe latency
    nvme_tier_discount: float = 0.3
    #: router-side replica-cache digest max age before a dispatch
    #: refreshes it (0 = refresh on every scored dispatch)
    digest_refresh_s: float = 0.5
    #: newest-N hash-chain heads kept per replica digest (bounds router
    #: memory AND the per-dispatch prompt hashing work)
    digest_max_entries: int = 512
    #: times one request may be resubmitted to another replica (drain /
    #: replica loss) before it fails; 0 = never resubmit
    resubmit_budget: int = 3
    #: bounded session->replica affinity map (LRU beyond this)
    session_capacity: int = 4096

    def __init__(self, **data):
        super().__init__(**data)
        if self.num_replicas < 1:
            raise ValueError(f"serving.fleet.num_replicas="
                             f"{self.num_replicas}: must be >= 1")
        if self.policy not in ("scored", "round_robin"):
            raise ValueError(f"serving.fleet.policy={self.policy!r}: "
                             "choose scored | round_robin")
        for k in ("least_loaded_weight", "affinity_weight",
                  "prefix_weight", "adapter_weight"):
            if getattr(self, k) < 0:
                raise ValueError(
                    f"serving.fleet.{k}={getattr(self, k)}: must be >= 0")
        for k in ("host_tier_discount", "nvme_tier_discount"):
            if not 0.0 <= getattr(self, k) <= 1.0:
                raise ValueError(
                    f"serving.fleet.{k}={getattr(self, k)}: must be in "
                    "[0, 1] (a multiplier on the matched-prefix score)")
        if self.digest_refresh_s < 0:
            raise ValueError(f"serving.fleet.digest_refresh_s="
                             f"{self.digest_refresh_s}: must be >= 0")
        if self.digest_max_entries < 1:
            raise ValueError(f"serving.fleet.digest_max_entries="
                             f"{self.digest_max_entries}: must be >= 1")
        if self.resubmit_budget < 0:
            raise ValueError(f"serving.fleet.resubmit_budget="
                             f"{self.resubmit_budget}: must be >= 0")
        if self.session_capacity < 1:
            raise ValueError(f"serving.fleet.session_capacity="
                             f"{self.session_capacity}: must be >= 1")


class AdaptersConfig(DeepSpeedConfigModel):
    """``serving.adapters`` — multi-tenant LoRA adapter serving
    (ISSUE 20): a paged :class:`serving/adapters.AdapterStore` holds up
    to ``max_hbm_adapters`` adapters HBM-resident as slot stacks feeding
    the batched gather-LoRA pass; refcount-0 residents demote LRU
    through the offload engine to host RAM/NVMe and swap back in
    overlapped with the running decode.  The DS_ADAPTERS env var
    overrides ``enabled`` either way (env-wins convention)."""
    enabled: bool = False
    #: adapter_id -> .npz path (the ``save_adapter`` on-disk spelling);
    #: registered + ingested at scheduler construction.  The ``ds_serve
    #: --adapters name=path,...`` flag populates this.
    adapters: Any = None
    #: HBM slot count — adapters concurrently usable in one step; the
    #: gather-LoRA stacks are sized [L, S, d, r_max] by this
    max_hbm_adapters: int = 4
    #: slot rank ceiling; lower-rank adapters zero-pad (exact)
    max_rank: int = 8
    #: restrict target projections ("qkv_w", "wq", ...); empty = any
    #: stacked block weight the registered adapters name
    targets: Any = None
    #: a failed adapter swap-in (fault/IO/integrity) serves the request
    #: from the BASE model (flagged on the response) instead of a typed
    #: rejection
    fallback_to_base: bool = False
    #: adapter_id -> SLO class name (ISSUE 9 QoS ladder): requests
    #: submitted with a defaulted slo_class inherit their tenant's
    slo_class_map: Any = None
    #: host-RAM tier capacity in adapters; overflow spills oldest to
    #: NVMe (0 = unbounded host tier, never spill)
    max_host_adapters: int = 16
    #: directory for NVMe-tier payload files; None = process-private
    #: temp dir (removed with the engine)
    nvme_dir: Optional[str] = None
    #: aio worker threads per direction (kv_tiering semantics)
    aio_threads: int = 2
    #: max in-flight async reads/writes per direction
    queue_depth: int = 2

    def __init__(self, **data):
        super().__init__(**data)
        raw = self.adapters or {}
        if not isinstance(raw, dict):
            raise ValueError("serving.adapters.adapters must be an object "
                             "of adapter_id -> npz path")
        self.adapters = {str(k): str(v) for k, v in raw.items()}
        raw_map = self.slo_class_map or {}
        if not isinstance(raw_map, dict):
            raise ValueError("serving.adapters.slo_class_map must be an "
                             "object of adapter_id -> SLO class name")
        self.slo_class_map = {str(k): str(v) for k, v in raw_map.items()}
        if self.targets is not None and not isinstance(
                self.targets, (list, tuple)):
            raise ValueError("serving.adapters.targets must be a list of "
                             "projection names (or omitted)")
        self.targets = tuple(str(t) for t in (self.targets or ()))
        if self.max_hbm_adapters < 1:
            raise ValueError(
                "serving.adapters.max_hbm_adapters="
                f"{self.max_hbm_adapters}: must be >= 1")
        if self.max_rank < 1:
            raise ValueError(f"serving.adapters.max_rank={self.max_rank}: "
                             "must be >= 1")
        if self.max_host_adapters < 0:
            raise ValueError(
                "serving.adapters.max_host_adapters="
                f"{self.max_host_adapters}: must be >= 0 (0 = unbounded)")
        if self.aio_threads < 1:
            raise ValueError(
                f"serving.adapters.aio_threads={self.aio_threads}: "
                "must be >= 1")
        if self.queue_depth < 1:
            raise ValueError(
                f"serving.adapters.queue_depth={self.queue_depth}: "
                "must be >= 1")


class ServingConfig(DeepSpeedConfigModel):
    """Continuous-batching serving (deepspeed_tpu/serving/): block-pool
    sizing, iteration-level scheduler budgets, admission control.  TPU-
    native addition — the reference's inference config has no serving
    loop to configure."""
    #: tokens per physical KV-cache block (the paging granularity)
    block_size: int = 16
    #: physical pool blocks, INCLUDING the reserved trash block 0;
    #: pool HBM = (num_blocks*block_size) x layers x kv_heads x head_dim
    num_blocks: int = 256
    #: decode-batch width = max concurrently running sequences
    max_num_seqs: int = 8
    #: admission control: queued requests beyond this reject 429-style
    max_queued: int = 128
    #: per-step prefill token budget (iteration-level scheduling knob)
    max_num_batched_tokens: int = 2048
    #: per-sequence block-table length cap; 0 = model context / block_size
    max_blocks_per_seq: int = 0
    #: default queued-request timeout (seconds); 0 = wait forever
    request_timeout_s: float = 0.0
    #: scheduler steps between monitor-sink metric emissions
    monitor_interval: int = 16
    #: multi-step decode fusion cap: up to this many decode iterations run
    #: inside ONE jitted lax.scan when the window provably cannot change a
    #: scheduling decision (window = min remaining tokens over active
    #: rows, so it ends exactly when the first row could retire).
    #: Amortizes per-step dispatch; 1 disables.  Power of two.
    max_fused_steps: int = 8
    #: int8-weights decode loop-form threshold (MB of dequantized bytes
    #: NOT absorbed by the fused-dequant qgemm kernel above which the
    #: decode dispatches to the lax.scan form — models/serving.py
    #: use_scan_decode).  DS_QUANT_SCAN_THRESHOLD_MB overrides.
    quant_scan_threshold_mb: int = 512
    #: MoE expert dispatch formulation override (moe/layer.py): None
    #: leaves the model config's ``dispatch_mode`` in force; "auto" /
    #: "einsum" / "grouped" installs a serving-wide override at
    #: scheduler construction (DS_MOE_DISPATCH env still wins at trace
    #: time).  "grouped" is the megablocks-style drop-free ragged GEMM
    #: (ops/pallas/grouped_gemm.py — ISSUE 8).
    moe_dispatch: Optional[str] = None
    #: fused decode megakernel toggle (ops/pallas/fused_decode.py —
    #: ISSUE 12: one Pallas call per layer for decode/verify/chunk
    #: windows): None = auto (on exactly when the kernel is real — a
    #: single TPU device, or DS_FUSED_DECODE_INTERPRET=1); True/False
    #: installs a serving-wide override at scheduler construction (the
    #: DS_FUSED_DECODE env still wins at trace time).
    fused_decode: Optional[bool] = None
    #: scheduler watchdog: seconds of pending work with step_count frozen
    #: before the server goes DEGRADED (waiting /generate handlers then
    #: 503 instead of hanging).  Generous default = the old handler-local
    #: heuristic's 10 x 60 s — one step legitimately holds the lock for
    #: minutes while XLA compiles a fresh bucket on a real model.
    #: DS_SERVE_STALL_TIMEOUT_S overrides; 0 disables the watchdog.
    stall_timeout_s: float = 600.0
    #: consecutive serving-loop step() failures before the server goes
    #: DEGRADED instead of retrying forever; 0 = never degrade
    max_loop_failures: int = 8
    #: speculative decoding sub-section (dict in JSON; validated into a
    #: SpecDecodeConfig below — nested pydantic construction would skip
    #: the sub-config's __init__ validation)
    spec: Any = None
    #: cross-request prefix-cache sub-section (same dict-in-JSON
    #: validation pattern as ``spec``)
    prefix_cache: Any = None
    #: tiered KV-cache spill sub-section (same pattern; ISSUE 16 —
    #: requires ``prefix_cache.enabled``)
    kv_tiering: Any = None
    #: per-class SLO accounting + admission-control sub-section (same
    #: pattern; ISSUE 7 accounting, ISSUE 9 shedding)
    slo: Any = None
    #: chunked-prefill sub-section (same pattern; ISSUE 9)
    chunked_prefill: Any = None
    #: replica-fleet sub-section (same pattern; ISSUE 11)
    fleet: Any = None
    #: multi-tenant LoRA adapter sub-section (same pattern; ISSUE 20)
    adapters: Any = None

    def __init__(self, **data):
        super().__init__(**data)
        if not isinstance(self.spec, SpecDecodeConfig):
            self.spec = SpecDecodeConfig(**(self.spec or {}))
        if not isinstance(self.adapters, AdaptersConfig):
            self.adapters = AdaptersConfig(**(self.adapters or {}))
        if not isinstance(self.fleet, FleetConfig):
            self.fleet = FleetConfig(**(self.fleet or {}))
        if not isinstance(self.prefix_cache, PrefixCacheConfig):
            self.prefix_cache = PrefixCacheConfig(
                **(self.prefix_cache or {}))
        if not isinstance(self.kv_tiering, KvTieringConfig):
            self.kv_tiering = KvTieringConfig(**(self.kv_tiering or {}))
        if self.kv_tiering.enabled and not self.prefix_cache.enabled:
            raise ValueError(
                "serving.kv_tiering.enabled=true requires "
                "serving.prefix_cache.enabled (cold tiers are keyed by "
                "the prefix cache's chained block hashes)")
        if not isinstance(self.slo, SLOConfig):
            self.slo = SLOConfig(**(self.slo or {}))
        if not isinstance(self.chunked_prefill, ChunkedPrefillConfig):
            self.chunked_prefill = ChunkedPrefillConfig(
                **(self.chunked_prefill or {}))
        if self.block_size < 1:
            raise ValueError(f"serving.block_size={self.block_size}: "
                             "must be >= 1")
        if self.num_blocks < 2:
            raise ValueError(f"serving.num_blocks={self.num_blocks}: need "
                             ">= 2 (block 0 is the reserved trash block)")
        if self.max_num_seqs < 1:
            raise ValueError(
                f"serving.max_num_seqs={self.max_num_seqs}: must be >= 1")
        if self.max_queued < 1:
            raise ValueError(
                f"serving.max_queued={self.max_queued}: must be >= 1")
        if self.max_num_batched_tokens < 1:
            raise ValueError("serving.max_num_batched_tokens="
                             f"{self.max_num_batched_tokens}: must be >= 1")
        if self.max_blocks_per_seq < 0:
            raise ValueError("serving.max_blocks_per_seq="
                             f"{self.max_blocks_per_seq}: must be >= 0 "
                             "(0 = model context / block_size)")
        if self.request_timeout_s < 0:
            raise ValueError("serving.request_timeout_s="
                             f"{self.request_timeout_s}: must be >= 0 "
                             "(0 = wait forever)")
        if self.monitor_interval < 1:
            raise ValueError("serving.monitor_interval="
                             f"{self.monitor_interval}: must be >= 1")
        if self.max_fused_steps < 1 or (
                self.max_fused_steps & (self.max_fused_steps - 1)):
            raise ValueError(
                f"serving.max_fused_steps={self.max_fused_steps}: must be "
                "a power of two >= 1 (one compiled program per size)")
        if self.quant_scan_threshold_mb < 0:
            raise ValueError(
                "serving.quant_scan_threshold_mb="
                f"{self.quant_scan_threshold_mb}: must be >= 0")
        if self.moe_dispatch is not None:
            from deepspeed_tpu.moe.layer import DISPATCH_MODES
            if self.moe_dispatch not in DISPATCH_MODES:
                raise ValueError(
                    f"serving.moe_dispatch={self.moe_dispatch!r}: choose "
                    f"one of {DISPATCH_MODES} (or omit to keep the model "
                    "config's dispatch_mode)")
        if self.stall_timeout_s < 0:
            raise ValueError(
                f"serving.stall_timeout_s={self.stall_timeout_s}: must be "
                ">= 0 (0 disables the stall watchdog)")
        if self.max_loop_failures < 0:
            raise ValueError(
                f"serving.max_loop_failures={self.max_loop_failures}: "
                "must be >= 0 (0 = never degrade on step failures)")

    def resolved_stall_timeout_s(self) -> float:
        """Config value with the DS_SERVE_STALL_TIMEOUT_S env override
        applied (the quant_scan_threshold pattern: env wins at use
        site)."""
        env = os.environ.get("DS_SERVE_STALL_TIMEOUT_S")
        if env is not None and env.strip():
            return float(env)
        return self.stall_timeout_s


# --------------------------------------------------------------------------- root
class DeepSpeedConfig:
    """Parses the JSON dict / file and exposes typed sub-configs + batch math."""

    def __init__(self, config: Union[str, Dict], mesh_topology=None, mpu=None):
        if isinstance(config, str):
            if not os.path.exists(config):
                raise FileNotFoundError(f"DeepSpeed config path not found: {config}")
            with open(config) as f:
                self._param_dict = json.load(f)
        elif isinstance(config, dict):
            self._param_dict = dict(config)
        else:
            raise ValueError(
                f"config must be a dict or a path to a JSON file, got {type(config)}")

        d = self._param_dict
        self.train_batch_size = d.get(C.TRAIN_BATCH_SIZE)
        self.train_micro_batch_size_per_gpu = d.get(C.TRAIN_MICRO_BATCH_SIZE_PER_GPU)
        self.gradient_accumulation_steps = d.get(C.GRADIENT_ACCUMULATION_STEPS)

        self.optimizer_name = None
        self.optimizer_params = None
        opt = d.get(C.OPTIMIZER)
        if opt:
            self.optimizer_name = opt.get("type", "").lower()
            self.optimizer_params = opt.get("params", {})
        self.optimizer_legacy_fusion = bool(opt.get("legacy_fusion", False)) if opt else False

        sched = d.get(C.SCHEDULER)
        self.scheduler_name = sched.get("type") if sched else None
        self.scheduler_params = sched.get("params", {}) if sched else {}

        self.fp16 = FP16Config(**d.get(C.FP16, {}))
        self.bf16 = BF16Config(**d.get(C.BF16, d.get("bfloat16", {})))
        self.zero_config = ZeroConfig(**d.get(C.ZERO_OPTIMIZATION, {}))
        self.mesh_config = MeshConfig(**d.get("mesh", {}))
        self.gradient_clipping = float(d.get(C.GRADIENT_CLIPPING, 0.0))
        self.prescale_gradients = bool(d.get(C.PRESCALE_GRADIENTS, False))
        self.gradient_predivide_factor = float(d.get(C.GRADIENT_PREDIVIDE_FACTOR, 1.0))
        self.steps_per_print = int(d.get(C.STEPS_PER_PRINT, C.STEPS_PER_PRINT_DEFAULT))
        self.wall_clock_breakdown = bool(d.get(C.WALL_CLOCK_BREAKDOWN, False))
        self.dump_state = bool(d.get(C.DUMP_STATE, False))
        self.disable_allgather = bool(d.get("disable_allgather", False))
        self.seed = int(d.get("seed", 42))

        self.activation_checkpointing_config = ActivationCheckpointingConfig(
            **d.get("activation_checkpointing", {}))
        self.flops_profiler_config = FlopsProfilerConfig(**d.get("flops_profiler", {}))
        self.comms_config = CommsLoggerConfig(**d.get("comms_logger", {}))
        self.monitor_config = MonitorConfig(
            tensorboard=TensorBoardConfig(**d.get("tensorboard", {})),
            wandb=WandbConfig(**d.get("wandb", {})),
            csv_monitor=CSVConfig(**d.get("csv_monitor", {})))
        self.aio_config = AioConfig(**d.get("aio", {}))
        self.curriculum_learning = CurriculumLearningConfig(
            **d.get("curriculum_learning", {}))
        self.curriculum_enabled_legacy = self.curriculum_learning.enabled
        self.curriculum_params_legacy = d.get("curriculum_learning", {})
        self.data_efficiency_config = d.get("data_efficiency", {})
        self.eigenvalue_config = EigenvalueConfig(**d.get("eigenvalue", {}))
        self.pld_config = PLDConfig(**d.get("progressive_layer_drop", {}))
        self.debug_config = DebugConfig(**d.get("debug", {}))
        self.elasticity_config = ElasticityConfig(**d.get("elasticity", {}))
        self.checkpoint_config = CheckpointConfig(**d.get("checkpoint", {}))
        self.resilience_config = ResilienceConfig(**d.get("resilience", {}))
        self.data_types_config = DataTypesConfig(**d.get("data_types", {}))
        self.serving_config = ServingConfig(**d.get("serving", {}))
        self.telemetry_config = TelemetryConfig(**d.get("telemetry", {}))
        self.compression_config = d.get("compression_training", {})
        self.autotuning_config = d.get("autotuning", {})
        self.sparse_gradients_enabled = bool(d.get("sparse_gradients", False))
        self.communication_data_type = d.get("communication_data_type", None)
        self.memory_breakdown = bool(d.get("memory_breakdown", False))

        self.zero_enabled = self.zero_config.stage > 0
        self.zero_optimization_stage = self.zero_config.stage

        dp_world = mesh_topology.dp_world_size if mesh_topology is not None else None
        self._resolve_batch_sizes(dp_world)
        self._sanity_check()

    # ------------------------------------------------------------------ batch math
    def _resolve_batch_sizes(self, dp_world: Optional[int]):
        """Batch-size triangulation: train = micro × gas × dp
        (reference config.py:911-933)."""
        dp = dp_world or 1
        train, micro, gas = (self.train_batch_size,
                             self.train_micro_batch_size_per_gpu,
                             self.gradient_accumulation_steps)
        if train is not None and micro is not None and gas is not None:
            pass
        elif train is not None and micro is not None:
            gas = train // (micro * dp)
        elif train is not None and gas is not None:
            micro = train // (gas * dp)
        elif micro is not None and gas is not None:
            train = micro * gas * dp
        elif train is not None:
            gas = 1
            micro = train // dp
        elif micro is not None:
            gas = 1
            train = micro * dp
        else:
            raise ValueError(
                "One of train_batch_size or train_micro_batch_size_per_gpu "
                "must be set in the DeepSpeed config")
        self.train_batch_size = train
        self.train_micro_batch_size_per_gpu = micro
        self.gradient_accumulation_steps = gas
        self._dp_world_for_check = dp

    def _sanity_check(self):
        train, micro, gas = (self.train_batch_size,
                             self.train_micro_batch_size_per_gpu,
                             self.gradient_accumulation_steps)
        dp = self._dp_world_for_check
        if micro is None or micro <= 0 or gas is None or gas <= 0:
            raise ValueError(
                f"Invalid batch config: micro={micro} gas={gas} "
                f"(train={train}, dp={dp})")
        if train != micro * gas * dp:
            raise ValueError(
                f"Check batch-size settings: train_batch_size {train} != "
                f"micro_batch {micro} × gradient_accumulation_steps {gas} × "
                f"data-parallel world {dp}")
        if self.fp16.enabled and self.bf16.enabled:
            raise ValueError("fp16 and bf16 cannot both be enabled")
        if self.zero_config.stage > 3:
            raise ValueError(f"ZeRO stage {self.zero_config.stage} > 3 is invalid")

    def print_config(self):
        logger.info(f"DeepSpeedConfig: {json.dumps(self._param_dict, indent=2, default=str)}")
