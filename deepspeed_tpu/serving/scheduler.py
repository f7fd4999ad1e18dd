"""Iteration-level continuous-batching scheduler (Orca OSDI '22 scheduling
over a vLLM-style paged KV pool).

Each ``step()`` is one engine iteration:

1. expire queued requests past their timeout (graceful 429, never a crash);
2. admit queued prefills — highest SLO class, then priority, first — up
   to the ``max_num_batched_tokens`` budget and the free-slot/free-block
   supply; with ``serving.prefix_cache`` on, each prompt is first matched
   block-by-block against the cross-request prefix cache and only the
   uncached suffix prefills (ISSUE 6); with ``serving.chunked_prefill``
   on (ISSUE 9), a prefill larger than the per-iteration chunk allowance
   admits into a persistent PREFILLING state instead of running whole;
2b. service PREFILLING rows: each iteration runs at most
   ``chunk_tokens`` of pending prefill — highest class first — from
   each request's committed cursor, riding the SAME batched-window
   program as the decode rows (``_window_step``, ISSUE 12), so one
   32k-token prompt can never spike every active stream's TPOT and a
   chunk's layer weight pass is shared with decode instead of paid
   separately;
3. grow each active row's block table for the token it is about to write
   (allocate-on-decode); under pool exhaustion the lowest-priority active
   request is preempted (blocks freed, request requeued; it resumes later
   by recomputing prompt+generated — no swap tier in v1);
4. run ONE jitted decode step over the packed active set.  The physical
   cache is a position-flat pool ``[L, num_blocks*block_size, ...]``
   (the `models/serving.py` cache layout with batch collapsed into the
   pool); block tables expand to per-position gather indices, the pool is
   gathered into the dense ``[L, B, S_pad, ...]`` view the existing
   `decode_fn` expects, and the one new KV vector per row scatters back.
   Finished rows retire immediately — their blocks recycle and a queued
   request can take the slot on the very next iteration, mid-batch.

The decode program compiles ONCE per (max_num_seqs, S_pad, sampling?)
— padding rows point at the reserved trash block and are ignored.

Greedy decoding is token-for-token identical to the static
``InferenceEngine.generate`` path: same prefill, same decode kernel, same
cache values (tested, including the int8 KV cache and across preemption).
Sampled requests draw per-row keys from ``fold_in(PRNGKey(seed),
position)`` — preemption-stable, but deliberately NOT the static engine's
batch-coupled rng chain.
"""
import collections
import os
import threading
import time
from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from deepspeed_tpu.serving.block_manager import BlockManager
from deepspeed_tpu.serving.request import (AdmissionError, QueueFullError,
                                           RequestState, RequestTooLongError,
                                           ServeRequest)
from deepspeed_tpu.utils.logging import logger


def _round_up(n: int, q: int) -> int:
    return -(-n // q) * q


def _jit_device_local(fn):
    """``jax.jit`` with the body TRACED under
    ``sharding_pin_scope(False)`` (comm/mesh.py): the scheduler's
    compiled programs are single-device by design (ROADMAP item 1 — the
    fleet router / sharded-serving tier is the multi-device path), so
    the training-mesh layout pins model code carries (e.g.
    ``moe_layer``'s token-major constraint over the zero-shard axes)
    must not engage inside them.  On a multi-device host a pin engages
    whenever the token count divides the data axis — and this jaxlib's
    SPMD partitioner miscompiles the scheduler's gather/scatter-heavy
    programs under it (reproduced: mixtral spec verify at window width
    8 on the 8-device CPU harness returns zero logits; width 5 —
    non-divisible, pin skipped — is correct)."""
    def traced(*args):
        from deepspeed_tpu.comm.mesh import sharding_pin_scope
        with sharding_pin_scope(False):
            return fn(*args)
    return jax.jit(traced)


def _sample_rows(logits, seeds, positions, temps, top_ks, top_ps, do_flags,
                 any_sampling: bool):
    """Per-row sampling with traced per-request params.  ``positions``
    keys the rng per (seed, absolute token index) so an evicted-and-
    resumed request reproduces its stream exactly.  The temperature /
    top-k / top-p pipeline lives in ``spec/verifier.py`` so speculative
    rejection sampling draws from the SAME distribution."""
    from deepspeed_tpu.serving.spec.verifier import process_sampling_logits
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if not any_sampling:                    # static: all-greedy steps skip
        return greedy                       # the sort entirely
    x = process_sampling_logits(logits, temps, top_ks, top_ps)
    keys = jax.vmap(lambda s, p: jax.random.fold_in(jax.random.PRNGKey(s), p)
                    )(seeds, positions)
    sampled = jax.vmap(jax.random.categorical)(keys, x).astype(jnp.int32)
    return jnp.where(do_flags, sampled, greedy)


class ServingMetrics:
    """Serving observability (ISSUE 4): counters + registry-backed
    latency histograms (TTFT, per-token decode latency, queue wait, e2e
    latency) and occupancy histograms, rendered three ways from ONE
    store — monitor events (monitor/monitor.py sinks), the flat
    ``snapshot()`` dict, and Prometheus text for ``/metrics``
    (``render_prometheus``, the telemetry registry's shared exposition
    function)."""

    _QUANTILES = ((50, "p50"), (90, "p90"), (99, "p99"))
    #: histogram name -> snapshot/monitor key stem
    _LATENCY_HISTS = (("serving/ttft_s", "ttft"),
                      ("serving/token_latency_s", "token_latency"),
                      ("serving/latency_s", "latency"),
                      ("serving/queue_wait_s", "queue_wait"))

    def __init__(self, registry=None, max_accept_len: int = 17):
        from deepspeed_tpu.telemetry import (COUNT_BUCKETS, MetricsRegistry,
                                             OCCUPANCY_BUCKETS)
        #: isolated per scheduler by default; ds_serve passes the
        #: process-wide registry so train+serve share one exposition
        self.registry = (registry if registry is not None
                         else MetricsRegistry())
        self.counters = collections.Counter()
        self.gauges: Dict[str, float] = {}
        reg = self.registry
        self.ttft_s = reg.histogram("serving/ttft_s")
        self.token_latency_s = reg.histogram("serving/token_latency_s")
        self.latency_s = reg.histogram("serving/latency_s")
        self.queue_wait_s = reg.histogram("serving/queue_wait_s")
        self.decode_occupancy = reg.histogram("serving/decode_occupancy",
                                              buckets=OCCUPANCY_BUCKETS)
        self.prefill_batch_tokens = reg.histogram(
            "serving/prefill_batch_tokens", buckets=COUNT_BUCKETS)
        # tokens emitted per verify pass per speculating request
        # (accepted drafts + the bonus token) — ISSUE 5; unit-granular
        # buckets sized to the configured cap (max_draft_tokens + 1) so
        # high-k workloads never collapse into +Inf
        self.spec_accept_len = reg.histogram(
            "serve/spec_accept_len",
            buckets=tuple(range(1, max(max_accept_len, 2) + 1)))

    def observe_finished(self, req: ServeRequest):
        self.counters["completed"] += 1
        if req.ttft_s is not None:
            self.ttft_s.observe(req.ttft_s)
        if req.latency_s is not None:
            self.latency_s.observe(req.latency_s)
        times = req.token_times
        for a, b in zip(times, times[1:]):
            self.token_latency_s.observe(b - a)

    def observe_queue_wait(self, wait_s: float):
        self.queue_wait_s.observe(wait_s)

    def _hist(self, name: str):
        return self.registry.histogram(name)

    def _spec_accept_gauges(self) -> Dict[str, float]:
        """serve/spec_accept_len quantiles + mean, in raw token units
        (ISSUE 5: the /metrics surface the adaptive-k dashboards read)."""
        h = self.spec_accept_len
        out: Dict[str, float] = {}
        vals = h.quantiles(tuple(q for q, _tag in self._QUANTILES))
        if vals is None:
            return out
        for (_q, tag), v in zip(self._QUANTILES, vals):
            out[f"serve/spec_accept_len_{tag}"] = round(v, 3)
        if h.count:
            out["serve/spec_accept_len_mean"] = round(h.sum / h.count, 3)
        return out

    def snapshot(self) -> Dict[str, float]:
        out = {f"serving/{k}": float(v) for k, v in self.counters.items()}
        out.update({f"serving/{k}": float(v)
                    for k, v in self.gauges.items()})
        for hist_name, stem in self._LATENCY_HISTS:
            vals = self._hist(hist_name).quantiles(
                tuple(q for q, _tag in self._QUANTILES))
            if vals is None:
                continue
            for (_q, tag), v in zip(self._QUANTILES, vals):
                out[f"serving/{stem}_{tag}_ms"] = round(v * 1e3, 3)
        out.update(self._spec_accept_gauges())
        return out

    def to_events(self, step: int):
        return [(name, value, step)
                for name, value in sorted(self.snapshot().items())]

    def render_prometheus(self, extra_labels=None) -> str:
        """Single exposition path: mirror the counters/gauges (and the
        quantile gauges the dashboards want pre-computed) into the
        registry, then render its text format — histogram buckets
        included.  ``extra_labels`` ride every sample line (the fleet
        front-end's per-``replica`` label, ISSUE 11)."""
        for k, v in self.counters.items():
            self.registry.set_counter(f"serving/{k}", float(v))
        for k, v in self.gauges.items():
            self.registry.set_gauge(f"serving/{k}", float(v))
        for hist_name, stem in self._LATENCY_HISTS:
            vals = self._hist(hist_name).quantiles(
                tuple(q for q, _tag in self._QUANTILES))
            if vals is None:
                continue
            for (_q, tag), v in zip(self._QUANTILES, vals):
                self.registry.set_gauge(
                    f"serving/{stem}_{tag}_ms", round(v * 1e3, 3))
        for name, value in self._spec_accept_gauges().items():
            self.registry.set_gauge(name, value)
        return self.registry.render_prometheus(extra_labels=extra_labels)


class ContinuousBatchingScheduler:
    """Drives a Model's existing prefill/decode fns as a serving loop.

    ``model`` must provide ``init_cache_fn/prefill_fn/decode_fn`` (every
    in-tree decoder does); ``params`` are the placed inference params
    (e.g. ``InferenceEngine.params``).  ``monitor`` is any
    ``monitor/monitor.py`` sink; gauge+counter events flow to it each
    ``monitor_interval`` steps.
    """

    PROMPT_BUCKET = 16          # prefill compile count = distinct buckets

    def __init__(self, model, params, config, kv_cache_dtype=None,
                 monitor=None, injector=None, registry=None,
                 proposer=None, flightrec=None, anomaly=None):
        if (model.init_cache_fn is None or model.prefill_fn is None
                or model.decode_fn is None):
            raise ValueError("model does not expose the KV-cache serving "
                             "surface (init_cache_fn/prefill_fn/decode_fn)")
        from deepspeed_tpu.resilience.faults import resolve_injector
        self.model = model
        self.params = params
        self.cfg = config
        self.kv_cache_dtype = kv_cache_dtype
        self.monitor = monitor
        self.injector = (injector if injector is not None
                         else resolve_injector())
        self._telemetry_registry = registry
        # cross-request prefix cache (ISSUE 6): released full blocks are
        # hash-addressed and retained; _admit matches prompts against
        # them and prefills only the uncached suffix
        pc = config.prefix_cache
        self._prefix_cache_on = bool(pc.enabled)
        self._prefix_min_blocks = pc.min_prefix_blocks
        self.block_mgr = BlockManager(config.num_blocks, config.block_size,
                                      injector=self.injector,
                                      cache_enabled=pc.enabled,
                                      max_cached_blocks=pc.max_cached_blocks)
        # int8-weights decode dispatch: install this config's threshold so
        # the model-side use_scan_decode sees it (env override still wins
        # inside get_quant_scan_threshold).  Only an EXPLICITLY supplied
        # key installs — a defaulted config leaves the module default (and
        # any test monkeypatch of it) in force
        if "quant_scan_threshold_mb" in config.model_fields_set:
            from deepspeed_tpu.models import serving as _serving
            _serving.set_quant_scan_threshold(
                int(config.quant_scan_threshold_mb) << 20)
        # MoE expert dispatch (ISSUE 8): an explicit serving.moe_dispatch
        # installs the process override so every model-side
        # resolve_dispatch_mode — decode, verify, suffix prefill — sees
        # it (DS_MOE_DISPATCH env still wins at trace time)
        if config.moe_dispatch is not None:
            from deepspeed_tpu.moe.layer import set_dispatch_override
            set_dispatch_override(config.moe_dispatch)

        bs = config.block_size
        model_ctx = int(getattr(model.config, "max_seq_len", 1 << 30))
        per_seq_cap = (config.max_blocks_per_seq * bs
                       if config.max_blocks_per_seq else model_ctx)
        #: hard per-request length ceiling (prompt + generated)
        self.max_model_len = min(model_ctx, per_seq_cap,
                                 self.block_mgr.num_usable_blocks * bs)
        # dense gather width: fixed for the whole session so the decode
        # program compiles once; 64-multiple for the decode kernel's
        # S-block alignment (engine.py cache_size does the same)
        self.s_pad = _round_up(self.max_model_len, 64)
        self.blocks_per_table = -(-self.s_pad // bs)
        # table→flat-pool expansion, shared by every dense gather
        # (_pos_idx_row): logical position p lives at
        # table[p // bs] * bs + p % bs
        self._pos_offs = np.arange(self.s_pad) % bs
        self._pos_blk = np.arange(self.s_pad) // bs

        #: per-step block-accounting invariant check (O(num_blocks) under
        #: the scheduler lock — a debug aid, not a production default);
        #: the spec test suite arms it for every scheduler it builds
        self._debug_invariant = bool(int(
            os.environ.get("DS_SERVE_DEBUG", "0") or 0))
        self._lock = threading.RLock()
        self._queue: List[ServeRequest] = []
        self._slots: List[Optional[ServeRequest]] = \
            [None] * config.max_num_seqs
        self._next_id = 0
        self._step_count = 0
        self.metrics = ServingMetrics(
            registry=self._telemetry_registry,
            max_accept_len=getattr(getattr(config, "spec", None),
                                   "max_draft_tokens", 16) + 1)
        # MoE routing-health telemetry (ISSUE 8 satellite): an
        # explicitly-passed registry (the ds_serve /metrics path) arms
        # the moe_layer host-callback tap; a registry-less scheduler
        # DISARMS it (last-constructed wins — a retired server's dead
        # registry must not keep receiving per-step callbacks from
        # programs a later scheduler traces)
        from deepspeed_tpu.moe.layer import set_moe_metrics_registry
        set_moe_metrics_registry(self._telemetry_registry)
        # black-box layer (ISSUE 7): flight recorder for per-request
        # lifecycle events, rolling step-latency anomaly detection, and
        # per-class SLO burn accounting — all writing into the SAME
        # registry/trace/correlation-id space as the PR 4 telemetry
        from deepspeed_tpu.telemetry.anomaly import (AnomalyMonitor,
                                                     SLOTracker)
        from deepspeed_tpu.telemetry.flight_recorder import \
            get_flight_recorder
        self.flightrec = (flightrec if flightrec is not None
                          else get_flight_recorder())
        self.anomaly = (anomaly if anomaly is not None
                        else AnomalyMonitor(registry=self.metrics.registry,
                                            flightrec=self.flightrec))
        self.slo = SLOTracker(getattr(config, "slo", None),
                              self.metrics.registry)
        # chunked prefill (ISSUE 9): prefill becomes a per-iteration
        # resource — admissions larger than the chunk allowance persist
        # in PREFILLING state and the batched-window step services
        # them, highest SLO class first, within the shared token budget
        cp = getattr(config, "chunked_prefill", None)
        self._chunked_on = bool(getattr(cp, "enabled", False))
        self._chunk_tokens = int(getattr(cp, "chunk_tokens", 256) or 256)
        self._prefill_spent = 0         # prefill tokens executed this step
        self._serve_t0 = time.monotonic()   # tokens/s accounting window
        self._prefill_fns = {}
        self._decode_fns = {}
        self._sample1_fns = {}
        self._window_fns = {}
        self._suffix_prefill_fns = {}
        # fused decode megakernel (ISSUE 12): an explicit
        # serving.fused_decode installs the process override so every
        # model-side fused_decode_active resolution — decode, verify,
        # suffix prefill — sees it (DS_FUSED_DECODE env wins at trace
        # time; None leaves auto-on-TPU in force)
        if config.fused_decode is not None:
            from deepspeed_tpu.ops.pallas.fused_decode import \
                set_fused_decode_override
            set_fused_decode_override(bool(config.fused_decode))
        self._copy_fn = None            # COW-fork block copy (lazy jit)
        self._finished_this_step: List[ServeRequest] = []
        # --- speculative decoding (ISSUE 5): resolve the proposer from
        # serving.spec.mode; an explicit proposer wins (and implies spec
        # on even when the config section says off — test/bench intent)
        self.proposer = self._resolve_proposer(proposer)
        # perf observatory (ISSUE 13): one dtype-aware weight-stream
        # model per scheduler (split_quantized_bytes library math) — the
        # HBM-byte term every compiled program family reports against
        from deepspeed_tpu.telemetry.costmodel import (costmodel_enabled,
                                                       param_stream_bytes)
        self._costmodel_on = costmodel_enabled()
        self._cost_stream = None
        if self._costmodel_on:
            try:
                mcfg = getattr(self.model, "config", None)
                self._cost_stream = param_stream_bytes(
                    self.params, batch=self.cfg.max_num_seqs,
                    top_k=getattr(mcfg, "top_k", None),
                    num_experts=getattr(mcfg, "num_experts", None))
            except Exception:       # cost accounting must never block serving
                self._costmodel_on = False
        # comm observatory (ISSUE 19): attach the process-wide CommStat
        # to THIS scheduler's telemetry spine so serve-side collective
        # windows (barriers, eager collectives) publish into the same
        # registry /debug/comm renders
        from deepspeed_tpu.telemetry.commstat import (commstat_enabled,
                                                      get_commstat)
        if commstat_enabled():
            get_commstat().attach(registry=self.metrics.registry,
                                  anomaly=self.anomaly,
                                  flightrec=self.flightrec,
                                  injector=self.injector)
        self.pool = self._init_pool()
        # memory observatory (ISSUE 14): per-step byte attribution of
        # the KV pool (allocated / prefix-cache retained / free), the
        # params, and the spec draft pool into the process-wide tiered
        # ledger — mem/* gauges, /debug/memory, OOM forensics
        from deepspeed_tpu.telemetry.memory import (get_memory_ledger,
                                                    memory_enabled,
                                                    tree_bytes)
        self._mem_on = memory_enabled(getattr(
            getattr(config, "telemetry", None), "memory", None))
        self._mem_ledger = get_memory_ledger() if self._mem_on else None
        self._pool_bytes = 0
        self._bytes_per_block = 0.0
        if self._mem_on:
            try:
                self._pool_bytes = tree_bytes(self.pool)
                self._bytes_per_block = (self._pool_bytes
                                         / self.cfg.num_blocks)
                from deepspeed_tpu.telemetry.memory import attribute_params
                attribute_params(self._mem_ledger, self.params,
                                 stream=self._cost_stream)
                draft_pool = getattr(self.proposer, "pool", None)
                if draft_pool is not None:
                    self._mem_ledger.set_bytes(
                        "device", "spec_draft", tree_bytes(draft_pool))
            except Exception:   # byte accounting must never block serving
                self._mem_on = False
        # tiered KV spill (ISSUE 16): LRU pressure demotes refcount-0
        # hashed blocks HBM→host→NVMe through the offload engine
        # instead of evicting; cold prefix hits swap back in async
        # (overlapped with the decode iteration) and preemption parks
        # committed KV on NVMe.  Needs the prefix cache — cold tiers
        # are keyed by its chain hashes.
        from deepspeed_tpu.serving.kv_tiering import tiering_enabled
        kt = getattr(config, "kv_tiering", None)
        self._tier_store = None
        self._park_on_preempt = bool(getattr(kt, "park_on_preempt", True))
        #: request_id -> cold chain hashes whose swap-in is in flight
        #: (the request sits out admission until they materialize)
        self._swap_pending = collections.OrderedDict()
        self._swapin_fn = None          # tier swap-in scatter (lazy jit)
        self._pool_treedef = jax.tree_util.tree_structure(self.pool)
        if tiering_enabled(kt) and self._prefix_cache_on:
            from deepspeed_tpu.serving.kv_tiering import KvTierStore
            self._tier_store = KvTierStore(
                kt, injector=self.injector, flightrec=self.flightrec)
            self.block_mgr.attach_tiering(self._tier_store,
                                          self._extract_block)
        # multi-tenant LoRA adapters (ISSUE 20): paged AdapterStore over
        # the same offload engine — requests carry adapter_id, admission
        # pins a resident HBM slot (swap-in overlapped with the running
        # decode like cold-tier prefix hits), and every program family
        # takes an optional trailing gather-LoRA operand
        from deepspeed_tpu.serving.adapters import adapters_enabled
        ac = getattr(config, "adapters", None)
        self._adapters_cfg = ac
        self.adapter_store = None
        self.adapter_registry = None
        #: request_id -> adapter_id whose swap-in is in flight (the
        #: request sits out admission until it materializes)
        self._adapter_pending: Dict[int, str] = {}
        #: rolling base-weight version label (ISSUE 20 live hot-swap);
        #: stamped on /metrics and every admit/retire/step flight event
        self.weights_version = "v1"
        self._weights_swapped = False
        if ac is not None and adapters_enabled(ac):
            if not model.meta.get("lora_serving"):
                raise ValueError(
                    f"serving.adapters.enabled: model "
                    f"{model.meta.get('name')!r} does not implement the "
                    "gather-LoRA serving pass (meta['lora_serving'])")
            from deepspeed_tpu.serving.adapters import (AdapterRegistry,
                                                        AdapterStore)
            self.adapter_registry = AdapterRegistry(
                max_rank=ac.max_rank,
                allowed_targets=ac.targets or None)
            shapes = self._lora_block_shapes()
            self.adapter_store = AdapterStore(
                self.adapter_registry, ac, shapes,
                injector=self.injector, flightrec=self.flightrec)
            for aid, path in sorted(ac.adapters.items()):
                self.register_adapter(aid, path=path)

    def _resolve_proposer(self, proposer):
        spec = getattr(self.cfg, "spec", None)
        mode = getattr(spec, "mode", "off") if spec is not None else "off"
        if proposer is not None:
            return proposer
        if mode == "off":
            return None
        if mode == "ngram":
            from deepspeed_tpu.serving.spec import NgramProposer
            return NgramProposer(ngram_max=spec.ngram_max,
                                 ngram_min=spec.ngram_min)
        # draft mode needs a model+params pair the scheduler cannot
        # conjure — bin/ds_serve builds the DraftModelProposer from
        # serving.spec.draft_model
        raise ValueError(
            "serving.spec.mode='draft' needs a DraftModelProposer passed "
            "as ContinuousBatchingScheduler(..., proposer=...)")

    # -------------------------------------------- adapter serving (20)
    def _lora_block_shapes(self) -> Dict[str, tuple]:
        """Stackable gather-LoRA targets from the base params: every
        3-D ``blocks`` leaf (stacked [L, d_in, d_out] projection;
        biases/norms are 2-D and skip), optionally restricted to
        ``serving.adapters.targets``.  Quantized leaves report their
        LOGICAL shape — the LoRA delta applies in float on the qdot
        output, never inside the int8 payload."""
        blocks = (self.params.get("blocks", {})
                  if isinstance(self.params, dict) else {})
        want = set(self._adapters_cfg.targets or ())
        shapes: Dict[str, tuple] = {}
        for t, leaf in blocks.items():
            if want and t not in want:
                continue
            shp = tuple(getattr(leaf, "shape", ()) or ())
            if not shp and hasattr(leaf, "q"):
                # QuantizedTensor: the int8 payload carries the logical
                # [L, d_in, d_out] shape
                shp = tuple(getattr(leaf.q, "shape", ()) or ())
            if len(shp) == 3:
                shapes[t] = (int(shp[0]), int(shp[1]), int(shp[2]))
        if not shapes:
            raise ValueError(
                "serving.adapters: no stackable [L, d_in, d_out] block "
                "weights found in the model params"
                + (f" for targets {sorted(want)}" if want else ""))
        return shapes

    def register_adapter(self, adapter_id: str, lora_tree=None, path=None,
                         alpha=None, slo_class=None):
        """Register + ingest one LoRA adapter (the ``ds_serve
        --adapters`` startup path and the test/tooling surface).
        Validation failure raises ValueError and leaves the registry
        unchanged; on success the payload enters the host paging tier
        and the first request swap-ins it to HBM."""
        if self.adapter_registry is None:
            raise ValueError("serving.adapters is not enabled")
        with self._lock:
            if path is not None:
                m = self.adapter_registry.register_file(
                    adapter_id, path, slo_class=slo_class)
            else:
                m = self.adapter_registry.register(
                    adapter_id, lora_tree, alpha=alpha,
                    slo_class=slo_class)
            try:
                ok = self.adapter_store.ingest(adapter_id)
            except ValueError:
                self.adapter_registry.unregister(adapter_id)
                raise
            if not ok:
                # fault-denied ingest: registered but in no tier — the
                # typed failure surfaces per-request at swap-in time
                self.metrics.counters["adapter_load_failures"] += 1
            return m

    def _adapter_slot(self, req: ServeRequest) -> int:
        """This request's HBM adapter slot for program packing
        (-1 = base model / no adapter)."""
        if self.adapter_store is None or req.adapter_id is None:
            return -1
        s = self.adapter_store.slot_of(req.adapter_id)
        return -1 if s is None else s

    def _lora_arg(self, groups) -> tuple:
        """Trailing gather-LoRA operand for one program execution: ()
        when no packed row carries an adapter — the program runs its
        unchanged base trace, so adapter-less steps pay exactly
        nothing — else the one pytree the model-side pass consumes
        (per-row slot groups + the store's slot stacks; each distinct
        adapter's factors stream once per execution)."""
        g = np.asarray(groups, np.int32)
        if self.adapter_store is None or not (g >= 0).any():
            return ()
        st = self.adapter_store
        return ({"groups": jnp.asarray(g), "scale": st.scale,
                 "stacks": st.stacks},)

    def _schedule_adapter_swapin(self, req: ServeRequest) -> bool:
        """Kick (or piggyback on) the async swap-in for a cold adapter;
        the request sits out admission until it materializes.  False =
        the adapter is in no tier (quarantined / dropped) — the caller
        runs the failure path."""
        aid = req.adapter_id
        if aid not in self._adapter_pending.values():
            if not self.adapter_store.schedule_swapin(
                    aid, corr=f"req-{req.request_id}"):
                return False
        if req.request_id not in self._adapter_pending:
            self.flightrec.record("req/adapter_swap_in",
                                  corr=f"req-{req.request_id}",
                                  adapter=aid)
        self._adapter_pending[req.request_id] = aid
        return True

    def _materialize_adapter_swapins(self):
        """Complete adapter swap-ins scheduled on an earlier step (the
        I/O already overlapped at least one decode iteration): install
        each into an HBM slot; waiters re-enter this step's admission
        line.  ``wait`` (every slot pinned) stays pending and retries
        as requests retire; ``fail`` runs the per-request failure path
        (typed reject, or base-model fallback per config)."""
        if self.adapter_store is None or not self._adapter_pending:
            return
        queued = {r.request_id: r for r in self._queue}
        status_of: Dict[str, str] = {}
        for rid in list(self._adapter_pending):
            aid = self._adapter_pending[rid]
            req = queued.get(rid)
            if req is None:         # expired / extracted while pending
                self._adapter_pending.pop(rid)
                continue
            st = status_of.get(aid)
            if st is None:
                st, _slot = self.adapter_store.swap_in(
                    aid, corr=f"req-{rid}")
                status_of[aid] = st
            if st == "ok":
                self._adapter_pending.pop(rid)
            elif st == "fail":
                self._adapter_pending.pop(rid)
                self._adapter_failure(req)

    def _adapter_failure(self, req: ServeRequest) -> bool:
        """One request's adapter could not materialize (fault / IO /
        integrity / quarantine).  With ``fallback_to_base`` the request
        degrades to the base model (flagged on its response) and True
        returns; otherwise it fails TYPED — rejected with a reason,
        never a crash — and every other tenant's stream is untouched."""
        aid = req.adapter_id
        ac = self._adapters_cfg
        if ac is not None and getattr(ac, "fallback_to_base", False):
            req.adapter_id = None
            req.adapter_fallback = True
            self.metrics.counters["adapter_fallbacks"] += 1
            self.flightrec.record("req/adapter_fallback",
                                  corr=f"req-{req.request_id}",
                                  adapter=aid)
            return True
        if req in self._queue:
            self._queue.remove(req)
        req.state = RequestState.REJECTED
        req.reject_reason = (f"adapter {aid!r} failed to load "
                             "(fault/IO/integrity)")
        self.metrics.counters["adapter_rejects"] += 1
        self.flightrec.record("req/adapter_fail",
                              corr=f"req-{req.request_id}", adapter=aid)
        req.done.set()
        return False

    def install_params(self, new_params, version: str):
        """Live base-weight hot-swap (ISSUE 20): install a new params
        pytree under the scheduler lock and roll the version label.
        Structure/shapes/dtypes must match the old tree — params is a
        TRACED argument of every compiled program family, so an
        identical-structure install triggers zero recompiles.  Call on
        a drained replica (fleet ``Router.swap_weights``) for token-
        identical streams; an undrained install changes weights
        mid-stream."""
        old = jax.tree_util.tree_structure(self.params)
        new = jax.tree_util.tree_structure(new_params)
        if old != new:
            raise ValueError(
                "install_params: new params tree does not match the "
                "serving tree (hot-swap requires identical structure)")
        with self._lock:
            self.params = new_params
            self.weights_version = str(version)
            self._weights_swapped = True
            self.flightrec.record("route/weights_swap",
                                  corr=f"serve-step-{self._step_count}",
                                  version=self.weights_version,
                                  step=self._step_count)
            self.metrics.counters["weights_swaps"] += 1

    # ------------------------------------------------------------- pool
    def _init_pool(self):
        """Position-flat physical cache: [L, num_blocks*block_size, ...]
        (init_cache layout with the batch dim collapsed into the pool)."""
        n_pos = self.cfg.num_blocks * self.cfg.block_size
        cache = self.model.init_cache_fn(1, n_pos, self.kv_cache_dtype)
        return jax.tree.map(lambda a: a[:, 0], cache)

    # ------------------------------------------------------- jitted fns
    def _instrument(self, name: str, fn, variant=None):
        """``_jit_device_local`` plus the ISSUE 13 cost model: the first
        invocation of each program variant traces ``fn`` once more (no
        compile) and registers a CostReport — dot FLOPs, weight-stream
        HBM bytes, pallas launch sites, collective bytes — publishing
        ``perf/*`` gauges into this scheduler's registry.

        ``variant(args) -> (suffix, weight_passes)`` resolves per-call
        program variants: the k-step fused decode scans k FULL weight
        passes per execution and jit compiles one program per k, so
        each k is its own cost family (``serve/decode:k8``) with a
        k-scaled byte model — one shared report would understate the
        floor by k.  Analysis failure (or DS_PERF_COSTMODEL=0) degrades
        to plain jit; it never blocks a step."""
        jitted = _jit_device_local(fn)
        if not self._costmodel_on:
            return jitted
        analyzed = set()
        stream = self._cost_stream or {}

        def wrapper(*args):
            vname, passes = name, 1
            if variant is not None:
                try:
                    suffix, passes = variant(args)
                    vname = name + suffix
                except Exception:           # malformed packing: keep base
                    vname, passes = name, 1
            if vname not in analyzed:
                analyzed.add(vname)
                try:
                    from deepspeed_tpu.telemetry.costmodel import analyze_fn
                    from deepspeed_tpu.telemetry.roofline import \
                        publish_report
                    base = stream.get("weights_floor_bytes")
                    report = analyze_fn(
                        fn, *args, name=vname,
                        hbm_bytes=None if base is None else base * passes,
                        detail=dict(
                            {k: v for k, v in stream.items()
                             if isinstance(v, int)},
                            weight_passes=passes))
                    publish_report(self.metrics.registry, report)
                except Exception as e:      # noqa: BLE001 — best-effort
                    logger.warning(f"costmodel: {vname} analysis "
                                   f"failed: {e}")
            return jitted(*args)

        return wrapper

    def _prefill_fn(self, sp: int):
        if sp not in self._prefill_fns:
            model, kv_dtype = self.model, self.kv_cache_dtype
            cache_len = _round_up(sp, 64)

            def fn(params, pool, tokens, length, dest_idx, lora=None):
                cache = model.init_cache_fn(1, cache_len, kv_dtype)
                if lora is None:
                    logits, cache = model.prefill_fn(
                        params, {"input_ids": tokens}, cache)
                else:
                    logits, cache = model.prefill_fn(
                        params, {"input_ids": tokens}, cache, lora=lora)
                pool = jax.tree.map(
                    lambda p, c: p.at[:, dest_idx].set(c[:, 0, :sp]),
                    pool, cache)
                return logits[0, length[0] - 1][None], pool

            self._prefill_fns[sp] = self._instrument(
                f"serve/prefill:sp{sp}", fn)
        return self._prefill_fns[sp]

    def _sample1_fn(self, any_sampling: bool):
        if any_sampling not in self._sample1_fns:
            self._sample1_fns[any_sampling] = _jit_device_local(
                lambda lg, s, pos, t, k, p, d: _sample_rows(
                    lg, s, pos, t, k, p, d, any_sampling))
        return self._sample1_fns[any_sampling]

    def _decode_fn(self, any_sampling: bool):
        """Multi-step decode program: ``dest_steps [k, B]`` carries the
        pre-allocated pool destination per fused iteration; a lax.scan
        runs k gather→decode→scatter→sample iterations on device,
        amortizing per-step dispatch (k=1 is plain single-step)."""
        key = any_sampling
        if key not in self._decode_fns:
            model = self.model

            def fn(params, pool, ints, floats, do_flags, pos_idx,
                   lora=None):
                # ints [4+k, B]: tokens, lengths, seeds, top_ks,
                # dest_steps[k]; floats [2, B]: temps, top_ps.  One packed
                # array per dtype — per-call device_put overhead measured
                # ~40% of toy-scale serving wall time with 11 loose args
                tokens, lengths, seeds, top_ks = ints[0], ints[1], \
                    ints[2], ints[3]
                dest_steps = ints[4:]
                temps, top_ps = floats[0], floats[1]
                B = tokens.shape[0]
                rows = jnp.arange(B)

                def body(carry, dest_idx):
                    pool, toks, lens = carry
                    dense = jax.tree.map(lambda p: p[:, pos_idx], pool)
                    if lora is None:
                        logits, new_cache = model.decode_fn(
                            params, toks, dense, lens)
                    else:
                        logits, new_cache = model.decode_fn(
                            params, toks, dense, lens, lora=lora)
                    # the ONE vector decode wrote per row, back to the pool
                    new_vecs = jax.tree.map(
                        lambda c: c[:, rows, lens], new_cache)
                    pool = jax.tree.map(
                        lambda p, nv: p.at[:, dest_idx].set(nv),
                        pool, new_vecs)
                    nxt = _sample_rows(logits, seeds, lens + 1, temps,
                                       top_ks, top_ps, do_flags,
                                       any_sampling)
                    return (pool, nxt, lens + 1), nxt

                (pool, _, _), toks = jax.lax.scan(
                    body, (pool, tokens, lengths), dest_steps)
                return toks, pool               # toks [k, B]

            # ints [4+k, B]: the scan length k IS the weight-pass
            # count of one execution (see _instrument docstring)
            self._decode_fns[key] = self._instrument(
                "serve/decode", fn,
                variant=lambda args: (f":k{args[2].shape[0] - 4}",
                                      args[2].shape[0] - 4))
        return self._decode_fns[key]

    def _window_fn(self, W: int, any_sampling: bool):
        """THE batched-window program (ISSUE 12): one compiled family —
        keyed only by (window bucket, sampling?) — through which plain
        decode rows (window width 1), speculative-verify windows
        (ISSUE 5), and chunked-prefill chunks (ISSUE 9) all ride the
        SAME per-layer weight pass: one dense pool gather, the model's
        ``verify_fn`` (the fused megakernel path when enabled — ONE
        Pallas call per layer), ONE windowed scatter back, and the
        accept/emit math on device.  This replaces the PR 5 verify
        family and the PR 9 per-request chunk programs: a prefill chunk
        now amortizes the decode batch's weight stream instead of
        paying its own (Sarathi-style piggybacking).

        Packing: ints [4 + 2W, B] — rows 0..W-1 window tokens (decode
        rows: col 0 = last committed token then padded drafts; chunk
        rows: the prompt slice at the cursor), W: first window position
        (decode: seq-1; chunk: cursor), W+1: draft_len (chunk rows:
        take-1 so the bonus column lands on the chunk's last real
        position), W+2: seeds, W+3: top_ks, W+4..: per-window-position
        pool destinations (pads point at the trash block); floats
        [2, B]: temps, top_ps."""
        key = (W, any_sampling)
        if key not in self._window_fns:
            from deepspeed_tpu.serving.spec.verifier import (accept_tokens,
                                                             scan_verify_fn)
            model = self.model
            vf = model.verify_fn
            if vf is None or os.environ.get("DS_SPEC_VERIFY") == "scan":
                vf = scan_verify_fn(model.decode_fn)

            def fn(params, pool, ints, floats, do_flags, pos_idx,
                   lora=None):
                tokens = ints[:W].T                     # [B, W]
                lengths = ints[W]
                draft_len = ints[W + 1]
                seeds, top_ks = ints[W + 2], ints[W + 3]
                dests = ints[W + 4:]                    # [W, B]
                temps, top_ps = floats[0], floats[1]
                B = tokens.shape[0]
                rows = jnp.arange(B)
                dense = jax.tree.map(lambda p: p[:, pos_idx], pool)
                if lora is None:
                    logits, new_cache = vf(params, tokens, dense, lengths)
                else:
                    # adapters need the model's real verify surface (the
                    # scan-of-decode fallback has no lora plumbing);
                    # lora_serving models always expose verify_fn
                    logits, new_cache = model.verify_fn(
                        params, tokens, dense, lengths, lora=lora)
                # ONE windowed scatter for the whole batch: clamped
                # GATHER of each row's window from the dense view (the
                # _suffix_prefill_fn clamp reasoning — pad rows whose
                # window overruns s_pad read clamped positions but their
                # dests point at the trash block), then one flat .set
                win_pos = lengths[:, None] + jnp.arange(W)[None, :]
                flat = dests.T.reshape(-1)              # [B*W]
                pool = jax.tree.map(
                    lambda p, c: p.at[:, flat].set(
                        c[:, rows[:, None],
                          jnp.minimum(win_pos, c.shape[2] - 1)].reshape(
                            (c.shape[0], B * W) + c.shape[3:])),
                    pool, new_cache)
                acc, out = accept_tokens(
                    logits, tokens, draft_len, seeds, lengths + 1,
                    temps, top_ks, top_ps, do_flags, any_sampling)
                return acc, out, pool

            self._window_fns[key] = self._instrument(
                f"serve/window:w{W}", fn)
        return self._window_fns[key]

    def _window_bucket(self, need: int) -> int:
        """Window widths compile per bucket: 1 and 2 exactly (the plain
        and minimal-draft steps), then SUFFIX_BUCKET multiples — one
        family covers spec verify AND chunk service up to SUFFIX_CHUNK
        (wider drafts keep rounding up, so program count stays
        bounded)."""
        if need <= 2:
            return need
        return _round_up(need, self.SUFFIX_BUCKET)

    #: suffix-prefill chunk width (ISSUE 6): cached-prefix admissions
    #: prefill only the uncached tail, riding the verify-window path in
    #: chunks of at most this many tokens — one weight pass per chunk,
    #: and a bounded compiled-program set (W ∈ SUFFIX_BUCKET-multiples up
    #: to 64) instead of one W-unrolled program per suffix length
    SUFFIX_CHUNK = 64
    #: finer than PROMPT_BUCKET: the window unrolls per-position
    #: attention, so rounding a 5-token tail up to 16 doubles its cost
    SUFFIX_BUCKET = 8

    def _suffix_prefill_fn(self, W: int):
        """Prefix-cache suffix prefill (ISSUE 6): score ``W`` prompt-tail
        tokens at positions ``length..length+W-1`` against the request's
        pool-gathered cache — the cached prefix supplies positions below
        ``length`` — and scatter the window's KV vectors back (pad
        positions land in the trash block).  This IS the speculative
        verify surface (`models/serving.py verify_window`, or the
        scan-of-decode fallback for families without it): one weight
        pass scores the whole window with per-position causal attention,
        exactly what a resume-style re-prefill of the suffix needs."""
        if W not in self._suffix_prefill_fns:
            from deepspeed_tpu.serving.spec.verifier import scan_verify_fn
            model = self.model
            vf = model.verify_fn
            if vf is None or os.environ.get("DS_SPEC_VERIFY") == "scan":
                vf = scan_verify_fn(model.decode_fn)

            def fn(params, pool, tokens, length, dests, pos_idx,
                   lora=None):
                # tokens [1, W]; length [1] = first suffix position;
                # dests [W] flat pool destinations; pos_idx [1, S_pad]
                dense = jax.tree.map(lambda p: p[:, pos_idx], pool)
                if lora is None:
                    logits, new_cache = vf(params, tokens, dense, length)
                else:
                    logits, new_cache = model.verify_fn(
                        params, tokens, dense, length, lora=lora)
                # ONE gather+scatter for the whole window (a per-position
                # .set loop would copy the full pool W times on backends
                # that don't fuse the chain).  Clamped GATHER, not a
                # dynamic_slice: when the padded window overruns the
                # dense width (a prompt ending within W of s_pad) a
                # dynamic_slice would clamp its START and silently
                # misalign every row against dests — the clamp here only
                # affects pad rows, whose dests point at the trash block
                win = jax.tree.map(
                    lambda c: c[:, 0][:, jnp.minimum(
                        length[0] + jnp.arange(W), c.shape[2] - 1)],
                    new_cache)
                pool = jax.tree.map(
                    lambda p, w: p.at[:, dests].set(w), pool, win)
                return logits, pool             # logits [1, W, V]

            self._suffix_prefill_fns[W] = _jit_device_local(fn)
        return self._suffix_prefill_fns[W]

    def _cow_copy(self, pair):
        """Execute a copy-on-write fork's physical KV move: duplicate the
        shared source block's pool positions into the request's private
        destination block (the BlockManager already swapped the table
        entry).  One jitted gather/scatter, reused for every fork."""
        if self._copy_fn is None:
            self._copy_fn = _jit_device_local(lambda pool, src, dst: jax.tree.map(
                lambda p: p.at[:, dst].set(p[:, src]), pool))
        src, dst = pair
        bs = self.block_mgr.block_size
        self.pool = self._copy_fn(
            self.pool,
            jnp.arange(src * bs, (src + 1) * bs, dtype=jnp.int32),
            jnp.arange(dst * bs, (dst + 1) * bs, dtype=jnp.int32))

    # ----------------------------------------------------- tiered KV (16)
    def _extract_block(self, block: int):
        """Snapshot one block's physical payload as host numpy leaves
        (the BlockManager's demotion extractor).  device_get of a pool
        slice per leaf — bit-exact, dtype-preserving (int8 KV
        included), so a later swap-in reproduces the block verbatim
        and tier hits stay token-identical."""
        bs = self.block_mgr.block_size
        lo, hi = block * bs, (block + 1) * bs
        return [np.asarray(leaf[:, lo:hi])
                for leaf in jax.tree_util.tree_leaves(self.pool)]

    def _write_block(self, block: int, arrays):
        """Scatter one swapped-in payload into its promoted pool block
        (the inverse of _extract_block): one jitted scatter, compiled
        once — same shape every time, the _cow_copy discipline."""
        if self._swapin_fn is None:
            self._swapin_fn = _jit_device_local(
                lambda pool, dst, vals: jax.tree.map(
                    lambda p, v: p.at[:, dst].set(v), pool, vals))
        bs = self.block_mgr.block_size
        vals = jax.tree_util.tree_unflatten(
            self._pool_treedef, [jnp.asarray(a) for a in arrays])
        self.pool = self._swapin_fn(
            self.pool,
            jnp.arange(block * bs, (block + 1) * bs, dtype=jnp.int32),
            vals)

    def _schedule_swapins(self, req, entries) -> bool:
        """Queue the async swap-in for a tier-matched prompt's cold
        entries; the request sits out admission (still QUEUED) until
        the next step materializes them — the reads overlap THIS
        step's decode instead of blocking it.  True = scheduled."""
        cold = [h for tier, _, h in entries if tier != "hbm"]
        if not cold or req.request_id in self._swap_pending:
            return False
        for h in cold:
            self._tier_store.prefetch(h, corr=f"req-{req.request_id}")
        # pend the WHOLE chain, hot entries included: materialization
        # must pin the already-hot blocks against its own promote-cap
        # trim, or a small max_cached_blocks demotes block k while
        # promoting block k+1 of the same prefix and the request
        # re-matches cold forever (swap-in livelock)
        self._swap_pending[req.request_id] = [h for _, _, h in entries]
        return True

    def _materialize_swapins(self):
        """Complete pending swap-ins (scheduled on an earlier step, so
        the I/O has already overlapped at least one decode iteration):
        fetch each payload, re-register its hash as an HBM cache entry
        (BlockManager.promote), and scatter the bytes into the promoted
        block — the normal prefix-cache admission path then attaches it
        like any hot hit.  A failed fetch (kv.swap fault, torn NVMe
        payload, I/O error) drops the rest of the chain: those blocks
        simply re-prefill — degraded, never corrupt."""
        if self._tier_store is None or not self._swap_pending:
            return
        queued = {r.request_id for r in self._queue}
        c = self.metrics.counters
        promoted = set()        # this pass's blocks: cap-trim exempt
        for rid in list(self._swap_pending):
            hashes = self._swap_pending.pop(rid)
            if rid not in queued:
                continue        # expired/extracted; entries stay cached
            for h in hashes:
                hot = self.block_mgr._by_hash.get(h)
                if hot is not None:
                    promoted.add(hot)   # pin the chain's hot prefix
                    continue
                got = self._tier_store.fetch(h, corr=f"req-{rid}")
                if got is None:
                    break       # degrade: the remainder re-prefills
                tier, arrays = got
                b = self.block_mgr.promote(h, protect=promoted)
                if b is None:   # pool exhausted mid-promotion
                    break
                promoted.add(b)
                self._write_block(b, arrays)
                if tier == "host":
                    c["kv_tier_hit_host"] += 1
                else:
                    c["kv_tier_hit_nvme"] += 1

    # ----------------------------------------------------------- submit
    def submit(self, prompt_ids, sampling=None, priority: int = 0,
               timeout_s: float = 0.0, slo_class: str = "default",
               adapter_id: Optional[str] = None) -> ServeRequest:
        """Enqueue a request; raises AdmissionError (429-style) instead of
        crashing or wedging the loop.  ``slo_class`` names the request's
        ``serving.slo`` class for burn accounting AND admission control
        (unknown classes fall back to ``default``): with
        ``serving.slo.shed_enabled``, a saturated system sheds the
        lowest-priority classes here with a RequestShedError carrying
        the Retry-After hint (ISSUE 9).  ``adapter_id`` selects the
        tenant's LoRA adapter (ISSUE 20): unknown ids raise the typed
        UnknownAdapterError (a 4xx at the front door, never a 500), and
        a request submitted with the DEFAULT class inherits its
        tenant's ``serving.adapters.slo_class_map`` class."""
        from deepspeed_tpu.serving.request import (RequestShedError,
                                                   SamplingParams,
                                                   UnknownAdapterError)
        with self._lock:
            req = ServeRequest(
                request_id=self._next_id,
                prompt_ids=prompt_ids,
                sampling=sampling or SamplingParams(),
                priority=priority, timeout_s=timeout_s,
                slo_class=slo_class, adapter_id=adapter_id)
            # consume the id for REJECTED requests too: a reject's
            # flight-recorder event must never share its req-<id> corr
            # with a later accepted request's timeline
            self._next_id += 1
            if adapter_id is not None:
                if (self.adapter_registry is None
                        or adapter_id not in self.adapter_registry):
                    req.state = RequestState.REJECTED
                    req.reject_reason = (
                        f"unknown adapter {adapter_id!r}"
                        if self.adapter_registry is not None else
                        f"adapter {adapter_id!r} requested but "
                        "serving.adapters is not enabled")
                    self.metrics.counters["adapter_unknown"] += 1
                    self.flightrec.record(
                        "req/reject", corr=f"req-{req.request_id}",
                        reason="adapter_unknown", adapter=adapter_id)
                    req.done.set()
                    raise UnknownAdapterError(req.reject_reason)
                if slo_class == "default":
                    # per-tenant QoS (ISSUE 9 ladder): the tenant's
                    # mapped class drives shedding, admission order,
                    # chunk service, and preemption below
                    mapped = self.adapter_store.slo_class_for(adapter_id)
                    if mapped:
                        slo_class = mapped
                        req.slo_class = mapped
            total = req.prompt_len + req.sampling.max_new_tokens
            if total > self.max_model_len \
                    or not self.block_mgr.fits_ever(total):
                req.state = RequestState.REJECTED
                req.reject_reason = (
                    f"prompt+max_new_tokens={total} exceeds serving "
                    f"capacity {self.max_model_len}")
                self.metrics.counters["rejected_too_long"] += 1
                self.flightrec.record("req/reject",
                                      corr=f"req-{req.request_id}",
                                      reason="too_long", tokens=total)
                req.done.set()
                raise RequestTooLongError(req.reject_reason)
            # SLO admission control (ISSUE 9): under saturation (burn
            # rates over threshold / queue pressure), classes below the
            # shed cutoff 429 here — BEFORE the queue-full check, so
            # low-class traffic can't fill the queue against the
            # classes the system is still meeting targets for
            cut = self.slo.shed_cutoff(len(self._queue),
                                       self.cfg.max_queued)
            if cut is not None and \
                    self.slo.class_priority(slo_class) < cut["priority"]:
                req.state = RequestState.REJECTED
                req.reject_reason = (
                    f"shed class {self.slo.resolve_class(slo_class)!r} "
                    f"under overload ({cut['reason']}); retry after "
                    f"{self.slo.retry_after_s:g}s")
                self.metrics.counters["rejected_shed"] += 1
                self.flightrec.record(
                    "req/reject", corr=f"req-{req.request_id}",
                    reason="shed",
                    slo_class=self.slo.resolve_class(slo_class))
                req.done.set()
                raise RequestShedError(req.reject_reason,
                                       self.slo.retry_after_s)
            if len(self._queue) >= self.cfg.max_queued:
                req.state = RequestState.REJECTED
                req.reject_reason = (
                    f"queue full ({self.cfg.max_queued} waiting)")
                self.metrics.counters["rejected_queue_full"] += 1
                self.flightrec.record("req/reject",
                                      corr=f"req-{req.request_id}",
                                      reason="queue_full")
                req.done.set()
                raise QueueFullError(req.reject_reason)
            self.metrics.counters["received"] += 1
            self._queue.append(req)
            self.flightrec.record("req/queue", corr=f"req-{req.request_id}",
                                  prompt_tokens=req.prompt_len,
                                  max_new=req.sampling.max_new_tokens,
                                  priority=priority, slo_class=slo_class,
                                  adapter=adapter_id)
            return req

    # ------------------------------------------------------------ state
    def active_requests(self) -> List[ServeRequest]:
        with self._lock:
            return [r for r in self._slots if r is not None]

    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    def has_work(self) -> bool:
        with self._lock:
            return bool(self._queue) or any(
                r is not None for r in self._slots)

    def has_work_unlocked(self) -> bool:
        """Lock-free (racy) variant for the watchdog: a wedged step()
        holds the scheduler lock for its whole duration — exactly the
        condition the watchdog must be able to observe without joining
        the deadlock.  GIL-atomic list reads are plenty for a stall
        heuristic."""
        return bool(self._queue) or any(
            r is not None for r in self._slots)

    def outstanding_tokens_unlocked(self) -> int:
        """Lock-free outstanding-work estimate for the fleet router's
        least-loaded policy (ISSUE 11): prefill tokens still owed plus
        decode tokens still to emit, over queued AND active requests.
        Same GIL-atomic-snapshot reasoning as ``has_work_unlocked`` — a
        dispatch decision must not queue behind a long step, and an
        estimate a few tokens stale routes just as well."""
        total = 0
        for r in list(self._queue):
            total += r.prompt_len + max(r.remaining_new_tokens, 0)
        for r in list(self._slots):
            if r is None:
                continue
            total += max(r.remaining_new_tokens, 0)
            inputs = r.prefill_inputs
            if inputs is not None:
                total += max(int(inputs.size) - r.prefill_pos, 0)
        return total

    def extract_for_resubmit(self, include_active: bool = True
                             ) -> List[ServeRequest]:
        """Fleet drain support (ISSUE 11): remove every queued request
        and — with ``include_active`` — evict every active row through
        the standard eviction path (blocks released into the prefix
        cache, committed generated tail preserved on the request), then
        hand them ALL back without completing them.  The caller (the
        fleet Router) resubmits each as a fresh request — prompt plus
        the generated-so-far tail — on a healthy replica; recompute-on-
        resume semantics make the continued stream token-identical to
        the uninterrupted one.  ``done`` is never set here: the original
        request objects are abandoned carriers, not completions."""
        with self._lock:
            extracted = list(self._queue)
            self._queue.clear()
            if include_active:
                for req in list(self._slots):
                    if req is None:
                        continue
                    # the standard eviction frees blocks (publishing
                    # committed full blocks to the cache) and requeues —
                    # reclaim it from the queue it just joined
                    self._evict(req)
                    self._queue.remove(req)
                    extracted.append(req)
            return extracted

    @property
    def step_count(self) -> int:
        return self._step_count

    def metrics_snapshot(self) -> Dict[str, float]:
        """Locked snapshot for readers outside the scheduler loop (the
        /metrics endpoint) — the loop thread mutates the counter dict
        and histograms mid-step."""
        with self._lock:
            return self.metrics.snapshot()

    def render_metrics(self, extra_labels=None) -> str:
        """Prometheus text for the /metrics endpoint (locked, same
        exposition function as the training-side metrics server).  The
        fleet front-end passes ``extra_labels={"replica": "<id>"}`` so
        N replicas merge into one labeled exposition (ISSUE 11).  On a
        multi-tenant server (serving.adapters) or once install_params
        has ever hot-swapped the base weights, every series additionally
        carries ``weights_version`` (ISSUE 20) so the live roll is
        attributable in dashboards."""
        labels = dict(extra_labels or {})
        if self.adapter_store is not None or self._weights_swapped:
            labels.setdefault("weights_version", self.weights_version)
        with self._lock:
            return self.metrics.render_prometheus(extra_labels=labels)

    # ------------------------------------------------- debug introspection
    # Both views below are deliberately LOCK-FREE (ISSUE 7): they exist
    # to answer "what is the scheduler doing" while a wedged step()
    # holds the scheduler lock — the same reasoning as the watchdog's
    # has_work_unlocked.  Reads are GIL-atomic snapshots of plain
    # attributes; a view racing a live step may be internally slightly
    # inconsistent (a request mid-retire, say), which is acceptable for
    # forensics and unacceptable to deadlock on.

    @staticmethod
    def _debug_request(req: ServeRequest, now: float) -> Dict:
        return {
            "request_id": req.request_id,
            "state": req.state.value,
            "slot": req.slot,
            "priority": req.priority,
            "slo_class": req.slo_class,
            "adapter_id": req.adapter_id,
            "prompt_tokens": req.prompt_len,
            "generated": req.num_generated,
            "max_new_tokens": req.sampling.max_new_tokens,
            "cached_tokens": req.num_cached_tokens,
            "preemptions": req.num_preemptions,
            "age_s": round(now - req.arrival_time, 3),
            "ttft_ms": (round(req.ttft_s * 1e3, 3)
                        if req.ttft_s is not None else None),
            "spec_k": req.spec_k,
            "spec_disabled": req.spec_disabled,
            "prefill_cursor": req.prefill_pos,
            "prefill_total": (int(req.prefill_inputs.size)
                              if req.prefill_inputs is not None else None),
        }

    def debug_requests(self) -> Dict:
        """The ``/debug/requests`` body: every queued + active request's
        live state (lock-free snapshot)."""
        now = time.monotonic()
        active = [self._debug_request(r, now)
                  for r in list(self._slots) if r is not None]
        queued = [self._debug_request(r, now) for r in list(self._queue)]
        return {"step_count": self._step_count,
                "active": active, "queued": queued}

    def debug_scheduler(self) -> Dict:
        """The ``/debug/scheduler`` body: scheduler + block-pool +
        prefix-cache + spec + SLO state (lock-free snapshot)."""
        bm = self.block_mgr
        slots = [r.request_id if r is not None else None
                 for r in list(self._slots)]
        out = {
            "step_count": self._step_count,
            "queue_depth": len(self._queue),
            "max_num_seqs": self.cfg.max_num_seqs,
            "max_model_len": self.max_model_len,
            "slots": slots,
            "block_pool": {
                "num_blocks": self.cfg.num_blocks,
                "block_size": bm.block_size,
                "free": bm.num_free_blocks,
                "cached": bm.num_cached_blocks,
                "allocated": bm.num_allocated_blocks,
                "utilization": round(bm.utilization(), 4),
                "cache_evictions": bm.cache_evictions,
            },
            "prefix_cache": {
                "enabled": self._prefix_cache_on,
                "min_prefix_blocks": self._prefix_min_blocks,
                "hits": int(self.metrics.counters["prefix_cache_hit"]),
                "misses": int(self.metrics.counters["prefix_cache_miss"]),
                "cow_forks": int(
                    self.metrics.counters["prefix_cache_cow_forks"]),
            },
            "spec": {
                "proposer": (type(self.proposer).__name__
                             if self.proposer is not None else None),
                "verify_steps": int(
                    self.metrics.counters["spec_verify_steps"]),
                "drafted": int(
                    self.metrics.counters["spec_drafted_tokens"]),
                "accepted": int(
                    self.metrics.counters["spec_accepted_tokens"]),
            },
            "slo": {
                "enabled": self.slo.enabled,
                "classes": sorted(self.slo.classes),
                "priorities": dict(self.slo.priorities),
                "shed_enabled": self.slo.shed_enabled,
                "burn_rates": self.slo.burn_rates(),
                "violations": int(self.metrics.counters["slo_violations"]),
                "shed": int(self.metrics.counters["rejected_shed"]),
            },
            "chunked_prefill": {
                "enabled": self._chunked_on,
                "chunk_tokens": self._chunk_tokens,
                "chunks_deferred": int(
                    self.metrics.counters["chunks_deferred"]),
                "prefilling": [
                    {"request_id": r.request_id,
                     "cursor": r.prefill_pos,
                     "total": (int(r.prefill_inputs.size)
                               if r.prefill_inputs is not None else None)}
                    for r in list(self._slots) if r is not None
                    and r.state == RequestState.PREFILLING],
            },
            "kv_tiering": ({"enabled": False}
                           if self._tier_store is None else dict(
                               {"enabled": True,
                                "park_on_preempt": self._park_on_preempt,
                                "demoted_not_evicted": bm.cache_demotions,
                                "pending_swapins": len(self._swap_pending)},
                               **self._tier_store.summary())),
            "adapters": ({"enabled": False}
                         if self.adapter_store is None else dict(
                             {"enabled": True,
                              "registered": sorted(
                                  self.adapter_registry.ids()),
                              "pending_swapins": len(self._adapter_pending),
                              "weights_version": self.weights_version},
                             **self.adapter_store.summary())),
        }
        return out

    # -------------------------------------------------------- lifecycle
    def _committed_tokens(self, req: ServeRequest) -> Optional[int]:
        """KV-materialized token count for cache publication: a
        PREFILLING request has KV only up to its committed chunk cursor
        (ISSUE 9); everything else uses register_committed's default
        (all but the newest sampled token)."""
        if req.state == RequestState.PREFILLING:
            return req.prefill_pos
        return None

    def _retire(self, req: ServeRequest, state: RequestState,
                reason: Optional[str] = None):
        if self.proposer is not None:
            self.proposer.release(req.request_id)
        # release INTO the cache (ISSUE 6): hash any last full blocks,
        # then free — hashed blocks park on the LRU for the next request
        self.block_mgr.register_committed(
            req.request_id, req.all_token_ids,
            materialized=self._committed_tokens(req),
            salt=req.adapter_id)
        self.block_mgr.free(req.request_id)
        if req.adapter_pinned:
            self.adapter_store.release(req.adapter_id)
            req.adapter_pinned = False
        req.prefill_inputs = None
        req.prefill_pos = 0
        if req.slot >= 0:
            self._slots[req.slot] = None
            req.slot = -1
        req.state = state
        if reason is not None:
            req.reject_reason = reason
        if state == RequestState.FINISHED:
            req.t_finish = time.monotonic()
            self.metrics.observe_finished(req)
            if self.adapter_store is not None:
                # per-tenant label (ISSUE 20): one series per adapter
                self.metrics.registry.inc("serving/tenant_completed",
                                          adapter=req.adapter_id or "base")
            self._finished_this_step.append(req)
            # SLO burn accounting (ISSUE 7): score the finished request
            # against its class targets; TPOT = mean inter-token gap
            times = req.token_times
            tpot = ((times[-1] - times[0]) / (len(times) - 1)
                    if len(times) > 1 else None)
            viol = self.slo.observe(req.slo_class, req.ttft_s, tpot)
            if viol:
                self.metrics.counters["slo_violations"] += 1
                self.flightrec.record(
                    "req/slo_violation", corr=f"req-{req.request_id}",
                    slo_class=self.slo.resolve_class(req.slo_class),
                    **{k: True for k in viol})
        self.flightrec.record(
            "req/retire", corr=f"req-{req.request_id}",
            state=state.value, generated=req.num_generated,
            ttft_ms=(round(req.ttft_s * 1e3, 3)
                     if req.ttft_s is not None else None),
            reason=reason, adapter=req.adapter_id,
            version=self.weights_version)
        req.done.set()

    def _evict(self, victim: ServeRequest):
        """Preempt: free blocks+slot, requeue for recompute-on-resume.
        With the prefix cache on, the victim's full blocks are hashed
        first — resume re-matches them and re-prefills (close to)
        nothing instead of the whole prompt+generated tail.  A victim
        caught MID-PREFILL (PREFILLING, ISSUE 9) publishes only up to
        its committed chunk cursor — re-admission resumes from the last
        committed chunk, never from half-written KV."""
        if self.proposer is not None:
            self.proposer.release(victim.request_id)
        self.block_mgr.register_committed(
            victim.request_id, victim.all_token_ids,
            materialized=self._committed_tokens(victim),
            salt=victim.adapter_id)
        victim_table = list(self.block_mgr.block_table(victim.request_id))
        self.block_mgr.free(victim.request_id)
        if self._tier_store is not None and self._park_on_preempt:
            # park the victim's whole committed KV on NVMe NOW (ISSUE
            # 16): preemption means pool pressure, so freeing the HBM
            # beats LRU retention — and resume becomes a swap-in, not a
            # re-prefill.  Only exclusively-owned hashed blocks move;
            # shared ones stay hot for their other owners.
            parked = self.block_mgr.park_blocks(victim_table)
            if parked:
                self.flightrec.record("kv/park",
                                      corr=f"req-{victim.request_id}",
                                      blocks=parked)
        victim.prefill_inputs = None
        victim.prefill_pos = 0
        if victim.slot >= 0:
            self._slots[victim.slot] = None
            victim.slot = -1
        if victim.adapter_pinned:
            # unpin: a preempted tenant's adapter becomes an ordinary
            # LRU citizen — it may demote to host/NVMe before resume,
            # and re-admission pays a swap-in, not a failure
            self.adapter_store.release(victim.adapter_id)
            victim.adapter_pinned = False
        victim.state = RequestState.EVICTED
        victim.num_preemptions += 1
        victim.queued_at = time.monotonic()    # timeout clock restarts
        self.metrics.counters["preemptions"] += 1
        self.flightrec.record("req/preempt",
                              corr=f"req-{victim.request_id}",
                              generated=victim.num_generated,
                              priority=victim.priority)
        self._queue.append(victim)
        logger.info(f"serving: preempted request {victim.request_id} "
                    f"(priority {victim.priority}, "
                    f"{victim.num_generated} tokens generated)")

    def _expire_queued(self):
        now = time.monotonic()
        for req in list(self._queue):
            if req.timeout_s > 0 and now - req.queued_at > req.timeout_s:
                self._queue.remove(req)
                self.metrics.counters["rejected_timeout"] += 1
                req.state = RequestState.REJECTED
                req.reject_reason = f"timed out after {req.timeout_s}s queued"
                # terminal flight event: without it a timed-out request's
                # timeline ends at req/queue and reads as still in flight
                self.flightrec.record("req/reject",
                                      corr=f"req-{req.request_id}",
                                      reason="timeout",
                                      queued_s=round(now - req.queued_at, 3))
                req.done.set()

    # -------------------------------------------------------- admission
    def _qos_key(self, req: ServeRequest):
        """Scheduling order (ISSUE 9): SLO class priority first, then
        per-request priority, then eviction count (aging — a request
        preempted N times stops being the perpetual victim among its
        peers and re-admits ahead of them), then arrival (oldest wins).
        ``max`` over this key picks the front of the admission line and
        the next chunk to service; ``min`` picks the preemption victim —
        so the lowest class yields pool and compute first.  Without the
        aging term, equal-priority traffic under recurring pool pressure
        could re-elect the same PREFILLING row every cycle and (with the
        prefix cache off, where committed chunks don't persist) restart
        its prefill from zero forever."""
        return (self.slo.class_priority(req.slo_class), req.priority,
                req.num_preemptions, -req.arrival_time)

    def _prefill_allowance(self) -> int:
        """Per-iteration prefill token allowance under chunked prefill:
        at most ``chunk_tokens``, shrunk when active decode rows claim
        their share of ``max_num_batched_tokens`` (one budget, shared),
        floored at one SUFFIX_BUCKET so prefill always progresses — a
        saturated decode batch slows chunking down, never starves it."""
        decode_rows = sum(1 for r in self._slots if r is not None
                          and r.state == RequestState.DECODE)
        allow = min(self._chunk_tokens,
                    self.cfg.max_num_batched_tokens - decode_rows)
        return max(allow, self.SUFFIX_BUCKET)

    def _admit(self):
        """Admit queued prefills (highest SLO class, then priority, then
        oldest, first) into free slots, bounded by the step token budget
        and the pool.

        With the prefix cache on (ISSUE 6), each prompt is first matched
        block-by-block against the cache: matched blocks attach to the
        request's table with a ref bump and prefill starts at the first
        uncached token — a fully cached prompt re-scores only its last
        token, into a copy-on-write fork of the final shared block.  A
        failed attach (pool pressure mid-admission, or an injected
        ``kv.cache`` fault) degrades to a plain full prefill, never to a
        corrupted table.

        With chunked prefill on (ISSUE 9) the token budget is a REAL
        per-iteration cap: an admission whose uncached prefill fits the
        remaining chunk allowance still runs the one-shot prefill
        program here; anything larger enters PREFILLING with a progress
        cursor and is serviced chunk-by-chunk by ``_window_step`` —
        the old first-admission escape (one 32k prompt monopolizing an
        iteration, spiking every active stream's TPOT) is gone."""
        budget = self.cfg.max_num_batched_tokens
        chunked = self._chunked_on
        allow = self._prefill_allowance() if chunked else budget
        bm = self.block_mgr
        spent = 0
        # tiered KV (ISSUE 16): swap-ins scheduled on an earlier step
        # materialize first — their hashes re-enter the HBM cache and
        # the owning requests re-enter the admission line below
        self._materialize_swapins()
        self._materialize_adapter_swapins()
        while self._queue:
            free_slots = [i for i, r in enumerate(self._slots) if r is None]
            if not free_slots:
                break
            # a request waiting on an in-flight swap-in (KV tier or
            # adapter) sits out this round; others admit
            waiting = self._swap_pending or self._adapter_pending
            cands = ([r for r in self._queue
                      if r.request_id not in self._swap_pending
                      and r.request_id not in self._adapter_pending]
                     if waiting else self._queue)
            if not cands:
                break
            req = max(cands, key=self._qos_key)
            # multi-tenant LoRA (ISSUE 20): an admission whose adapter is
            # cold schedules the swap-in and sits out this round — the
            # swap overlaps the running decode, exactly like a cold-tier
            # prefix hit.  A swap that cannot even start (no tier holds
            # the payload) degrades per serving.adapters.fallback_to_base
            # or rejects typed.
            if (self.adapter_store is not None
                    and req.adapter_id is not None
                    and not self.adapter_store.resident(req.adapter_id)):
                if self._schedule_adapter_swapin(req):
                    continue
                if not self._adapter_failure(req):
                    continue
            resumed = req.state == RequestState.EVICTED
            tokens = req.all_token_ids
            # resume re-prefills everything but the last generated token —
            # decode recomputes that one's KV as it proceeds.  A request
            # evicted MID-PREFILL has generated nothing: its whole prompt
            # is the input and the first token is still owed (ISSUE 9)
            inputs = tokens[:-1] if resumed and req.num_generated \
                else tokens
            n_in = int(inputs.size)
            matched, start = ([], 0)
            if self._prefix_cache_on:
                matched, start = self._match_prefix(req, inputs, resumed)
                # tiered KV (ISSUE 16): a prompt whose prefix extends
                # into a cold tier schedules the async swap-in and sits
                # out this round — next step the promoted blocks are
                # ordinary HBM hits and the request pays a swap-in
                # instead of a re-prefill
                if self._tier_store is not None:
                    entries = bm.match_prefix_tiered(
                        inputs, salt=req.adapter_id)
                    if (len(entries) > len(matched)
                            and len(entries) >= self._prefix_min_blocks
                            and self._schedule_swapins(req, entries)):
                        continue
            # the budget meters PREFILL COMPUTE: cached tokens are free
            need = n_in - start
            if not chunked and spent and spent + need > budget:
                break
            # chunked: a prefill the remaining allowance can't absorb
            # defers into PREFILLING — it is still admitted (slot +
            # blocks) so chunk service can start next phase/iteration
            defer = chunked and need > allow - spent
            # blocks covering positions [0, n_in] — prefill fill plus the
            # first decode write — so admission never instantly preempts
            total = bm.blocks_for_tokens(n_in + 1)
            n_full = n_in // bm.block_size
            fork_pair = None
            c = self.metrics.counters
            if matched:
                # prefill writing INTO the matched region (the fully
                # cached prompt's last token) forks that block COW
                fork = start < len(matched) * bm.block_size
                n_fresh = total - len(matched) + (1 if fork else 0)
                got = bm.acquire_prefix(req.request_id, matched,
                                        n_fresh, fork)
                if got is None:
                    # degrade: full prefill — the whole prompt is now
                    # prefill compute, so the budget check re-runs
                    matched, start = ([], 0)
                    need = n_in
                    if not chunked and spent and spent + n_in > budget:
                        break
                    defer = chunked and need > allow - spent
                else:
                    fork_pair = got[1]
            if not matched:
                if not bm.can_allocate(total):
                    break
                # allocate BEFORE dequeueing: a denied allocation
                # (injected fault or free-list race) must leave the
                # request queued, not admit it blockless.  The failure
                # is an OOM-shaped event: snapshot the byte ledger
                # (ISSUE 14 forensics) so the post-mortem answers
                # "what held the pool when admission starved"
                if bm.allocate(req.request_id, total) is None:
                    self._record_alloc_failure(
                        "kv.alloc", request_id=req.request_id,
                        needed_blocks=total,
                        free_blocks=bm.num_free_blocks,
                        cached_blocks=bm.num_cached_blocks)
                    break
            self._queue.remove(req)
            if self._prefix_cache_on:
                # hits count at ATTACH on the admission that sticks, not
                # at lookup: a discarded match (below min_prefix_blocks,
                # attach denied) served nothing and must not inflate the
                # hit-rate gauge, and a request left queued by pool
                # pressure must not re-count its misses every retry
                c["prefix_cache_hit"] += len(matched)
                c["prefix_cache_miss"] += n_full - len(matched)
            req.state = RequestState.PREFILL
            req.slot = free_slots[0]
            self._slots[req.slot] = req
            req.num_cached_tokens = start
            if self.adapter_store is not None \
                    and req.adapter_id is not None:
                # pin the adapter for the request's whole residency —
                # refcount > 0 keeps the LRU from demoting it mid-decode
                self.adapter_store.acquire(req.adapter_id)
                req.adapter_pinned = True
                self.flightrec.record(
                    "req/adapter_attach", corr=f"req-{req.request_id}",
                    adapter=req.adapter_id,
                    adapter_slot=self.adapter_store.slot_of(req.adapter_id))
            self.flightrec.record(
                "req/resume" if resumed else "req/admit",
                corr=f"req-{req.request_id}", slot=req.slot,
                step=self._step_count, cached_tokens=start,
                prompt_tokens=n_in, deferred=bool(defer and need > 0),
                adapter=req.adapter_id, version=self.weights_version)
            if matched:
                self.flightrec.record(
                    "req/prefix_hit", corr=f"req-{req.request_id}",
                    blocks=len(matched), cached_tokens=start,
                    cow_fork=fork_pair is not None)
            self.metrics.observe_queue_wait(
                time.monotonic() - req.queued_at)
            if resumed:
                # goodput accounting: the generated tail re-prefilled
                # here is work the pool preemption threw away — a
                # cache re-hit of the request's own blocks shrinks it
                self.metrics.counters["recomputed_tokens"] += max(
                    0, n_in - max(start, req.prompt_len))
            if fork_pair is not None:
                self._cow_copy(fork_pair)
                self.metrics.counters["prefix_cache_cow_forks"] += 1
            if start >= n_in:
                # resumed request fully served from cache: nothing to
                # prefill, the generated tail is already sampled — straight
                # to decode (recomputed_tokens rides at 0)
                req.state = RequestState.DECODE
            elif defer:
                req.state = RequestState.PREFILLING
                req.prefill_inputs = inputs
                req.prefill_pos = start
            else:
                spent += need
                self._run_prefill(req, inputs, resumed, start)
            if resumed:
                self.metrics.counters["resumed"] += 1
        self._prefill_spent += spent

    def _match_prefix(self, req: ServeRequest, inputs: np.ndarray,
                      resumed: bool):
        """Cache lookup for one admission: returns (matched blocks,
        prefill-start token).  Fresh requests cap the start at the last
        prompt token — its logits seed sampling, so it must be re-scored
        even when its block is cached (the COW-fork case); resumed
        requests may skip prefill entirely."""
        from deepspeed_tpu.telemetry import get_tracer
        bm = self.block_mgr
        n_in = int(inputs.size)
        with get_tracer().span("serve/prefix_match", cat="serving",
                               corr=f"req-{req.request_id}",
                               args={"request_id": req.request_id,
                                     "prompt_tokens": n_in,
                                     "resumed": bool(resumed)}):
            # salt = adapter_id (ISSUE 20): one tenant's cached blocks
            # can never attach to another tenant's prompt
            blocks = bm.match_prefix(inputs, salt=req.adapter_id)
        # hit/miss accounting happens in _admit once the admission
        # sticks — lookups that don't end in an attach count as misses
        if len(blocks) < self._prefix_min_blocks:
            return [], 0
        start = len(blocks) * bm.block_size
        if not resumed and start >= n_in:
            start = n_in - 1
        return blocks, start

    def _run_prefill(self, req: ServeRequest, inputs: np.ndarray,
                     resumed: bool, start: int = 0):
        from deepspeed_tpu.telemetry import get_tracer
        with get_tracer().span("serve/prefill", cat="serving",
                               corr=f"req-{req.request_id}",
                               args={"request_id": req.request_id,
                                     "tokens": int(inputs.size) - start,
                                     "cached": int(start),
                                     "resumed": bool(resumed)}):
            self._run_prefill_traced(req, inputs, resumed, start)

    def _run_prefill_traced(self, req: ServeRequest, inputs: np.ndarray,
                            resumed: bool, start: int = 0):
        bm = self.block_mgr
        if start > 0:
            # cached-prefix admission: only the uncached suffix runs
            last_logits = self._suffix_prefill(req, inputs, start)
        else:
            sp = min(max(_round_up(inputs.size, self.PROMPT_BUCKET),
                         self.PROMPT_BUCKET), self.s_pad)
            padded = np.zeros((1, sp), np.int32)
            padded[0, :inputs.size] = inputs
            # flat pool destination per prompt position; pads write into
            # the trash block (positions 0..block_size-1), never a live
            # block
            dest = np.arange(sp) % bm.block_size
            pos = np.arange(inputs.size)
            dest[:inputs.size] = [bm.position_index(req.request_id, int(p))
                                  for p in pos]
            last_logits, self.pool = self._prefill_fn(sp)(
                self.params, self.pool, jnp.asarray(padded),
                jnp.asarray([inputs.size], np.int32), jnp.asarray(dest),
                *self._lora_arg([self._adapter_slot(req)]))
        self.metrics.counters["prefill_tokens"] += int(inputs.size) - start
        if start == 0:
            # the cached-suffix path records per chunk; this is the
            # one-shot full-prompt program
            self.flightrec.record("req/prefill_chunk",
                                  corr=f"req-{req.request_id}",
                                  tokens=int(inputs.size), offset=0,
                                  cursor=int(inputs.size))
        self._finish_prefill(req, inputs, last_logits)

    def _finish_prefill(self, req: ServeRequest, inputs: np.ndarray,
                        last_logits, tok: Optional[int] = None):
        """Shared prefill epilogue (one-shot, cached-suffix, and
        batched-window chunked completion): publish the prefilled blocks
        to the prefix cache, flip to DECODE, and emit the first token —
        sampled here from the last real position's logits, or passed in
        as ``tok`` when the window program's bonus column already drew
        it (same rng-position key family, so both forms are
        token-identical).  A request that already carries a generated
        tail (resumed mid-decode) emits nothing — its next token is on
        record and decode continues it."""
        # the prompt's full blocks are cache content from here on —
        # registering BEFORE the first sample lets the next admission in
        # this very step hit them (materialized = exactly the prefilled
        # prefix; the token sampled below has no KV yet)
        self.block_mgr.register_committed(req.request_id, inputs,
                                          materialized=int(inputs.size),
                                          salt=req.adapter_id)
        req.state = RequestState.DECODE
        req.prefill_inputs = None
        req.prefill_pos = 0
        if req.num_generated:
            return                  # generated tail already sampled
        if tok is None:
            s = req.sampling
            tok = int(np.asarray(self._sample1_fn(bool(s.do_sample))(
                last_logits,
                # 31-bit mask: the decode path packs seeds as int32 —
                # both paths must derive the SAME key for one request's
                # stream
                jnp.asarray([s.seed & 0x7FFFFFFF], np.uint32),
                jnp.asarray([req.prompt_len], np.int32),
                jnp.asarray([s.temperature], np.float32),
                jnp.asarray([s.top_k], np.int32),
                jnp.asarray([s.top_p], np.float32),
                jnp.asarray([s.do_sample])))[0])
        req.record_token(tok)
        self.metrics.counters["generated_tokens"] += 1
        if req.finished_by(tok):
            self._retire(req, RequestState.FINISHED)

    def _prefill_window(self, req: ServeRequest, inputs: np.ndarray,
                        pos: int, take: int, pos_idx: np.ndarray):
        """ONE verify-window prefill program execution: score
        ``inputs[pos:pos+take]`` (take <= SUFFIX_CHUNK) at traced offset
        ``pos`` against the request's pool-gathered cache and scatter
        the window's KV back; returns the window's last real position's
        logits ``[1, V]``.  This is the shared chunk program — the
        prefix-cache suffix path and the chunked-prefill cursor path
        reuse the same ``_suffix_prefill_fns`` compiled set."""
        bm = self.block_mgr
        W = min(_round_up(take, self.SUFFIX_BUCKET), self.SUFFIX_CHUNK)
        toks = np.zeros((1, W), np.int32)
        toks[0, :take] = inputs[pos:pos + take]
        # pad window positions keep the trash pattern
        dests = (np.arange(W) % bm.block_size).astype(np.int32)
        for j in range(take):
            dests[j] = bm.position_index(req.request_id, pos + j)
        logits, self.pool = self._suffix_prefill_fn(W)(
            self.params, self.pool, jnp.asarray(toks),
            jnp.asarray([pos], np.int32), jnp.asarray(dests),
            jnp.asarray(pos_idx),
            *self._lora_arg([self._adapter_slot(req)]))
        return logits[0, take - 1][None]

    def _suffix_prefill(self, req: ServeRequest, inputs: np.ndarray,
                        start: int):
        """Prefill tokens ``start..n_in-1`` against the cached prefix,
        in SUFFIX_CHUNK-sized verify windows (see _suffix_prefill_fn);
        returns the last real position's logits ``[1, V]`` for first-
        token sampling."""
        n_in = int(inputs.size)
        # dense gather indices over the request's (fully allocated,
        # possibly shared) table — fixed across chunks
        pos_idx = self._pos_idx_row(req.request_id)[None]
        pos, last = start, None
        while pos < n_in:
            take = min(self.SUFFIX_CHUNK, n_in - pos)
            last = self._prefill_window(req, inputs, pos, take, pos_idx)
            self.flightrec.record("req/prefill_chunk",
                                  corr=f"req-{req.request_id}",
                                  tokens=take, offset=pos,
                                  cursor=pos + take)
            pos += take
        return last

    # --------------------------------------------- chunked prefill phase
    def _chunks_pending(self) -> bool:
        """Any PREFILLING row still owed chunk service (the spec-decode
        throttle and the deferral telemetry both key on this)."""
        return any(r is not None and r.state == RequestState.PREFILLING
                   for r in self._slots)

    def _chunk_takes(self):
        """Plan this iteration's chunked-prefill service (ISSUE 9
        semantics on the ISSUE 12 batched-window surface): split the
        per-iteration prefill allowance across PREFILLING rows —
        highest SLO class / priority first — as request_id -> total
        tokens this iteration.  Rows the allowance can't reach (not
        even one bucket or the tiny remainder) are deferred (counted)
        and keep their cursor.  The ``serve.chunk`` fault site fires
        here, BEFORE any KV write: a ``raise`` propagates out of step()
        (cursor and block table untouched), a ``deny`` defers the row
        this iteration."""
        if not self._chunked_on:
            return {}
        rows = [r for r in self._slots if r is not None
                and r.state == RequestState.PREFILLING]
        if not rows:
            return {}
        allow = self._prefill_allowance()
        rows.sort(key=self._qos_key, reverse=True)
        takes = {}
        for req in rows:
            left = allow - self._prefill_spent - sum(takes.values())
            remaining = int(req.prefill_inputs.size) - req.prefill_pos
            if left < min(self.SUFFIX_BUCKET, remaining):
                self.metrics.counters["chunks_deferred"] += 1
                continue
            if self.injector.deny("serve.chunk"):
                self.metrics.counters["chunks_deferred"] += 1
                continue
            takes[req.request_id] = min(left, remaining)
        return takes

    # ------------------------------------------------- decode iteration
    def _grow_tables(self):
        """Allocate-on-decode: each active row needs a block for the
        position it writes this step; exhaustion preempts the lowest-
        priority active request (possibly the grower itself)."""
        for req in list(self._slots):
            if req is None or req.state != RequestState.DECODE:
                continue
            write_pos = int(req.all_token_ids.size) - 1
            bm = self.block_mgr
            while write_pos // bm.block_size >= len(
                    bm.block_table(req.request_id)):
                if bm.allocate(req.request_id, 1) is not None:
                    continue
                # PREFILLING rows are preemptible too (ISSUE 9): a
                # lowest-class chunking prompt yields its pool to a
                # higher-class decode before any decode row does
                active = [r for r in self._slots if r is not None
                          and r.state in (RequestState.DECODE,
                                          RequestState.PREFILLING)]
                victim = min(active, key=self._qos_key)
                if victim is req:
                    # the grower is about to evict ITSELF: true pool
                    # exhaustion, not pressure rebalancing.  Snapshot
                    # the ledger BEFORE the eviction returns the
                    # victim's blocks — the forensic record must show
                    # who held the bytes at the moment of failure, not
                    # the post-eviction state
                    self._record_alloc_failure(
                        "kv.alloc", request_id=req.request_id,
                        phase="grow", needed_blocks=1,
                        free_blocks=bm.num_free_blocks)
                self._evict(victim)
                if victim is req:
                    break

    def _prepare_window(self, active, k: int) -> bool:
        """Extend every active row's block table to cover ``k`` upcoming
        writes — all or nothing, never preempting (window sizing falls
        back to k=1, whose growth path may preempt)."""
        bm = self.block_mgr
        plan = []
        total = 0
        for req in active:
            last_pos = int(req.all_token_ids.size) - 1 + (k - 1)
            n = last_pos // bm.block_size + 1 \
                - len(bm.block_table(req.request_id))
            if n > 0:
                plan.append((req, n))
                total += n
        if total > bm.num_reclaimable_blocks:
            return False
        for req, n in plan:
            if bm.allocate(req.request_id, n) is None:
                # denied mid-plan (injected fault): blocks already granted
                # stay on their tables — harmless extra coverage — but the
                # window must shrink to one it can fully back
                return False
        return True

    def _choose_window(self, active) -> int:
        """Fused-step count: the largest power of two that (a) respects
        max_fused_steps, (b) cannot outrun the first possible retirement
        (min remaining tokens — so a finishing row's slot frees exactly
        when it would have), and (c) has pool blocks for every write."""
        rem = min(r.remaining_new_tokens for r in active)
        k = 1
        while k * 2 <= min(rem, self.cfg.max_fused_steps):
            k *= 2
        while k > 1 and not self._prepare_window(active, k):
            k //= 2
        return k

    def _decode(self):
        """All-plain decode iteration (no drafts, no pending chunks):
        the k-step fused decode program (``max_fused_steps``) — the
        batched-window step owns every iteration that has window work."""
        active = [r for r in self._slots if r is not None
                  and r.state == RequestState.DECODE]
        if not active:
            return
        B = self.cfg.max_num_seqs
        bm = self.block_mgr
        k = self._choose_window(active)
        # packed args (see _decode_fn): ints [4+k, B], floats [2, B]
        ints = np.zeros((4 + k, B), np.int32)
        ints[4:] = (np.arange(k) % bm.block_size)[:, None]  # trash pattern
        floats = np.ones((2, B), np.float32)
        do_flags = np.zeros((B,), bool)
        pos_idx = np.zeros((B, self.s_pad), np.int32)
        groups = np.full((B,), -1, np.int32)
        for req in active:
            b = req.slot
            seq = req.all_token_ids
            pos_idx[b] = self._pos_idx_row(req.request_id)
            groups[b] = self._adapter_slot(req)
            s = req.sampling
            ints[0, b], ints[1, b] = seq[-1], seq.size - 1
            ints[2, b], ints[3, b] = s.seed & 0x7FFFFFFF, s.top_k
            for j in range(k):
                ints[4 + j, b] = bm.position_index(
                    req.request_id, seq.size - 1 + j)
            floats[0, b], floats[1, b] = s.temperature, s.top_p
            do_flags[b] = s.do_sample
        any_sampling = bool(do_flags.any())
        t0 = time.perf_counter()
        toks, self.pool = self._decode_fn(any_sampling)(
            self.params, self.pool, ints, floats, do_flags, pos_idx,
            *self._lora_arg(groups))
        toks = np.asarray(toks)                  # [k, B]
        if self._costmodel_on:
            from deepspeed_tpu.telemetry.roofline import observe_achieved
            observe_achieved(self.metrics.registry, f"serve/decode:k{k}",
                             time.perf_counter() - t0)
        self.metrics.counters["decode_steps"] += k
        for req in active:
            for j in range(k):
                tok = int(toks[j, req.slot])
                req.record_token(tok)
                self.metrics.counters["generated_tokens"] += 1
                if req.finished_by(tok):
                    # immediate retirement: blocks recycle mid-batch, the
                    # slot is admittable on the very next iteration.  An
                    # EOS inside a fused window discards the window tail
                    # (k never outruns max_new, only EOS cuts early).
                    self._retire(req, RequestState.FINISHED)
                    break

    # --------------------------------------------- speculative decoding
    #: verify passes with a draft before min_accept_rate can trip
    SPEC_MIN_PASSES = 4
    #: draft-length clamp while prefill chunks are pending (ISSUE 9):
    #: verify windows and chunk windows contend for the same iteration —
    #: a wide speculative window would stretch every chunk's wait just
    #: like an unchunked prefill stretched decode's
    SPEC_THROTTLE_K = 2

    def _spec_budget(self, req: ServeRequest) -> int:
        """Adaptive per-request draft length for this round (0 = don't
        speculate: disabled, or too close to max_new for a draft plus
        the bonus token to fit).  Clamped to SPEC_THROTTLE_K while
        PREFILLING rows await chunk service (spec auto-throttle,
        ISSUE 9)."""
        spec = self.cfg.spec
        if req.spec_disabled or req.remaining_new_tokens <= 1:
            return 0
        if req.spec_k <= 0:
            req.spec_k = spec.max_draft_tokens      # start optimistic
        k = min(req.spec_k, spec.max_draft_tokens,
                req.remaining_new_tokens - 1)
        if k > self.SPEC_THROTTLE_K and self._chunks_pending():
            self.metrics.counters["spec_throttled"] += 1
            k = self.SPEC_THROTTLE_K
        return k

    def _propose_drafts(self, active) -> Dict[int, np.ndarray]:
        from deepspeed_tpu.telemetry import get_tracer
        tracer = get_tracer()
        bm = self.block_mgr
        drafts: Dict[int, np.ndarray] = {}
        for req in active:
            k = self._spec_budget(req)
            if k <= 0:
                continue
            with tracer.span("serve/draft", cat="serving",
                             corr=f"req-{req.request_id}",
                             args={"request_id": req.request_id, "k": k}):
                d = np.asarray(self.proposer.propose(req, k),
                               np.int32).reshape(-1)[:k]
            if d.size == 0:
                continue
            # window writes reach position (seq-1)+len(d): all-or-nothing
            # block growth, never preempting — a denied/exhausted pool
            # just drops the draft and the row decodes plain in-window
            last = int(req.all_token_ids.size) - 1 + int(d.size)
            need = last // bm.block_size + 1 \
                - len(bm.block_table(req.request_id))
            if need > 0 and bm.allocate(req.request_id, need) is None:
                continue
            drafts[req.request_id] = d
        return drafts

    def _window_step(self) -> bool:
        """The unified batched-window iteration (ISSUE 12 tentpole):
        decode rows (with their speculative drafts when a proposer is
        armed) AND every PREFILLING row's chunk share ride ONE
        ``_window_fn`` execution — one pool gather, one per-layer
        weight pass (the fused megakernel when enabled), one windowed
        scatter.  When a chunk share exceeds the window cap the step
        loops chunk-only passes until the iteration's allowance is
        spent (same per-iteration boundedness as the PR 9 phase, fewer
        launches — chunk rows batch together instead of running B=1
        programs).  Returns False when there is no window work at all —
        the all-plain k-step fused decode path then runs instead.

        Fault degradation is unchanged: ``serve.spec`` (raise/deny)
        fires before any KV write and drops every draft (the step
        degrades to plain-decode-in-window); ``serve.chunk`` fires in
        the planning walk before any KV write."""
        from deepspeed_tpu.resilience.faults import FaultInjected
        bm = self.block_mgr
        active = [r for r in self._slots if r is not None
                  and r.state == RequestState.DECODE]
        takes = self._chunk_takes()
        drafts = {}
        if self.proposer is not None and active:
            drafts = self._propose_drafts(active)
        if drafts:
            try:
                denied = self.injector.deny("serve.spec")
            except FaultInjected:
                denied = True
            if denied:
                # degrade to plain decode this step; hand back the
                # window blocks the dropped drafts had reserved
                self.metrics.counters["spec_faults"] += 1
                for rid in drafts:
                    req = self._request_in_slot(rid)
                    if req is not None:
                        bm.truncate(rid, int(req.all_token_ids.size))
                drafts = {}
        if not drafts and not takes:
            return False
        # first pass: decode rows + each chunk row's first window
        self._run_window(active, drafts, takes)
        # chunk-only passes spend the rest of the allowance (decode rows
        # already emitted this iteration)
        while takes:
            takes = {rid: t for rid, t in takes.items() if t > 0
                     and self._request_in_slot(rid) is not None}
            if not takes:
                break
            self._run_window([], {}, takes)
        return True

    def _run_window(self, decode_rows, drafts, takes):
        """Execute ONE batched-window program over the given decode rows
        (+drafts) and chunk rows (``takes`` mutates: each serviced row's
        remaining iteration share decrements).  Host epilogue: the spec
        acceptance walk for decode rows, cursor advance / completion
        sampling for chunk rows."""
        bm = self.block_mgr
        B = self.cfg.max_num_seqs
        chunk_rows = []                 # (req, take-this-pass)
        need = 1 if decode_rows else 0
        for d in drafts.values():
            need = max(need, 1 + int(d.size))
        for rid, left in takes.items():
            req = self._request_in_slot(rid)
            if req is None or left <= 0:
                continue
            take = min(self.SUFFIX_CHUNK, left)
            chunk_rows.append((req, take))
            need = max(need, take)
        if need == 0:
            return
        W = self._window_bucket(need)
        ints = np.zeros((4 + 2 * W, B), np.int32)
        ints[W + 4:] = (np.arange(W) % bm.block_size)[:, None]  # trash
        floats = np.ones((2, B), np.float32)
        do_flags = np.zeros((B,), bool)
        pos_idx = np.zeros((B, self.s_pad), np.int32)
        groups = np.full((B,), -1, np.int32)
        for req in decode_rows:
            b = req.slot
            seq = req.all_token_ids
            d = drafts.get(req.request_id)
            nd = 0 if d is None else int(d.size)
            pos_idx[b] = self._pos_idx_row(req.request_id)
            groups[b] = self._adapter_slot(req)
            s = req.sampling
            ints[0, b] = seq[-1]
            if nd:
                ints[1:1 + nd, b] = d
            ints[W, b] = seq.size - 1
            ints[W + 1, b] = nd
            ints[W + 2, b], ints[W + 3, b] = s.seed & 0x7FFFFFFF, s.top_k
            # real pool destinations for the last token + draft writes;
            # pad window positions keep the trash pattern
            for j in range(nd + 1):
                ints[W + 4 + j, b] = bm.position_index(
                    req.request_id, seq.size - 1 + j)
            floats[0, b], floats[1, b] = s.temperature, s.top_p
            do_flags[b] = s.do_sample
        for req, take in chunk_rows:
            b = req.slot
            inputs = req.prefill_inputs
            pos = req.prefill_pos
            pos_idx[b] = self._pos_idx_row(req.request_id)
            groups[b] = self._adapter_slot(req)
            s = req.sampling
            ints[0:take, b] = inputs[pos:pos + take]
            ints[W, b] = pos
            # draft_len = take-1 puts the bonus column on the chunk's
            # last real position — its emitted token IS the first-token
            # sample when this chunk completes the prefill
            ints[W + 1, b] = take - 1
            ints[W + 2, b], ints[W + 3, b] = s.seed & 0x7FFFFFFF, s.top_k
            for j in range(take):
                ints[W + 4 + j, b] = bm.position_index(
                    req.request_id, pos + j)
            floats[0, b], floats[1, b] = s.temperature, s.top_p
            do_flags[b] = s.do_sample
        from deepspeed_tpu.telemetry import get_tracer
        tracer = get_tracer()
        any_sampling = bool(do_flags.any())
        # the serve/window span carries the PASS's device time — the
        # per-row serve/chunk spans below are host bookkeeping only (a
        # batched program has no per-row execution time to attribute)
        # cost annotation (ISSUE 13): once the family's CostReport is
        # registered (first execution analyzed it), the span carries the
        # program's static cost beside its measured device time
        span_args = {"W": W, "decode_rows": len(decode_rows),
                     "drafted_rows": len(drafts),
                     "chunk_rows": len(chunk_rows)}
        if self._costmodel_on:
            from deepspeed_tpu.telemetry.costmodel import get_report
            rep = get_report(f"serve/window:w{W}")
            if rep is not None:
                span_args.update(cost_flops=rep.flops,
                                 cost_hbm_bytes=rep.hbm_bytes,
                                 cost_pallas_launches=rep.pallas_launches)
        t0 = time.perf_counter()
        with tracer.span("serve/window", cat="serving", args=span_args):
            acc, out, self.pool = self._window_fn(W, any_sampling)(
                self.params, self.pool, ints, floats, do_flags, pos_idx,
                *self._lora_arg(groups))
            acc, out = np.asarray(acc), np.asarray(out)
        if self._costmodel_on:
            from deepspeed_tpu.telemetry.roofline import observe_achieved
            observe_achieved(self.metrics.registry, f"serve/window:w{W}",
                             time.perf_counter() - t0)
        self.metrics.counters["window_steps"] += 1
        if drafts:
            self.metrics.counters["spec_verify_steps"] += 1
        if decode_rows:
            self._apply_spec_result(decode_rows, drafts, acc, out)
        for req, take in chunk_rows:
            takes[req.request_id] -= take
            inputs = req.prefill_inputs
            n_in = int(inputs.size)
            with tracer.span(
                    "serve/chunk", cat="serving",
                    corr=f"req-{req.request_id}",
                    args={"request_id": req.request_id,
                          "offset": int(req.prefill_pos),
                          "tokens": int(take),
                          "remaining": int(n_in - req.prefill_pos - take)}):
                req.prefill_pos += take
                self.flightrec.record(
                    "req/prefill_chunk", corr=f"req-{req.request_id}",
                    tokens=take, offset=req.prefill_pos - take,
                    cursor=req.prefill_pos, total=n_in)
            self._prefill_spent += take
            self.metrics.counters["prefill_tokens"] += take
            self.metrics.counters["window_chunk_tokens"] += take
            # committed chunks become prefix-cache content immediately:
            # a same-prefix admission (or this row's own post-eviction
            # resume) attaches them instead of recomputing
            self.block_mgr.register_committed(
                req.request_id, inputs, materialized=req.prefill_pos,
                salt=req.adapter_id)
            if req.prefill_pos >= n_in:
                # completion: the window's bonus column already drew the
                # first token — ONE epilogue serves every prefill form
                takes.pop(req.request_id, None)
                self._finish_prefill(req, inputs, None,
                                     tok=int(out[req.slot, take - 1]))

    def _pos_idx_row(self, request_id: int) -> np.ndarray:
        """One row of dense-gather indices: the flat pool position of
        every logical position 0..s_pad-1 for this request.  Positions
        past the allocated table ride block 0 (the trash block), like
        padding rows — the length masking never reads them."""
        table = np.zeros((self.blocks_per_table,), np.int64)
        t = self.block_mgr.block_table(request_id)
        table[:len(t)] = t
        return (table[self._pos_blk] * self.block_mgr.block_size
                + self._pos_offs).astype(np.int32)

    def _request_in_slot(self, request_id: int) -> Optional[ServeRequest]:
        for r in self._slots:
            if r is not None and r.request_id == request_id:
                return r
        return None

    def _apply_spec_result(self, active, drafts, acc: np.ndarray,
                           out: np.ndarray):
        """Host-side acceptance walk per row: commit the longest accepted
        draft prefix plus the token the verify logits emit at the stop
        position (rejection resample / bonus), then truncate the block
        table back to the committed length — whole now-unused blocks
        return to the pool."""
        from deepspeed_tpu.telemetry import get_tracer
        tracer = get_tracer()
        bm = self.block_mgr
        c = self.metrics.counters
        for req in active:
            b, rid = req.slot, req.request_id
            d = drafts.get(rid)
            nd = 0 if d is None else int(d.size)
            a = 0
            while a < nd and acc[b, a]:
                a += 1
            emitted = [int(t) for t in d[:a]] if nd else []
            emitted.append(int(out[b, a]))
            with tracer.span("serve/verify", cat="serving",
                             corr=f"req-{rid}",
                             args={"request_id": rid, "drafted": nd,
                                   "accepted": a}):
                for tok in emitted:
                    req.record_token(tok)
                    c["generated_tokens"] += 1
                    if req.finished_by(tok):
                        # EOS inside the accepted prefix discards the
                        # rest of the window for this row only
                        self._retire(req, RequestState.FINISHED)
                        break
            if nd:
                c["spec_drafted_tokens"] += nd
                c["spec_accepted_tokens"] += a
                c["spec_rolled_back_tokens"] += nd - a
                self.metrics.spec_accept_len.observe(a + 1)
                self.flightrec.record("req/spec_accept", corr=f"req-{rid}",
                                      drafted=nd, accepted=a)
                self._spec_adapt(req, nd, a)
            if req.slot >= 0:       # still live: paged-KV rollback
                bm.truncate(rid, int(req.all_token_ids.size))

    def _spec_adapt(self, req: ServeRequest, drafted: int, accepted: int):
        """Per-request adaptive draft length: double on full acceptance,
        halve on full rejection; a rolling acceptance-rate EMA below
        ``serving.spec.min_accept_rate`` (after a few passes) disables
        speculation for the request — mixed workloads stop paying verify
        cost for unspeculatable streams."""
        spec = self.cfg.spec
        req.spec_passes += 1
        rate = accepted / drafted
        req.spec_accept_ema = (rate if req.spec_accept_ema < 0
                               else 0.5 * req.spec_accept_ema + 0.5 * rate)
        if accepted == drafted:
            req.spec_k = min(max(req.spec_k, 1) * 2,
                             spec.max_draft_tokens)
        elif accepted == 0:
            req.spec_k = max(1, req.spec_k // 2)
        if (spec.min_accept_rate > 0
                and req.spec_passes >= self.SPEC_MIN_PASSES
                and req.spec_accept_ema < spec.min_accept_rate):
            req.spec_disabled = True
            self.metrics.counters["spec_auto_disabled"] += 1

    # ------------------------------------------------------------- step
    def step(self) -> List[ServeRequest]:
        """One engine iteration; returns requests finished this step.

        The iteration runs inside a ``serve/step`` span (correlation id
        ``serve-step-N``) with admit/grow/decode child spans; per-request
        prefill spans carry ``req-<id>`` so one request's admission,
        decode windows, and any faults line up in the trace."""
        from deepspeed_tpu.telemetry import get_tracer
        tracer = get_tracer()
        step_id = self._step_count
        t0 = time.perf_counter()
        # fault site OUTSIDE the lock: an injected stall models a wedged
        # engine without also wedging the /metrics + submit paths
        with tracer.span("serve/step", cat="serving",
                         corr=f"serve-step-{step_id}",
                         args={"step": step_id}):
            self.injector.check("serve.step")
            with self._lock:
                self._finished_this_step = []
                self._prefill_spent = 0
                gen0 = self.metrics.counters["generated_tokens"]
                self._expire_queued()
                with tracer.span("serve/admit", cat="serving"):
                    self._admit()
                with tracer.span("serve/grow", cat="serving"):
                    self._grow_tables()
                if self._mem_on:
                    # mid-step occupancy tap: per-step pool occupancy
                    # peaks right after growth — the watermark must see
                    # a request that admits AND retires this iteration
                    self._update_memory_ledger(publish=False)
                active = sum(r is not None and
                             r.state == RequestState.DECODE
                             for r in self._slots)
                with tracer.span("serve/decode", cat="serving",
                                 args={"active": active}):
                    # unified batched-window step (ISSUE 12): decode
                    # rows, spec-verify windows, and prefill chunks ride
                    # ONE compiled family; all-plain iterations keep the
                    # k-step fused decode program
                    if not self._window_step():
                        self._decode()
                if self._prefill_spent:
                    self.metrics.prefill_batch_tokens.observe(
                        self._prefill_spent)
                # per-iteration budget split (ISSUE 9 telemetry): how
                # this step's tokens divided between prefill compute and
                # decode/sampled emissions
                self.metrics.gauges["step_prefill_tokens"] = \
                    self._prefill_spent
                self.metrics.gauges["step_decode_tokens"] = int(
                    self.metrics.counters["generated_tokens"] - gen0)
                if self._prefix_cache_on:
                    # newly filled full blocks become cache entries while
                    # their owners still decode — concurrent same-prefix
                    # admissions share them immediately
                    for r in self._slots:
                        if r is not None and r.state == RequestState.DECODE:
                            self.block_mgr.register_committed(
                                r.request_id, r.all_token_ids,
                                salt=r.adapter_id)
                self._step_count += 1
                if self._debug_invariant:
                    # allocation-accounting invariant (ISSUE 5): spec
                    # rollback shrinks tables mid-flight — catch any
                    # double-free/leak at the step that caused it
                    # (DS_SERVE_DEBUG=1; off by default — the scan is
                    # O(num_blocks) inside the scheduler lock)
                    self.block_mgr.check_invariant()
                    if self.adapter_store is not None:
                        # adapter census (ISSUE 20): every pinned row's
                        # refcount must reconcile with the store's table
                        census: Dict[str, int] = {}
                        for r in self._slots:
                            if r is not None and r.adapter_pinned:
                                census[r.adapter_id] = \
                                    census.get(r.adapter_id, 0) + 1
                        self.adapter_store.check_invariant(census)
                if active:
                    self.metrics.decode_occupancy.observe(
                        active / self.cfg.max_num_seqs)
                self._update_gauges()
                if self.monitor is not None and (
                        self._step_count % self.cfg.monitor_interval == 0):
                    self.monitor.write_events(
                        self.metrics.to_events(self._step_count))
                finished = list(self._finished_this_step)
            # black-box step record + rolling anomaly check (ISSUE 7);
            # still inside the serve/step span, so the anomaly instant
            # lands between this step's B/E pair with its corr id
            dur_s = time.perf_counter() - t0
            self.flightrec.record(
                "serve/step", corr=f"serve-step-{step_id}",
                dur_ms=round(dur_s * 1e3, 3), active=active,
                queued=len(self._queue), finished=len(finished),
                version=self.weights_version)
            self.anomaly.observe("serve.step", dur_s,
                                 corr=f"serve-step-{step_id}")
            return finished

    def _update_gauges(self):
        """Occupancy + goodput gauges (ISSUE 4).  Goodput = generated
        tokens that were not later thrown away to preemption recompute;
        tokens/s is the cumulative decode rate since scheduler start."""
        from deepspeed_tpu.telemetry import serving_goodput
        c = self.metrics.counters
        elapsed = time.monotonic() - self._serve_t0
        self.metrics.gauges.update(
            queue_depth=len(self._queue),
            active_seqs=sum(r is not None for r in self._slots),
            block_pool_utilization=round(
                self.block_mgr.utilization(), 4),
            free_blocks=self.block_mgr.num_free_blocks,
            goodput=round(serving_goodput(
                c["generated_tokens"], c["recomputed_tokens"]), 4))
        if self._prefix_cache_on:
            c["prefix_cache_evict"] = self.block_mgr.cache_evictions
            self.metrics.gauges["cached_blocks"] = \
                self.block_mgr.num_cached_blocks
            lookups = c["prefix_cache_hit"] + c["prefix_cache_miss"]
            if lookups:
                self.metrics.gauges["prefix_cache_hit_rate"] = round(
                    c["prefix_cache_hit"] / lookups, 4)
        ts = self._tier_store
        if ts is not None:
            # tiered KV (ISSUE 16): policy counters mirror in as
            # serving/* counters (the cache_evictions idiom above);
            # occupancy + in-flight + hit-rate ride as gauges
            c["kv_demotions"] = ts.demotions
            c["kv_spills"] = ts.spills
            c["kv_parked_blocks"] = ts.parks
            c["kv_swap_in_blocks"] = ts.swapins
            c["kv_swap_failures"] = ts.failures
            counts = ts.counts()
            self.metrics.gauges.update(
                kv_host_blocks=counts["host"],
                kv_nvme_blocks=counts["nvme"],
                kv_inflight_swaps=len(ts.inflight()))
            attempts = ts.swapins + ts.failures
            if attempts:
                self.metrics.gauges["kv_tier_hit_rate"] = round(
                    ts.swapins / attempts, 4)
        st = self.adapter_store
        if st is not None:
            # adapter paging (ISSUE 20): store counters mirror in as
            # serving/adapter_* counters; residency rides as gauges
            s = st.summary()
            c["adapter_swap_ins"] = s["swap_ins"]
            c["adapter_demotions"] = s["demotions"]
            c["adapter_spills"] = s["spills"]
            c["adapter_dropped"] = s["dropped"]
            c["adapter_load_failures"] = max(
                c["adapter_load_failures"], s["load_failures"])
            c["adapter_slot_waits"] = s["slot_waits"]
            c["adapter_integrity_failures"] = s["integrity_failures"]
            self.metrics.gauges.update(
                adapter_resident_hbm=len(s["resident"]),
                adapter_host=s["host_adapters"],
                adapter_nvme=s["nvme_adapters"],
                adapter_pending_swapins=len(self._adapter_pending),
                adapter_quarantined=s["quarantined"])
        if elapsed > 0 and c["generated_tokens"]:
            self.metrics.gauges["tokens_per_s"] = round(
                c["generated_tokens"] / elapsed, 3)
        if c["spec_drafted_tokens"]:
            self.metrics.gauges["spec_accept_rate"] = round(
                c["spec_accepted_tokens"] / c["spec_drafted_tokens"], 4)
        if self._mem_on:
            self._update_memory_ledger()

    def _record_alloc_failure(self, site: str, **detail):
        """OOM forensics (ISSUE 14): a failed pool allocation snapshots
        the byte ledger into the forensics ring + flight recorder
        (``mem/alloc_failure``); the /debug and post-mortem surfaces
        read the snapshot, not the live (already-changed) pool."""
        if not self._mem_on:
            return
        try:
            self._update_memory_ledger()
            self._mem_ledger.record_alloc_failure(
                site, flightrec=self.flightrec,
                step=self._step_count, **detail)
        except Exception as e:  # forensics must never fail the step
            logger.debug(f"memory forensics failed ({e})")

    def _update_memory_ledger(self, publish: bool = True):
        """Memory observatory tap (ISSUE 14): the KV pool's bytes split
        by who holds them — live request tables (``kv_pool``), the
        prefix cache's retained refcount-0 set (``prefix_cache``), the
        free list (``kv_free``), and the reserved trash block
        (``kv_reserved``) — so the four owners sum EXACTLY to the pool
        pytree's leaf bytes (the parity contract the acceptance test
        enforces).  With ``publish`` it also refreshes the ``mem/*``
        gauges and feeds the HBM used fraction into the rolling anomaly
        detector (a leak alerts BEFORE the OOM) where the backend
        reports device stats; the mid-step occupancy tap (after table
        growth — where per-step occupancy PEAKS, so the watermarks see
        requests that admit and retire within one iteration) skips
        that half."""
        led = self._mem_ledger
        bm = self.block_mgr
        bpb = self._bytes_per_block
        led.set_bytes("device", "kv_pool",
                      bm.num_allocated_blocks * bpb,
                      blocks=bm.num_allocated_blocks,
                      block_size=bm.block_size)
        led.set_bytes("device", "prefix_cache",
                      bm.num_cached_blocks * bpb,
                      blocks=bm.num_cached_blocks)
        led.set_bytes("device", "kv_free", bm.num_free_blocks * bpb,
                      blocks=bm.num_free_blocks)
        led.set_bytes("device", "kv_reserved", bpb, blocks=1)
        if not publish:
            return
        led.publish_and_feed(self.metrics.registry, self.anomaly,
                             corr=f"serve-step-{self._step_count}")

    def run_until_idle(self, max_steps: int = 100_000):
        """Drive step() until queue and slots drain (bench/test helper)."""
        steps = 0
        while self.has_work():
            self.step()
            steps += 1
            if steps >= max_steps:
                raise RuntimeError(
                    f"scheduler did not drain in {max_steps} steps")
        return steps
