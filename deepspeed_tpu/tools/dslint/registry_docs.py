"""Curated documentation tables for the registries that have no
in-code declaration site: DS_* environment variables and metric names.

DSL004 enforces both directions: a ``DS_*`` read (or a metric emission)
with no entry here fails the lint, and an entry here that nothing in
the tree reads/emits fails too — so this file can neither lag nor
bloat.  ``docs/reference/registries.md`` is generated from these plus
the scanned use sites (``scripts/dslint.py --write-registries``).

Keep descriptions to one line; they land verbatim in the generated
reference tables.
"""

#: DS_* environment variable -> one-line description
ENV_VARS = {
    "DS_ACCELERATOR": "force the accelerator backend (tpu/cpu) instead "
                      "of auto-detection",
    "DS_ADAPTERS": "0/1 disables/forces multi-tenant LoRA adapter "
                   "serving (wins over serving.adapters.enabled; "
                   "ISSUE 20)",
    "DS_BENCH_DIR": "bench-ledger directory override (default BENCH/; "
                    "scripts/bench_util.py)",
    "DS_BENCH_LEDGER": "1 appends BenchRecords from the bench scripts "
                       "to the BENCH/ ledger history",
    "DS_FAULTS": "fault-injection spec string (site:action[=param]@when;"
                 " appended to resilience.faults)",
    "DS_FLASH_KERNEL": "attention dispatch override: pallas flash kernel"
                       " vs xla reference",
    "DS_FLASH_VMEM_MB": "VMEM budget the flash-attention block-size "
                        "autotuner fits under",
    "DS_GGEMM_BLOCKS": "grouped-GEMM (bm,bk,bn) block-shape override "
                       "(ggemm_sweep winners)",
    "DS_FUSED_DECODE": "0/1 disables/forces the fused per-layer decode "
                       "megakernel path (wins over serving.fused_decode)",
    "DS_FUSED_DECODE_BLOCKS": "fused megakernel cache-stream block_s "
                              "override (fused_sweep winners)",
    "DS_FUSED_DECODE_INTERPRET": "run the fused decode megakernel in "
                                 "interpret mode (CPU tier-1)",
    "DS_FUSED_DECODE_VMEM_MB": "resident-layer VMEM budget the fused "
                               "megakernel dispatch fits under",
    "DS_GGEMM_INTERPRET": "run the grouped-GEMM Pallas kernels in "
                          "interpret mode (CPU tier-1)",
    "DS_HBM_GBPS": "per-device HBM bandwidth (GB/s) for roofline floors "
                   "(wins over the device-kind table; how CPU tier-1 "
                   "exercises floor math)",
    "DS_ICI_GBPS": "per-device interconnect (ICI) bandwidth (GB/s) for "
                   "comm roofline floors and comm/achieved_vs_floor "
                   "(wins over the device-kind table; None on CPU — no "
                   "fictitious floors; ISSUE 19)",
    "DS_DCN_GBPS": "declared data-center-network bandwidth (GB/s) for "
                   "cross-host comm accounting (declaration-only: no "
                   "by-kind table exists for the DCN fabric; ISSUE 19)",
    "DS_COMMSTAT": "0/1 disables/forces the comm observatory CommStat "
                   "(per-op stats, step collective window, /debug/comm; "
                   "wins over telemetry.comm.enabled; ISSUE 19)",
    "DS_KV_TIERING": "0/1 disables/forces tiered KV spill "
                     "(host-RAM/NVMe cold tiers; wins over "
                     "serving.kv_tiering.enabled)",
    "DS_MEM_LEDGER": "0/1 disables/forces the tiered memory ledger "
                     "taps (wins over telemetry.memory)",
    "DS_MOE_DISPATCH": "MoE expert-dispatch override: auto/einsum/"
                       "grouped (wins over config)",
    "DS_NUMERICS": "0/1 disables/forces the numerics observatory "
                   "(in-graph grad stats + NaN provenance; wins over "
                   "telemetry.numerics.enabled)",
    "DS_FINGERPRINT_INTERVAL": "steps between determinism "
                               "fingerprints (wins over telemetry."
                               "numerics.fingerprint_interval; 0 "
                               "disables the periodic stream)",
    "DS_NVME_GBPS": "declared swap-device bandwidth (GB/s) for the "
                    "swap/achieved_vs_floor gauges (no by-kind table: "
                    "the NVMe part is unknowable from JAX — no "
                    "fictitious floors)",
    "DS_PARAM_RESIDENT_LAYERS": "NVMe param streaming working-set depth "
                                "override (wins over offload_param."
                                "resident_layers; ISSUE 17)",
    "DS_PEAK_FLOPS": "per-device peak FLOPs for MFU math (wins over "
                     "telemetry.peak_flops)",
    "DS_PERF_COSTMODEL": "0/1 disables/forces the serving scheduler's "
                         "compiled-program cost analysis",
    "DS_QGEMM": "0 disables the fused-dequant int8 qgemm kernel "
                "(per-layer dequant fallback)",
    "DS_QGEMM_BLOCKS": "qgemm (bm,bk,bn) block-shape override "
                       "(qgemm_sweep winners)",
    "DS_QGEMM_INTERPRET": "run the qgemm Pallas kernel in interpret "
                          "mode (CPU tier-1)",
    "DS_QUANT_SCAN_THRESHOLD_MB": "int8 decode loop-form threshold "
                                  "(wins over serving."
                                  "quant_scan_threshold_mb)",
    "DS_RESUME": "checkpoint tag to resume from ('latest' after a "
                 "preemption exit-86 restart)",
    "DS_SERVE_DEBUG": "1 arms the per-step block-pool invariant check "
                      "(O(num_blocks) under the lock)",
    "DS_SERVE_STALL_TIMEOUT_S": "scheduler-watchdog stall verdict "
                                "override (wins over serving."
                                "stall_timeout_s)",
    "DS_SPEC_VERIFY": "'scan' forces the scan_verify_fn fallback for "
                      "speculative verification",
    "DS_TRACE": "Chrome-trace output path; arms span tracing (wins "
                "over telemetry.trace)",
}

#: module -> its module-level tuple of metric names that reach a registry
#: by a variable: the sums a train step returns beside its loss, which the
#: engine gauges under the names its model gave them (``step_load()``)
METRIC_NAME_TUPLES = {
    "deepspeed_tpu/moe/layer.py": "STEP_LOAD",
    "deepspeed_tpu/models/ouro.py": "STEP_LOAD",
    "deepspeed_tpu/ops/pallas/ds_flash_attention.py": "STEP_LOAD"}

#: metric name (as exposed on /metrics, after the ServingMetrics
#: ``serving/`` prefix normalization) -> one-line description
METRICS = {
    # --- training engine
    "train/step_counts": "what the model left out of a step's loss, by "
                         "count (label count= the model's name for it: "
                         "moe/rows_over_bound = routed rows past "
                         "held_rows_bound); each step in which one is "
                         "not zero is warned of",
    "train/steps": "train_batch iterations completed",
    "train/step_latency_s": "histogram of what a train_batch call took "
                            "to return (a dispatch time on an async "
                            "device)",
    "train/tokens_per_s": "training token throughput over the last "
                          "synced window (steps_per_print boundary, or "
                          "every step under wall_clock_breakdown)",
    "train/model_flops_per_s": "achieved model FLOP/s over the same "
                               "synced window",
    "train/mfu": "model FLOPs utilization vs device peak over the same "
                 "synced window",
    "train/profiled_flops_per_s": "flops-profiler measured FLOP/s",
    "train/profiled_mfu": "flops-profiler measured MFU",
    # --- compiles, from jax's own events (telemetry/tracing.py)
    "compile/recompiles": "backend compiles (cache loads included) of a "
                          "step program that had already run: a new "
                          "shape, placement or static argument; the first "
                          "of each program is logged with its step",
    "compile/cache_hits": "persistent compile cache hits",
    "compile/cache_misses": "compiles written to the persistent compile "
                            "cache as new entries",
    # --- checkpointing
    "ckpt/saves": "checkpoint publishes (sync + async)",
    "ckpt/restores": "checkpoint restores",
    "ckpt/save_duration_s": "stage+publish duration histogram",
    "ckpt/restore_duration_s": "restore duration histogram",
    "ckpt/fallbacks": "restores that fell back to an older valid tag",
    "retry/retries": "checkpoint-I/O retry attempts, labeled by op",
    # --- anomaly / postmortem
    "anomaly/last_score": "most recent MAD score per step kind",
    "postmortem/bundles": "post-mortem bundles written",
    # --- perf observatory (cost model + roofline, ISSUE 13)
    "perf/flops": "cost-model dot FLOPs per program execution, labeled "
                  "by program",
    "perf/hbm_bytes": "cost-model weight-stream HBM bytes per "
                      "execution, labeled by program",
    "perf/pallas_launches": "kernel-launch sites in the compiled "
                            "program, labeled by program",
    "perf/collective_bytes": "collective payload bytes per execution, "
                             "labeled by program",
    "perf/floor_ms": "roofline floor per execution (ms; only where a "
                     "device rate resolves), labeled by program",
    "perf/achieved_ms": "latest measured program execution wall clock "
                        "(ms), labeled by program",
    "perf/achieved_vs_floor": "achieved/floor ratio (the live "
                              "N-x-over-floor gap), labeled by program",
    # --- comm observatory (per-collective telemetry + interconnect
    # roofline + overlap attribution, ISSUE 19)
    "comm/calls": "CommsLogger per-op call count as a live labeled "
                  "counter, labeled by op",
    "comm/total_bytes": "CommsLogger per-op message-byte total, "
                        "labeled by op",
    "comm/total_time_ms": "CommsLogger per-op eager-timed total (ms), "
                          "labeled by op",
    "comm/wire_bytes": "ring-algorithm interconnect wire bytes per "
                       "execution (2(N-1)/N all-reduce etc.), labeled "
                       "by program",
    "comm/floor_ms": "interconnect comm floor per execution (ms; only "
                     "where an ICI rate resolves — never fictitious "
                     "on CPU), labeled by program",
    "comm/achieved_vs_floor": "achieved/comm-floor ratio (the "
                              "collapsing-link gauge; publishes ONLY "
                              "under a declared/known ICI rate), "
                              "labeled by program",
    "comm/op_latency_s": "host-timed per-collective latency histogram, "
                         "labeled by op",
    "comm/op_gbps": "host-timed achieved collective bandwidth "
                    "histogram (GB/s), labeled by op",
    "comm/achieved_gbps": "latest achieved collective bandwidth gauge, "
                          "labeled by op",
    "comm/overlap_fraction": "share of the step's observed comm time "
                             "that ran off the critical thread (1.0 = "
                             "fully hidden behind compute)",
    # --- memory observatory (tiered ledger + OOM forensics, ISSUE 14)
    "mem/owner_bytes": "live bytes per owner, labeled by tier+owner "
                       "(params/optimizer/kv_pool/prefix_cache/...; a "
                       "training engine's device tier is one chip's: "
                       "its fullest local device)",
    "mem/tier_bytes": "live bytes per tier (device/host/nvme)",
    "mem/tier_watermark_bytes": "high-watermark of a tier's total, "
                                "labeled by tier",
    "mem/hbm_used_bytes": "in use + reserved (bytes_in_use + "
                          "bytes_reserved), fullest local device, via "
                          "the accelerator abstraction (absent on CPU)",
    "mem/hbm_limit_bytes": "that device's bytes_limit (absent on CPU)",
    "mem/hbm_used_fraction": "in use + reserved over bytes_limit, "
                             "fullest local device (the anomaly/mem_hbm "
                             "leak feed; absent on CPU)",
    "mem/alloc_failures": "allocation failures snapshotted into the "
                          "OOM forensics ring",
    # --- offload I/O (swap bandwidth telemetry, ISSUE 14)
    "swap/in_bytes": "bytes read back from swap (NVMe -> host)",
    "swap/out_bytes": "bytes written to swap (host -> NVMe)",
    "swap/ops": "completed swap I/O requests, labeled by op",
    "swap/op_latency_s": "per-request submit-to-completion latency "
                         "histogram, labeled op+window",
    "swap/op_gbps": "per-request achieved bandwidth histogram (GB/s), "
                    "labeled op+window",
    "swap/achieved_gbps": "latest achieved swap bandwidth gauge, "
                          "labeled by op",
    "swap/achieved_vs_floor": "achieved/declared-DS_NVME_GBPS ratio "
                              "(only when the floor is declared), "
                              "labeled by op",
    # --- NVMe param streaming (ISSUE 17)
    "offload/param_prefetch_overlap": "fraction of shard reads satisfied "
                                      "by an in-flight prefetch "
                                      "(measured, never asserted)",
    "offload/param_resident_layers": "layers currently materialized in "
                                     "the host working set",
    "offload/param_swap_failures": "param.swap faults / shard I/O errors",
    "offload/param_degraded_reads": "shards rebuilt synchronously from "
                                    "the fp32 masters (torn/failed read)",
    "offload/param_fetch_block_s": "wall-clock the weight pass spent "
                                   "blocked in shard fetch",
    # --- offload storage integrity (ISSUE 18)
    "offload/integrity_fail": "payload checksum mismatches detected on "
                              "fetch (key quarantined), labeled by tier",
    "offload/quarantined": "keys currently in the engine's quarantine "
                           "ring (a fresh put of the key clears it)",
    "offload/io_failures": "terminal (post-retry) aio failures, labeled "
                           "by direction; these feed the tier breaker",
    "offload/write_reverts": "failed fire-and-forget NVMe writes whose "
                             "entries were rebuilt on the host tier "
                             "from the retained source",
    "offload/breaker_state": "tier circuit-breaker state (0=closed, "
                             "1=half_open, 2=open), labeled by tier",
    # --- MoE routing health
    "moe/dispatch_tokens": "tokens routed into expert dispatch",
    "moe/dropped_tokens": "tokens dropped at capacity (einsum mode; "
                          "grouped pins 0)",
    "moe_drop_fraction": "dropped/dispatched fraction gauge",
    "moe/router_entropy": "mean per-token routing entropy in nats "
                          "(ln E = uniform, ~0 = collapsed router)",
    "moe/expert_load_max_fraction": "hottest expert's share of routed "
                                    "choices (1/E = balanced)",
    "moe/expert_load_fraction": "per-expert share of routed choices, "
                                "labeled by expert",
    "moe/dead_experts": "experts that received zero routed choices, "
                        "counted per routing step",
    "moe/aux_loss": "weighted load-balancing aux loss gauge",
    "moe/z_loss": "router z-loss gauge",
    # the router's load: in a train step from the step's own outputs (sums
    # over its expert layer-calls, micro-batches and chips, gauged by the
    # engine when the step has ended: engine.step_load()); the five the
    # registry tap sets (held_*, exchange_rows_*, *_per_routed_row) on a
    # forward with a tap by callback, per expert layer run
    "moe/held_live_rows": "rows of a held-subset plan's live prefix "
                          "(used_blocks tiles: what dispatch, the grouped "
                          "kernels and combine walk), per expert layer run "
                          "(in a train step: from the step's own outputs, "
                          "summed over the step; on a forward with a tap: "
                          "by callback)",
    "moe/held_plan_rows": "static length of that plan (held_rows_bound + "
                          "one tile per held expert); in a train step "
                          "summed as moe/held_live_rows is",
    "moe/exchange_rows_sent": "(token, chip) rows one chip sent to the "
                              "chips of the expert axis (itself among "
                              "them) in an exchanged expert layer run (in "
                              "a train step: from the step's own outputs, "
                              "summed over the step and the chips; on a "
                              "forward with a tap: by callback)",
    "moe/exchange_rows_received": "(token, sender) rows that chip received "
                                  "from them (what landed there); in a "
                                  "train step summed as "
                                  "moe/exchange_rows_sent is",
    "moe/exchange_wire_rows_per_routed_row": "of the rows it sent, those "
                                             "that crossed to another chip, "
                                             "a routed (token, expert) row: "
                                             "a token crosses to a chip once "
                                             "(the tap's, by callback; a "
                                             "train step: moe/exchange_"
                                             "wire_rows / moe/routed_rows)",
    "moe/routed_rows": "(token, choice) rows a train step's routers sent "
                       "to the experts held here, before any bound, summed "
                       "over its expert layer-calls, micro-batches and "
                       "chips (from the step's own outputs)",
    "moe/even_rows": "what moe/routed_rows is under even routing: a "
                     "constant of the shapes, summed the same way",
    "moe/even_expert_rows": "one expert's even share of a layer-call's "
                            "routed rows (rows / num_experts), summed as "
                            "moe/routed_rows is",
    "moe/fullest_expert_rows": "rows of the fullest held expert of a "
                               "layer-call (max of the plan's counts), "
                               "summed as moe/routed_rows is",
    "moe/exchange_wire_rows": "of moe/exchange_rows_sent, the rows that "
                              "left their chip, summed the same way",
    "moe/fullest_chip_rows": "(token, expert) rows the fullest chip's plan "
                             "received in an exchanged layer-call, summed "
                             "over layer-calls and micro-batches, once an "
                             "expert axis (every chip computes the same)",
    "moe/mean_chip_rows": "the mean over the chips where "
                          "moe/fullest_chip_rows is the maximum",
    # --- a looped stack's exits (models/ouro.py): from a train step's own
    # outputs, rounded, summed over its micro-batches (engine.step_load())
    "ouro/exit_mass_1": "the scored positions' probability of leaving "
                        "after pass 1 under the exit gates, summed over a "
                        "train step's positions and micro-batches and "
                        "rounded (from the step's own outputs); the four "
                        "masses add up to ouro/scored_tokens",
    "ouro/exit_mass_2": "the same after pass 2: lam_2 (1 - lam_1)",
    "ouro/exit_mass_3": "the same after pass 3",
    "ouro/exit_mass_4": "what is left for the last pass: the product of "
                        "the earlier gates' (1 - lam)",
    "ouro/scored_tokens": "the positions a train step's loss scored (the "
                          "next token in the same document), summed as "
                          "ouro/exit_mass_1 is",
    "ouro/exit_pass_tokens": "sum over passes t of t x the mass leaving "
                             "after t; over ouro/scored_tokens it is the "
                             "pass a token is expected to leave after",
    # --- what a packed step's documents let the flash kernels skip
    # (ops/pallas/ds_flash_attention.py step_tile_sums): from the step's
    # own outputs, summed over its micro-batches (engine.step_load())
    "flash/visited_tiles": "score tiles one head's forward pass visited "
                           "over a train step's rows, a packed call shape "
                           "of the step each: every q-block from the first "
                           "key block of its own documents (and of its "
                           "window) to its diagonal",
    "flash/positional_tiles": "what position alone would have visited "
                              "there (tracing.flash_calls() tiles a row): "
                              "flash/visited_tiles over it is the share "
                              "of the causal tiles the documents left",
    # --- numerics observatory (training health, ISSUE 15)
    "num/grad_norm": "last resolved global gradient norm (-1 = "
                     "non-finite)",
    "num/loss": "last resolved training loss gauge",
    "num/loss_scale": "last resolved dynamic loss scale (the "
                      "loss-scale timeline's live point)",
    "num/update_ratio": "last resolved ||update||/||param|| step-size "
                        "health gauge",
    "num/group_grad_norm": "per-leaf-group gradient norm, labeled by "
                           "group (-1 = non-finite)",
    "num/nonfinite_steps": "steps with non-finite gradients, labeled "
                           "handled (loss-scaler overflow) vs "
                           "unexpected",
    "num/fingerprints": "determinism fingerprints recorded (interval "
                        "stream + checkpoint stamps)",
    "num/fingerprint_mismatch": "restores whose recomputed fingerprint "
                                "disagreed with the manifest stamp",
    # --- serving: request lifecycle counters
    "serving/received": "requests accepted into the queue",
    "serving/completed": "requests finished",
    "serving/resumed": "preempted requests re-admitted",
    "serving/preemptions": "evictions under pool pressure",
    "serving/rejected_too_long": "rejections: prompt+max_new exceeds "
                                 "capacity",
    "serving/rejected_queue_full": "rejections: queue at max_queued",
    "serving/rejected_timeout": "rejections: queued past timeout",
    "serving/rejected_shed": "rejections: SLO overload shedding (429 + "
                             "Retry-After)",
    "serving/rejected_not_accepting": "rejections: draining/degraded "
                                      "server",
    # --- serving: throughput / tokens
    "serving/generated_tokens": "decode tokens emitted",
    "serving/prefill_tokens": "prompt tokens prefilled",
    "serving/recomputed_tokens": "tokens recomputed after preemption "
                                 "(goodput loss)",
    "serving/decode_steps": "jitted decode dispatches",
    "serving/tokens_per_s": "cumulative decode rate gauge",
    "serving/goodput": "non-recomputed fraction of generated tokens",
    "serving/step_prefill_tokens": "this iteration's prefill token "
                                   "spend gauge",
    "serving/step_decode_tokens": "this iteration's decode emissions "
                                  "gauge",
    "serving/chunks_deferred": "chunked-prefill windows deferred by the "
                               "per-iteration allowance",
    "serving/window_steps": "unified batched-window program executions "
                            "(decode+spec+chunks in one launch)",
    "serving/window_chunk_tokens": "prefill tokens serviced through the "
                                   "batched-window surface",
    # --- serving: occupancy / health
    "serving/queue_depth": "queued requests gauge",
    "serving/active_seqs": "occupied decode slots gauge",
    "serving/decode_occupancy": "active/max_num_seqs histogram",
    "serving/prefill_batch_tokens": "per-iteration prefill batch-size "
                                    "histogram",
    "serving/block_pool_utilization": "allocated fraction of the KV "
                                      "pool",
    "serving/free_blocks": "free-list size gauge",
    "serving/loop_failures": "consecutive serving-loop step failures",
    "serving/stalls": "watchdog stall verdicts",
    "serving/health_state": "numeric health state (0=ready .. "
                            "4=stopped)",
    # --- serving: latency histograms (+ quantile gauges)
    "serving/ttft_s": "time-to-first-token histogram",
    "serving/token_latency_s": "per-token decode latency histogram",
    "serving/latency_s": "end-to-end request latency histogram",
    "serving/queue_wait_s": "admission queue wait histogram",
    # --- serving: prefix cache
    "serving/prefix_cache_hit": "admissions that attached cached "
                                "blocks",
    "serving/prefix_cache_miss": "admissions with no usable cached "
                                 "prefix",
    "serving/prefix_cache_evict": "cached blocks evicted from the LRU",
    "serving/prefix_cache_cow_forks": "copy-on-write forks of a cached "
                                      "block",
    "serving/prefix_cache_hit_rate": "hit/(hit+miss) gauge",
    "serving/cached_blocks": "refcount-0 blocks retained in the cache",
    # --- serving: tiered KV (host/NVMe spill, ISSUE 16)
    "serving/kv_demotions": "HBM cache blocks demoted to the host tier "
                            "instead of evicted",
    "serving/kv_spills": "host-tier blocks spilled onward to NVMe under "
                         "host_blocks pressure",
    "serving/kv_parked_blocks": "committed KV blocks parked on NVMe at "
                                "preemption",
    "serving/kv_swap_in_blocks": "cold-tier blocks materialized back "
                                 "into HBM",
    "serving/kv_swap_failures": "swap-outs/swap-ins abandoned (kv.swap "
                                "fault or I/O error; degraded to "
                                "evict/re-prefill)",
    "serving/kv_tier_hit_host": "swap-ins satisfied from the host tier",
    "serving/kv_tier_hit_nvme": "swap-ins satisfied from the NVMe tier",
    "serving/kv_host_blocks": "blocks resident in the host tier gauge",
    "serving/kv_nvme_blocks": "blocks resident in the NVMe tier gauge",
    "serving/kv_inflight_swaps": "async swap-in reads in flight gauge",
    "serving/kv_tier_hit_rate": "swap_ins/(swap_ins+failures) gauge",
    # --- serving: speculative decoding
    "serving/spec_drafted_tokens": "draft tokens proposed",
    "serving/spec_accepted_tokens": "draft tokens accepted by verify",
    "serving/spec_rolled_back_tokens": "draft tokens rolled back",
    "serving/spec_verify_steps": "speculative verify dispatches",
    "serving/spec_faults": "serve.spec faults degraded to plain decode",
    "serving/spec_auto_disabled": "requests whose accept EMA disabled "
                                  "drafting",
    "serving/spec_throttled": "draft-k clamps while prefill chunks "
                              "pending",
    "serving/spec_accept_rate": "accepted/drafted gauge",
    "serve/spec_accept_len": "tokens emitted per verify pass histogram "
                             "(+ p50/p90/p99/mean gauges)",
    # --- fleet routing (serving/fleet, ISSUE 11)
    "fleet/dispatches": "requests placed on a replica, labeled by "
                        "replica",
    "fleet/misroutes": "fleet.dispatch deny faults routed policy-blind",
    "fleet/unroutable": "submissions with no READY replica",
    "fleet/resubmits": "requests moved to another replica (drain / "
                       "replica loss)",
    "fleet/drains": "replica drains initiated through the router",
    "fleet/completed": "fleet requests finished",
    "fleet/failed": "fleet requests terminally failed at the router",
    "fleet/prefix_routed": "dispatches won by a prefix-digest match",
    "fleet/affinity_hits": "dispatches that honored session affinity",
    "fleet/digest_refreshes": "replica cache-digest refreshes",
    "fleet/healthy_replicas": "READY replicas gauge",
    "fleet/inflight": "router-tracked in-flight requests gauge",
    "fleet/outstanding_tokens": "per-replica outstanding token budget "
                                "gauge, labeled by replica",
    "fleet/prefix_cache_hit_rate": "fleet-aggregate prefix-cache hit "
                                   "rate gauge",
    # --- serving: multi-tenant adapters (paged LoRA store, ISSUE 20)
    "serving/adapter_unknown": "submissions naming an unregistered "
                               "adapter_id (typed 4xx, never a 500)",
    "serving/adapter_rejects": "requests terminally failed on adapter "
                               "swap-in (no base fallback configured)",
    "serving/adapter_fallbacks": "requests degraded to the base model "
                                 "after an adapter swap-in failure",
    "serving/adapter_load_failures": "adapter.load faults / integrity "
                                     "failures during swap-in",
    "serving/adapter_swap_ins": "adapters materialized into an HBM slot "
                                "from the host/NVMe tiers",
    "serving/adapter_demotions": "refcount-0 adapters demoted from HBM "
                                 "to the host tier (LRU victims)",
    "serving/adapter_spills": "host-tier adapters spilled onward to "
                              "NVMe under max_host_adapters pressure",
    "serving/adapter_dropped": "cold-tier adapter payloads dropped "
                               "(re-ingest from the registry on next "
                               "use)",
    "serving/adapter_slot_waits": "swap-ins deferred because every HBM "
                                  "slot was pinned by live requests",
    "serving/adapter_integrity_failures": "adapter payload checksum "
                                          "mismatches (key quarantined "
                                          "in the offload engine)",
    "serving/adapter_resident_hbm": "adapters HBM-resident gauge",
    "serving/adapter_host": "adapters parked on the host tier gauge",
    "serving/adapter_nvme": "adapters parked on NVMe gauge",
    "serving/adapter_pending_swapins": "requests waiting on an adapter "
                                       "swap-in gauge",
    "serving/adapter_quarantined": "adapter keys in the engine's "
                                   "quarantine ring gauge",
    "serving/tenant_completed": "finished requests per tenant, labeled "
                                "by adapter (\"base\" = no adapter)",
    "serving/weights_swaps": "base-weight trees installed via "
                             "install_params (live hot-swap)",
    "fleet/weight_swaps": "fleet-wide base-weight rollouts completed "
                          "through Router.swap_weights",
    # --- serving: SLO accounting
    "serving/slo_requests": "finished requests with SLO accounting, "
                            "labeled by class",
    "serving/slo_violations": "requests over their class targets",
    "serving/slo_ttft_violations": "TTFT target misses, labeled by "
                                   "class",
    "serving/slo_tpot_violations": "TPOT target misses, labeled by "
                                   "class",
    "serving/slo_ttft_burn_rate": "rolling TTFT violation fraction "
                                  "gauge",
    "serving/slo_tpot_burn_rate": "rolling TPOT violation fraction "
                                  "gauge",
}
