"""Unified telemetry (ISSUE 4): metrics registry + Prometheus
exposition, Chrome-trace span tracer with correlation ids, and
MFU/goodput accounting — the cross-cutting observability layer train
and serve both report through (docs/tutorials/monitoring-profiling.md).
ISSUE 7 adds the black-box layer: a structured flight recorder,
rolling anomaly detection + SLO burn accounting, and the live
``/debug/*`` introspection surface.  ISSUE 13 adds the perf
observatory: a jaxpr-walking cost model for every compiled hot-path
program family and a roofline layer pricing each one against the
device's FLOP/bandwidth rates (``perf/*`` gauges, ``/debug/perf``).
ISSUE 14 adds the memory observatory: a tiered per-owner byte ledger
with OOM forensics (``mem/*`` gauges, ``/debug/memory``,
``memory.json`` in post-mortem bundles) and offload I/O bandwidth
telemetry over the aio/swap paths (``swap/*``, ``DS_NVME_GBPS``).
ISSUE 15 adds the numerics observatory: lazily banked in-graph
training-health stats with NaN provenance, MoE router health, and
determinism fingerprints (``num/*`` gauges, ``/debug/numerics``,
``numerics.json`` in post-mortem bundles).  ISSUE 19 adds the
communication observatory: per-collective cost attribution with an
interconnect roofline (``DS_ICI_GBPS``), the process-wide CommStat
runtime stats with a comm/compute overlap meter, and ``/debug/comm`` +
``comm.json`` surfaces.
"""
from deepspeed_tpu.telemetry.registry import (      # noqa: F401
    COUNT_BUCKETS, DEFAULT_LATENCY_BUCKETS_S, Histogram, MetricsRegistry,
    OCCUPANCY_BUCKETS, get_registry)
from deepspeed_tpu.telemetry.tracing import (       # noqa: F401
    NULL_TRACER, SpanTracer, TRACE_ENV, configure_tracer, get_tracer,
    reset_tracer)
from deepspeed_tpu.telemetry.mfu import (           # noqa: F401
    PEAK_FLOPS_ENV, mfu, peak_flops_per_device, serving_goodput,
    tokens_per_second, total_peak_flops)
from deepspeed_tpu.telemetry.flight_recorder import (  # noqa: F401
    FlightRecorder, NULL_FLIGHT_RECORDER, configure_flight_recorder,
    get_flight_recorder, reset_flight_recorder)
from deepspeed_tpu.telemetry.anomaly import (       # noqa: F401
    AnomalyMonitor, RollingMadDetector, SLOTracker)
from deepspeed_tpu.telemetry.costmodel import (     # noqa: F401
    COSTMODEL_ENV, CostReport, analyze_fn, analyze_jaxpr,
    costmodel_enabled, count_pallas_launches, get_reports,
    param_stream_bytes, register_report)
from deepspeed_tpu.telemetry.roofline import (      # noqa: F401
    HBM_GBPS_BY_KIND, HBM_GBPS_ENV, ICI_GBPS_BY_KIND, ICI_GBPS_ENV,
    classify, comm_floor_seconds, dcn_bytes_per_s, floor_seconds,
    hbm_bytes_per_s, ici_bytes_per_s, observe_achieved, perf_table,
    publish_report)
from deepspeed_tpu.telemetry.memory import (        # noqa: F401
    MEM_ENV, MemoryLedger, attribute_params, device_bytes,
    device_memory_stats, get_memory_ledger, hbm_used_fraction,
    memory_enabled, peek_step_memory, reset_memory_ledger, step_memory,
    tree_bytes)
from deepspeed_tpu.telemetry.iostat import (        # noqa: F401
    IoStat, NVME_GBPS_ENV, get_iostat, nvme_bytes_per_s, reset_iostat)
from deepspeed_tpu.telemetry.numerics import (      # noqa: F401
    FINGERPRINT_ENV, NUMERICS_ENV, NumericsState, configure_numerics,
    group_stats, leaf_groups, numerics_enabled, peek_numerics,
    reset_numerics, resolve_fingerprint_interval, state_fingerprint)
from deepspeed_tpu.telemetry.commstat import (      # noqa: F401
    COMMSTAT_ENV, CommStat, commstat_enabled, get_commstat,
    peek_commstat, reset_commstat, timed_collective)
from deepspeed_tpu.telemetry.debug import (         # noqa: F401
    comm_payload, flightrec_payload, format_thread_stacks,
    memory_payload, numerics_payload, parse_debug_query, perf_payload)
from deepspeed_tpu.telemetry.http_endpoint import MetricsServer  # noqa: F401
