"""Roofline attribution over the cost model (ISSUE 13 tentpole).

``mfu.py`` answers "what fraction of peak FLOPs did we achieve";
this module answers the decode-regime question PERF.md has been
answering by hand: **what is the hardware floor for this program, and
how far above it are we running**.  A per-device HBM-bandwidth table
(same shape as ``PEAK_FLOPS_BY_KIND``) prices a program's
:class:`~deepspeed_tpu.telemetry.costmodel.CostReport` into

- ``floor_ms`` — ``max(flops/peak, hbm_bytes/bandwidth)`` per
  execution, the roofline lower bound;
- a compute-bound vs bandwidth-bound classification (which term won);
- ``achieved_vs_floor`` — measured wall clock over the floor, the
  "4-5x-over-floor" gap as a live gauge instead of a PERF.md table.

The comm observatory (ISSUE 19) adds the third roofline axis: an
interconnect (ICI) bandwidth table prices each program's per-axis
collective WIRE bytes into a comm floor beside the FLOP and HBM
floors, steps classify ``comm_bound`` when that term wins, and
``comm/achieved_vs_floor`` tracks the live gap.  ``DS_ICI_GBPS`` /
``DS_DCN_GBPS`` override the declared interconnect rates.

On CPU neither table resolves and every floor-dependent output is None
— **no fictitious floors**.  ``DS_HBM_GBPS`` overrides per device
(it is also how CPU tier-1 tests exercise the floor math).  Gauges
land in the shared metrics registry under ``perf/*`` labeled by
program, on both /metrics surfaces.
"""
import os
from typing import Any, Dict, Optional

from deepspeed_tpu.telemetry import costmodel as _cm
from deepspeed_tpu.telemetry.mfu import peak_flops_per_device

HBM_GBPS_ENV = "DS_HBM_GBPS"
ICI_GBPS_ENV = "DS_ICI_GBPS"
DCN_GBPS_ENV = "DS_DCN_GBPS"

#: HBM bandwidth per chip (GB/s) by device-kind substring (lowercase).
#: Sources: published TPU system specs (per-chip); "v5 lite" is the
#: device_kind a v5e reports (Google Cloud "TPU v5e" page: 819 GB/s HBM,
#: 1,600 Gbit/s ICI).
HBM_GBPS_BY_KIND = {
    "v5p": 2765.0,
    "v5 lite": 819.0,
    "v5e": 819.0,
    "v5litepod": 819.0,
    "v4": 1228.0,
    "v3": 900.0,
    "v2": 700.0,
}

#: inter-chip interconnect (ICI) bandwidth per chip (GB/s) by
#: device-kind substring.  Sources: published TPU system specs —
#: aggregate per-chip ICI link bandwidth (v2 496 Gbps, v3 656 Gbps,
#: v4 2400 Gbps, v5e 1600 Gbps, v5p 4800 Gbps), /8 to GB/s.
ICI_GBPS_BY_KIND = {
    "v5p": 600.0,
    "v5 lite": 200.0,
    "v5e": 200.0,
    "v5litepod": 200.0,
    "v4": 300.0,
    "v3": 82.0,
    "v2": 62.0,
}


def hbm_bytes_per_s(device=None, env: Optional[dict] = None
                    ) -> Optional[float]:
    """HBM bandwidth for one device in bytes/s: ``DS_HBM_GBPS`` env
    wins, then the device-kind table; None when unknown (CPU, exotic
    parts) — callers must skip floor math rather than report against a
    made-up bandwidth."""
    env = os.environ if env is None else env
    override = env.get(HBM_GBPS_ENV, "").strip()
    if override:
        return float(override) * 1e9
    if device is None:
        import jax
        device = jax.local_devices()[0]
    kind = str(getattr(device, "device_kind", "")).lower()
    for sub, gbps in HBM_GBPS_BY_KIND.items():
        if sub in kind:
            return gbps * 1e9
    return None


def ici_bytes_per_s(device=None, env: Optional[dict] = None
                    ) -> Optional[float]:
    """Inter-chip interconnect bandwidth for one device in bytes/s:
    ``DS_ICI_GBPS`` env wins, then the device-kind table; None when
    unknown (CPU, single-chip hosts) — a comm floor against a made-up
    link rate is worse than no floor."""
    env = os.environ if env is None else env
    override = env.get(ICI_GBPS_ENV, "").strip()
    if override:
        return float(override) * 1e9
    if device is None:
        import jax
        device = jax.local_devices()[0]
    kind = str(getattr(device, "device_kind", "")).lower()
    for sub, gbps in ICI_GBPS_BY_KIND.items():
        if sub in kind:
            return gbps * 1e9
    return None


def dcn_bytes_per_s(env: Optional[dict] = None) -> Optional[float]:
    """Data-center-network bandwidth in bytes/s — declaration-only
    (``DS_DCN_GBPS``): the DCN fabric between hosts has no device-kind
    table, so without an explicit declaration there is no rate."""
    env = os.environ if env is None else env
    override = env.get(DCN_GBPS_ENV, "").strip()
    if override:
        return float(override) * 1e9
    return None


def _comm_wire_bytes(report) -> int:
    """A program's per-execution interconnect wire bytes: the per-axis
    ring-accounted total when the costmodel attributed collectives,
    else the raw operand-byte aggregate as an upper bound."""
    wire = 0
    fn = getattr(report, "comm_wire_bytes", None)
    if callable(fn):
        wire = int(fn())
    if wire <= 0:
        wire = int(getattr(report, "collective_bytes", 0))
    return wire


def floor_seconds(report, peak_flops: Optional[float] = None,
                  hbm_bps: Optional[float] = None,
                  ici_bps: Optional[float] = None) -> Optional[float]:
    """Roofline lower bound for one execution: the slowest of the
    compute, HBM, and interconnect terms, over the terms whose
    hardware rate is known.  None when no rate resolves."""
    terms = []
    if peak_flops and peak_flops > 0 and report.flops > 0:
        terms.append(report.flops / peak_flops)
    if hbm_bps and hbm_bps > 0 and report.hbm_bytes > 0:
        terms.append(report.hbm_bytes / hbm_bps)
    wire = _comm_wire_bytes(report)
    if ici_bps and ici_bps > 0 and wire > 0:
        terms.append(wire / ici_bps)
    if not terms:
        return None
    return max(terms)


def comm_floor_seconds(report, ici_bps: Optional[float]
                       ) -> Optional[float]:
    """The interconnect term alone: wire bytes over the declared link
    rate; None without a rate or without comm bytes."""
    wire = _comm_wire_bytes(report)
    if not (ici_bps and ici_bps > 0 and wire > 0):
        return None
    return wire / ici_bps


def classify(report, peak_flops: Optional[float] = None,
             hbm_bps: Optional[float] = None,
             ici_bps: Optional[float] = None) -> Optional[str]:
    """"compute_bound" / "bandwidth_bound" / "comm_bound" by which
    roofline term dominates; None when the comparison needs a rate we
    don't have.  The comm term only competes when an interconnect rate
    is declared/known AND the program moves collective bytes."""
    if not (peak_flops and hbm_bps and report.flops > 0
            and report.hbm_bytes > 0):
        return None
    compute_s = report.flops / peak_flops
    memory_s = report.hbm_bytes / hbm_bps
    comm_s = comm_floor_seconds(report, ici_bps)
    if comm_s is not None and comm_s > max(compute_s, memory_s):
        return "comm_bound"
    return "compute_bound" if compute_s >= memory_s else "bandwidth_bound"


#: (DS_HBM_GBPS, DS_PEAK_FLOPS) env values -> resolved rates; the
#: device kind is constant per process, so rates only change when the
#: env overrides do — observe_achieved runs per decode step and must
#: not pay jax.local_devices + table walks every time
_RATES_CACHE: Dict[tuple, Dict[str, Optional[float]]] = {}


def device_rates(env: Optional[dict] = None) -> Dict[str, Optional[float]]:
    """(peak_flops, hbm_bps) for the first local device, None-safe on
    any backend (one place resolves both tables + envs).  Cached per
    (env-override) pair; pass an explicit ``env`` dict to bypass the
    cache (tests)."""
    from deepspeed_tpu.telemetry.mfu import PEAK_FLOPS_ENV
    cache_key = None
    if env is None:
        cache_key = (os.environ.get(HBM_GBPS_ENV, ""),
                     os.environ.get(PEAK_FLOPS_ENV, ""),
                     os.environ.get(ICI_GBPS_ENV, ""),
                     os.environ.get(DCN_GBPS_ENV, ""))
        hit = _RATES_CACHE.get(cache_key)
        if hit is not None:
            return hit
    try:
        import jax
        dev = jax.local_devices()[0]
    except Exception:
        dev = None
    try:
        peak = peak_flops_per_device(dev, env=env) if dev is not None \
            else None
    except Exception:
        peak = None
    try:
        bw = hbm_bytes_per_s(dev, env=env) if dev is not None else None
    except Exception:
        bw = None
    try:
        ici = ici_bytes_per_s(dev, env=env) if dev is not None else None
    except Exception:
        ici = None
    rates = {"peak_flops": peak, "hbm_bytes_per_s": bw,
             "ici_bytes_per_s": ici,
             "dcn_bytes_per_s": dcn_bytes_per_s(env=env),
             "device_kind": str(getattr(dev, "device_kind", "unknown"))}
    if cache_key is not None:
        _RATES_CACHE[cache_key] = rates
    return rates


def publish_report(registry, report):
    """Static cost gauges for one program family, labeled by program —
    rendered identically by ds_serve /metrics and the training
    endpoint.  Floor gauges appear only when a hardware rate resolves
    (no fictitious floors on CPU)."""
    _cm.register_report(report)
    name = report.name
    registry.set_gauge("perf/flops", float(report.flops), program=name)
    registry.set_gauge("perf/hbm_bytes", float(report.hbm_bytes),
                       program=name)
    registry.set_gauge("perf/pallas_launches",
                       float(report.pallas_launches), program=name)
    registry.set_gauge("perf/collective_bytes",
                       float(report.collective_bytes), program=name)
    wire = _comm_wire_bytes(report)
    if wire > 0:
        registry.set_gauge("comm/wire_bytes", float(wire), program=name)
    rates = device_rates()
    floor = floor_seconds(report, rates["peak_flops"],
                          rates["hbm_bytes_per_s"],
                          rates["ici_bytes_per_s"])
    if floor is not None:
        registry.set_gauge("perf/floor_ms", floor * 1e3, program=name)
    comm_floor = comm_floor_seconds(report, rates["ici_bytes_per_s"])
    if comm_floor is not None:
        registry.set_gauge("comm/floor_ms", comm_floor * 1e3,
                           program=name)


def observe_achieved(registry, name: str, duration_s: float):
    """One measured execution of a registered program: updates the
    lock-free achieved table and the ``perf/achieved_ms`` gauge, and —
    when the program's floor resolves — the ``perf/achieved_vs_floor``
    ratio (the live "N-x-over-floor" gap).  Programs whose comm floor
    resolves (wire bytes AND a declared/known interconnect rate — never
    fictitious on CPU) additionally publish ``comm/achieved_vs_floor``,
    the collapsing-link gauge."""
    _cm.record_achieved(name, duration_s)
    registry.set_gauge("perf/achieved_ms", duration_s * 1e3, program=name)
    report = _cm.get_report(name)
    if report is None:
        return
    rates = device_rates()
    floor = floor_seconds(report, rates["peak_flops"],
                          rates["hbm_bytes_per_s"],
                          rates["ici_bytes_per_s"])
    if floor and floor > 0:
        registry.set_gauge("perf/achieved_vs_floor",
                           duration_s / floor, program=name)
    comm_floor = comm_floor_seconds(report, rates["ici_bytes_per_s"])
    if comm_floor and comm_floor > 0:
        registry.set_gauge("comm/achieved_vs_floor",
                           duration_s / comm_floor, program=name)


def perf_table(env: Optional[dict] = None) -> Dict[str, Any]:
    """The ``/debug/perf`` body and the post-mortem ``perf.json``
    payload: device rates + one row per registered program (static
    cost, floor, classification, live achieved stats).  Lock-free with
    respect to every subsystem it reports on — safe to hit while a
    step is wedged."""
    rates = device_rates(env=env)
    peak, bw = rates["peak_flops"], rates["hbm_bytes_per_s"]
    ici = rates["ici_bytes_per_s"]
    achieved = _cm.get_achieved()
    programs = {}
    for name, report in sorted(_cm.get_reports().items()):
        row = report.to_dict()
        floor = floor_seconds(report, peak, bw, ici)
        row["floor_ms"] = None if floor is None else round(floor * 1e3, 6)
        row["bound"] = classify(report, peak, bw, ici)
        comm_floor = comm_floor_seconds(report, ici)
        row["comm_floor_ms"] = None if comm_floor is None else round(
            comm_floor * 1e3, 6)
        a = achieved.get(name)
        if a is not None:
            last_ms, count, total_ms = a
            row["achieved_ms"] = round(last_ms, 6)
            row["achieved_count"] = count
            # the first sample (compile + analysis trace) is excluded
            # from the total — the mean is over warm executions
            row["achieved_mean_ms"] = round(
                total_ms / (count - 1) if count > 1 else last_ms, 6)
            if floor and floor > 0:
                row["achieved_vs_floor"] = round(
                    (last_ms / 1e3) / floor, 4)
            if comm_floor and comm_floor > 0:
                row["comm_achieved_vs_floor"] = round(
                    (last_ms / 1e3) / comm_floor, 4)
        programs[name] = row
    return {
        "device_kind": rates["device_kind"],
        "peak_flops": peak,
        "hbm_gbps": None if bw is None else bw / 1e9,
        "ici_gbps": None if ici is None else ici / 1e9,
        "dcn_gbps": (None if rates["dcn_bytes_per_s"] is None
                     else rates["dcn_bytes_per_s"] / 1e9),
        "programs": programs,
    }
