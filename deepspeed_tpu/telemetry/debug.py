"""Live debug introspection helpers (ISSUE 7 tentpole).

The ``/debug/*`` surface shared by ``bin/ds_serve`` and the training
:class:`~deepspeed_tpu.telemetry.http_endpoint.MetricsServer`:

- ``format_thread_stacks()`` — an all-thread Python stack dump.  This
  is THE tool for a wedged scheduler: the lock-free watchdog can flag
  DEGRADED but cannot say *where* the step is stuck; ``/debug/stacks``
  can, because it never takes any scheduler lock (it walks
  ``sys._current_frames()``, which the interpreter hands over without
  cooperation from the stuck thread).
- ``flightrec_payload()`` — the ``/debug/flightrec`` JSON body with
  ``?n=``/``?corr=``/``?kind=`` filtering.
- ``perf_payload()`` — the ``/debug/perf`` JSON body (ISSUE 13): the
  registered per-program cost table with roofline floors and live
  achieved-vs-floor.  Reads only dict snapshots from the cost-model
  store — never a scheduler lock — so it answers while a step is
  wedged (the same contract the chaos acceptance test enforces).
- ``memory_payload()`` — the ``/debug/memory`` JSON body (ISSUE 14):
  the tiered byte ledger (per-owner bytes, watermarks, the
  allocation-failure forensics ring) plus the swap I/O summary.  Same
  lock-free contract: ledger/iostat snapshots are GIL-atomic dict
  copies, never a scheduler lock — "where did the bytes go" must be
  answerable while the step that ran out of them is wedged.
- ``numerics_payload()`` — the ``/debug/numerics`` JSON body
  (ISSUE 15): the training-health bank (per-leaf-group grad norms,
  loss/loss-scale/update-ratio timeline, NaN provenance records,
  determinism fingerprint stream, restore audits).  Resolving the
  lazily banked device records IS the read path — it takes only the
  bank's own lock plus one device fetch, never an engine/scheduler
  lock, and a GET on a process without an armed bank answers
  ``{"armed": false}`` without creating one (the peek contract).
- ``offload_payload()`` — the ``/debug/offload`` JSON body
  (ISSUE 18): every live SwapEngine's integrity + occupancy snapshot
  (tier bytes, checksum failures, quarantine ring, retained write
  sources, circuit-breaker state/counters).  Reads dict snapshots
  through a weakref registry only — never an engine or scheduler
  lock — so "is the NVMe tier sick" is answerable while the step that
  hit it is wedged.
- ``comm_payload()`` — the ``/debug/comm`` JSON body (ISSUE 19): the
  CommStat per-op runtime stats, the per-program per-axis collective
  attribution with comm floors, and the overlap meter.  Peek contract
  (an unarmed process answers ``{"armed": false}``) and lock-free like
  the rest — a wedged collective must not block its own diagnosis.
- ``parse_debug_query()`` — tiny query-string parsing shared by both
  HTTP front doors.

Everything here is read-only and lock-free with respect to the
subsystems it inspects — safe to hit while the process is wedged,
which is the whole point.
"""
import sys
import threading
import time
import traceback
from typing import Any, Dict, Optional, Tuple
from urllib.parse import parse_qs, urlparse


def format_thread_stacks() -> str:
    """Dump every thread's Python stack (the ``py-spy dump`` you can
    curl).  Thread names come from ``threading.enumerate()`` — daemon
    loops in this codebase are named (ds-serve-loop, ds-serve-watchdog,
    ds-metrics), so a wedged step reads as "ds-serve-loop is inside
    ``model.decode_fn``" at a glance."""
    names = {t.ident: t.name for t in threading.enumerate()}
    lines = [f"# thread stack dump pid={__import__('os').getpid()} "
             f"unix={time.time():.3f} threads={len(names)}"]
    for ident, frame in sorted(sys._current_frames().items()):
        name = names.get(ident, "?")
        lines.append(f"\n--- thread {ident} ({name}) ---")
        lines.extend(line.rstrip()
                     for line in traceback.format_stack(frame))
    return "\n".join(lines) + "\n"


def parse_debug_query(path: str) -> Tuple[str, Dict[str, str]]:
    """``/debug/flightrec?n=100&corr=req-3`` -> ("/debug/flightrec",
    {"n": "100", "corr": "req-3"})."""
    parsed = urlparse(path)
    query = {k: v[-1] for k, v in parse_qs(parsed.query).items()}
    return parsed.path, query


def flightrec_payload(recorder, query: Optional[Dict[str, str]] = None
                      ) -> Dict[str, Any]:
    """The ``/debug/flightrec`` body: recorder stats + a filtered event
    snapshot.  Query keys: ``n`` (last N after filtering, default 256),
    ``corr`` (exact correlation id), ``kind`` (prefix match)."""
    query = query or {}
    try:
        last_n = int(query.get("n", 256))
    except ValueError:
        last_n = 256
    events = recorder.events(last_n=last_n,
                             corr=query.get("corr"),
                             kind_prefix=query.get("kind"))
    return {
        "capacity": recorder.capacity,
        "enabled": recorder.enabled,
        "total_recorded": recorder.total_recorded,
        "dropped": recorder.dropped,
        "returned": len(events),
        "events": events,
    }


def memory_payload(query: Optional[Dict[str, str]] = None
                   ) -> Dict[str, Any]:
    """The ``/debug/memory`` body: ledger snapshot (tiers × owners with
    watermarks + failure ring + device stats), the train step's own
    account of one chip's bytes under ``"step"`` (``peek_step_memory``:
    None until somebody has asked ``step_memory()`` for it — this body
    never starts the load of the step's executable that the first asker
    pays) and the swap I/O summary.
    ``?tier=<name>`` filters the tier table.  Reads the EXISTING iostat
    and account (peek, never create/install): a read-only debug GET
    must not mutate global state, and an aio import failure must not
    500 the endpoint the ledger half can still answer."""
    from deepspeed_tpu.telemetry.iostat import peek_iostat
    from deepspeed_tpu.telemetry.memory import (get_memory_ledger,
                                                peek_step_memory)
    payload = get_memory_ledger().snapshot()
    payload["step"] = peek_step_memory()
    io = peek_iostat()
    payload["swap"] = io.summary() if io is not None else {"ops": {}}
    want = (query or {}).get("tier")
    if want:
        payload["tiers"] = {k: v for k, v in payload["tiers"].items()
                            if k == want}
    return payload


def numerics_payload(query: Optional[Dict[str, str]] = None
                     ) -> Dict[str, Any]:
    """The ``/debug/numerics`` body: group-norm table + health
    timeline + NaN provenance + fingerprints.  ``?n=<N>`` bounds the
    history tail (default 64); ``?group=<substring>`` filters the
    per-group norms in each returned entry."""
    from deepspeed_tpu.telemetry.numerics import peek_numerics
    state = peek_numerics()
    if state is None:
        return {"armed": False, "groups": [], "history": [],
                "nonfinite": {"unexpected_steps": 0, "overflow_steps": 0,
                              "records": []},
                "fingerprints": [], "restore_audits": []}
    payload = state.snapshot()
    payload["armed"] = True
    query = query or {}
    try:
        last_n = int(query.get("n", 64))
    except ValueError:
        last_n = 64
    payload["history"] = payload["history"][-last_n:]
    want = query.get("group")
    if want:
        keep = [i for i, g in enumerate(payload["groups"])
                if want in g]
        payload["groups"] = [payload["groups"][i] for i in keep]
        for entry in payload["history"]:
            norms = entry.get("group_norms")
            if norms:
                entry["group_norms"] = [norms[i] for i in keep
                                        if i < len(norms)]
    return payload


def offload_payload(query: Optional[Dict[str, str]] = None
                    ) -> Dict[str, Any]:
    """The ``/debug/offload`` body: one snapshot per live SwapEngine
    (owner, tier occupancy, integrity counters, quarantine ring,
    breaker state).  ``?owner=<substring>`` filters engines.  Peek
    contract: the weakref registry is read as-is — a GET never creates
    or retains an engine."""
    from deepspeed_tpu.offload.engine import live_engines
    engines = [e.snapshot() for e in live_engines()]
    want = (query or {}).get("owner")
    if want:
        engines = [s for s in engines if want in s.get("owner", "")]
    return {"engines": engines, "count": len(engines)}


def perf_payload(query: Optional[Dict[str, str]] = None) -> Dict[str, Any]:
    """The ``/debug/perf`` body: device rates + the per-program cost
    table (static cost, roofline floor, bound classification, live
    achieved-vs-floor).  ``?program=<substring>`` filters rows."""
    from deepspeed_tpu.telemetry.roofline import perf_table
    payload = perf_table()
    want = (query or {}).get("program")
    if want:
        payload["programs"] = {k: v for k, v
                               in payload["programs"].items()
                               if want in k}
    return payload


def comm_payload(query: Optional[Dict[str, str]] = None) -> Dict[str, Any]:
    """The ``/debug/comm`` body (ISSUE 19): the CommStat runtime
    summary (per-op latency/GB-s, trace-time byte totals, the overlap
    meter), the per-program per-axis collective attribution with comm
    floors, and the resolved interconnect rates.  Peek contract: an
    unarmed process answers ``{"armed": false}`` without creating the
    CommStat; lock-free throughout (dict snapshots only), so it
    answers while a collective — or an injected ``comm.collective``
    stall — has the step wedged.  ``?op=<substring>`` filters the op
    rows, ``?program=<substring>`` the program rows."""
    from deepspeed_tpu.telemetry import costmodel as _cm
    from deepspeed_tpu.telemetry.commstat import peek_commstat
    from deepspeed_tpu.telemetry.roofline import (comm_floor_seconds,
                                                  device_rates)
    cs = peek_commstat()
    payload: Dict[str, Any] = {"armed": cs is not None}
    if cs is not None:
        payload.update(cs.summary())
    else:
        payload.update({"ops": {}, "traced": {},
                        "overlap_fraction": None, "denied": 0})
    rates = device_rates()
    ici = rates.get("ici_bytes_per_s")
    payload["ici_gbps"] = None if ici is None else ici / 1e9
    dcn = rates.get("dcn_bytes_per_s")
    payload["dcn_gbps"] = None if dcn is None else dcn / 1e9
    programs: Dict[str, Any] = {}
    achieved = _cm.get_achieved()
    for name, report in sorted(_cm.get_reports().items()):
        wire = report.comm_wire_bytes()
        if not report.collectives and wire <= 0:
            continue                    # compute-only program: no comm row
        row: Dict[str, Any] = {
            "collectives": {k: dict(v)
                            for k, v in report.collectives.items()},
            "comm_wire_bytes": wire,
        }
        floor = comm_floor_seconds(report, ici)
        row["comm_floor_ms"] = None if floor is None else round(
            floor * 1e3, 6)
        a = achieved.get(name)
        if a is not None and floor and floor > 0:
            row["comm_achieved_vs_floor"] = round((a[0] / 1e3) / floor, 4)
        programs[name] = row
    payload["programs"] = programs
    query = query or {}
    want_op = query.get("op")
    if want_op:
        payload["ops"] = {k: v for k, v in payload["ops"].items()
                          if want_op in k}
        payload["traced"] = {k: v for k, v in payload["traced"].items()
                             if want_op in k}
    want_prog = query.get("program")
    if want_prog:
        payload["programs"] = {k: v for k, v
                               in payload["programs"].items()
                               if want_prog in k}
    return payload
