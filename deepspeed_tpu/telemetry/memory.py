"""Tiered byte ledger + OOM forensics (ISSUE 14 tentpole).

The perf observatory (ISSUE 13) prices programs against *rates*
(FLOP/s, HBM GB/s); nothing in the stack accounts for *capacity*:
HBM/host/NVMe bytes are invisible until an allocation fails.  This
module is the process-wide :class:`MemoryLedger` that attributes live
bytes per **tier** (``device`` HBM via the accelerator abstraction's
``memory_stats``, ``host`` pinned/DRAM copies, ``nvme`` swap files)
and per **owner** within a tier (model params — split dtype/quantized
via the costmodel ``param_stream_bytes`` walk — optimizer state, the
KV block pool, the prefix-cache retained set, the spec draft pool).  A
training engine's device tier is **one chip's**: each owner's bytes on
the fullest local device, counted from the arrays' own shards, with the
step's summed gradient tree and the rest of its program's temporaries
(``gradients``, ``workspace``) from :func:`step_memory` once somebody has
asked for that account.

Three read surfaces, one source of truth:

- ``mem/*`` gauges in the shared metrics registry
  (:meth:`MemoryLedger.publish`) on BOTH /metrics front doors;
- the lock-free ``/debug/memory`` endpoint
  (:func:`deepspeed_tpu.telemetry.debug.memory_payload`) — answers
  while a wedged step holds the scheduler lock, same contract as
  ``/debug/perf``;
- ``memory.json`` in post-mortem bundles, carrying high-watermarks and
  the last N **allocation-failure events**: a denied ``kv.alloc`` (or
  any OOM-shaped failure) snapshots the ledger at the moment of
  failure into a bounded forensics ring AND the flight recorder
  (``mem/alloc_failure``), so "where did the bytes go" has an answer
  *after* the process is dead.

Writers take the ledger's own lock (never any scheduler lock); readers
snapshot plain dicts under the GIL — the costmodel registry idiom.
``DS_MEM_LEDGER=0`` (or ``telemetry.memory: false``) disables the
per-step taps.
"""
import collections
import os
import threading
import time
from typing import Any, Dict, List, Optional

from deepspeed_tpu.telemetry.tracing import (TRAIN_STEP_PROGRAM,
                                             get_program_memory)

MEM_ENV = "DS_MEM_LEDGER"

#: the ledger's tier vocabulary; owners within a tier are free-form
TIERS = ("device", "host", "nvme")

#: bounded allocation-failure forensics ring (events, not bytes)
DEFAULT_MAX_FAILURES = 32


#: process-wide config default: the engine installs its
#: ``telemetry.memory`` value here so config-less taps (the NVMe
#: swapper has no telemetry section) honor a config-level disable
_CONFIG_DEFAULT: Optional[bool] = None


def set_memory_config_default(value: Optional[bool]):
    """Install the process-level ``telemetry.memory`` resolution
    default (engine init; None clears)."""
    global _CONFIG_DEFAULT
    _CONFIG_DEFAULT = None if value is None else bool(value)


def memory_enabled(config_default: Optional[bool] = None) -> bool:
    """Resolution order (the repo's env-wins convention):
    ``DS_MEM_LEDGER`` env > the ``telemetry.memory`` config value the
    caller passes > the process default an engine installed > on."""
    env = os.environ.get(MEM_ENV, "").strip()
    if env:
        return env not in ("0", "false", "off")
    if config_default is not None:
        return bool(config_default)
    if _CONFIG_DEFAULT is not None:
        return _CONFIG_DEFAULT
    return True


def device_memory_stats(device_index: int = 0) -> Dict[str, int]:
    """Device memory stats through the accelerator abstraction (NOT a
    raw ``jax.devices()[0].memory_stats()`` — the CPU-degraded probe
    must stay consistent everywhere; ISSUE 14 satellite).  ``{}`` when
    the backend has no stats (CPU) — callers must skip fraction math
    rather than report against made-up limits."""
    try:
        from deepspeed_tpu.accelerator import get_accelerator
        return dict(get_accelerator().memory_stats(device_index) or {})
    except Exception:           # no backend at all (early import, tests)
        return {}


def local_device_stats() -> List[Dict[str, int]]:
    """The memory stats of every local device that reports any, through
    the accelerator abstraction (``[]`` on the CPU).  An accelerator
    that does not say how many local devices it has is asked for its
    first alone."""
    try:
        from deepspeed_tpu.accelerator import get_accelerator
        acc = get_accelerator()
        count = getattr(acc, "local_device_count", lambda: 1)()
        every = [dict(acc.memory_stats(i) or {}) for i in range(count)]
    except Exception:           # no backend at all (early import, tests)
        return []
    return [stats for stats in every if stats]


def used_bytes(stats: Dict[str, int]) -> int:
    """What a device holds now: the buffers the runtime keeps
    (``bytes_in_use``: state, batches) plus what its programs have
    reserved for their temporaries (``bytes_reserved`` — on a TPU these
    are NOT inside ``bytes_in_use``); the first alone where the backend
    reports no second."""
    return int(stats.get("bytes_in_use", 0) or 0) \
        + int(stats.get("bytes_reserved", 0) or 0)


def peak_bytes(stats: Dict[str, int]) -> int:
    """The allocator's own high-watermark of :func:`used_bytes`
    (``peak_bytes_in_use`` + ``peak_bytes_reserved``, what
    benchmarks/harness/device.py reads as ``peak_hbm_gib``); no lower
    than what is held now."""
    return max(int(stats.get("peak_bytes_in_use", 0) or 0)
               + int(stats.get("peak_bytes_reserved", 0) or 0),
               used_bytes(stats))


def fullest_device_stats(by=used_bytes) -> Dict[str, int]:
    """The stats of the local device that reads highest ``by`` (``{}``
    where no device reports any)."""
    return max(local_device_stats(), key=by, default={})


def hbm_used_fraction(stats: Optional[Dict[str, int]] = None
                      ) -> Optional[float]:
    """:func:`used_bytes` / bytes_limit (of the fullest local device
    where no stats are handed in), or None when the limit is unknown —
    no fictitious fractions on backends without memory stats."""
    s = fullest_device_stats() if stats is None else stats
    limit = s.get("bytes_limit") or 0
    if not limit:
        return None
    return float(used_bytes(s)) / float(limit)


class MemoryLedger:
    """Per-(tier, owner) live-byte attribution with high-watermarks and
    an allocation-failure forensics ring.

    Writers (``set_bytes``/``add_bytes``/``record_alloc_failure``) take
    the ledger lock; every read path copies dicts under the GIL — no
    reader can deadlock on a wedged writer.  The lock is reentrant: a
    finalizer that writes the ledger (``SwapEngine.__del__`` closes and
    accounts) runs wherever the cyclic collector does, which may be this
    thread inside a write."""

    def __init__(self, max_failures: int = DEFAULT_MAX_FAILURES):
        self._lock = threading.RLock()
        #: (tier, owner) -> live bytes
        self._owners: Dict[tuple, float] = {}
        #: (tier, owner) -> caller-supplied detail dict
        self._detail: Dict[tuple, Dict[str, Any]] = {}
        #: (tier, owner) -> high-watermark bytes
        self._owner_peak: Dict[tuple, float] = {}
        #: tier -> high-watermark of the tier TOTAL
        self._tier_peak: Dict[str, float] = {}
        #: device-stats watermark (peak_bytes over observe_device's reads)
        self._hbm_peak = 0.0
        self._failures: collections.deque = collections.deque(
            maxlen=max(int(max_failures), 1))
        self.alloc_failures = 0

    # ------------------------------------------------------------ writers
    def _store_locked(self, key: tuple, v: float,
                      detail: Optional[Dict[str, Any]]):
        """One owner write + watermark maintenance; caller holds the
        lock."""
        tier = key[0]
        self._owners[key] = v
        if detail:
            self._detail[key] = dict(detail)
        if v > self._owner_peak.get(key, 0.0):
            self._owner_peak[key] = v
        # a copy: a finalizer's write may add an owner while this sums
        total = sum(b for (t, _), b in list(self._owners.items())
                    if t == tier)
        if total > self._tier_peak.get(tier, 0.0):
            self._tier_peak[tier] = total

    def set_bytes(self, tier: str, owner: str, nbytes,
                  **detail) -> float:
        """Set one owner's live bytes in a tier (absolute, idempotent —
        per-step taps re-set rather than accumulate).  ``detail`` keys
        ride into ``/debug/memory`` and ``memory.json`` (the params
        owner carries its dtype/quantized split here)."""
        if tier not in TIERS:
            raise ValueError(f"tier={tier!r}: one of {TIERS}")
        v = float(max(nbytes, 0))
        with self._lock:
            self._store_locked((tier, owner), v, detail)
        return v

    def add_bytes(self, tier: str, owner: str, delta) -> float:
        """Relative update, atomic under the ledger lock (concurrent
        adders must not lose increments)."""
        if tier not in TIERS:
            raise ValueError(f"tier={tier!r}: one of {TIERS}")
        key = (tier, owner)
        with self._lock:
            v = max(self._owners.get(key, 0.0) + float(delta), 0.0)
            self._store_locked(key, v, None)
        return v

    def observe_device(self) -> Dict[str, int]:
        """Sample every local device's memory stats, tracking the
        high-watermark of :func:`peak_bytes` over all of them; returns
        the stats of the device that holds most now (``{}`` on backends
        without them)."""
        every = local_device_stats()
        peak = float(max(map(peak_bytes, every), default=0))
        if peak:
            with self._lock:
                if peak > self._hbm_peak:
                    self._hbm_peak = peak
        return max(every, key=used_bytes, default={})

    def record_alloc_failure(self, site: str, flightrec=None,
                             **detail) -> Dict[str, Any]:
        """OOM forensics: one allocation failure (a denied ``kv.alloc``,
        a compile-time OOM, a failed host pin) snapshots the ledger —
        per-tier owner bytes at the moment of failure plus the device
        stats — into the bounded failure ring AND the flight recorder
        (kind ``mem/alloc_failure``), so the post-mortem bundle can
        answer "what held the bytes when this failed"."""
        stats = self.observe_device()
        with self._lock:
            owners = dict(self._owners)
            self.alloc_failures += 1
        event = {
            "ts": round(time.time(), 3),
            "site": site,
            "detail": dict(detail),
            "tiers": {t: int(sum(b for (tt, _), b in owners.items()
                                 if tt == t)) for t in TIERS},
            "owners": {f"{t}/{o}": int(b)
                       for (t, o), b in sorted(owners.items())},
        }
        if stats:
            event["device"] = {k: int(v) for k, v in stats.items()
                               if isinstance(v, (int, float))}
        with self._lock:
            self._failures.append(event)
        if flightrec is None:
            from deepspeed_tpu.telemetry.flight_recorder import \
                get_flight_recorder
            flightrec = get_flight_recorder()
        flightrec.record("mem/alloc_failure", site=site,
                         tiers=event["tiers"], **detail)
        return event

    # ------------------------------------------------------------ readers
    def owner_bytes(self, tier: str, owner: str) -> float:
        return self._owners.get((tier, owner), 0.0)

    def tier_bytes(self, tier: str) -> float:
        owners = dict(self._owners)
        return sum(b for (t, _), b in owners.items() if t == tier)

    def failures(self):
        return list(self._failures)

    def snapshot(self) -> Dict[str, Any]:
        """The ``/debug/memory`` / ``memory.json`` body: per-tier owner
        tables with watermarks, device stats, and the failure ring —
        all from GIL-atomic dict copies (lock-free read contract)."""
        owners = dict(self._owners)
        detail = dict(self._detail)
        owner_peak = dict(self._owner_peak)
        tier_peak = dict(self._tier_peak)
        # read-only device probe: no ledger lock, no peak mutation —
        # the /debug/memory reader must not touch ANY lock a wedged
        # writer could be holding
        stats = fullest_device_stats()
        tiers: Dict[str, Any] = {}
        for t in TIERS:
            rows = {}
            for (tt, o), b in sorted(owners.items()):
                if tt != t:
                    continue
                row = {"bytes": int(b),
                       "watermark_bytes": int(owner_peak.get((tt, o), b))}
                d = detail.get((tt, o))
                if d:
                    row["detail"] = d
                rows[o] = row
            total = sum(b for (tt, _), b in owners.items() if tt == t)
            if rows or tier_peak.get(t):
                tiers[t] = {"total_bytes": int(total),
                            "watermark_bytes": int(tier_peak.get(t, total)),
                            "owners": rows}
        out: Dict[str, Any] = {
            "ts": round(time.time(), 3),
            "tiers": tiers,
            "alloc_failures": self.alloc_failures,
            "failures": list(self._failures),
        }
        if stats:
            dev = {k: int(v) for k, v in stats.items()
                   if isinstance(v, (int, float))}
            frac = hbm_used_fraction(stats)
            if frac is not None:
                dev["used_fraction"] = round(frac, 4)
            dev["used_bytes"] = used_bytes(stats)
            dev["watermark_bytes"] = int(max(self._hbm_peak,
                                             peak_bytes(stats)))
            out["device_stats"] = dev
        return out

    # ---------------------------------------------------------- exposition
    def publish(self, registry) -> Dict[str, int]:
        """``mem/*`` gauges into a metrics registry (rendered by both
        /metrics surfaces).  Device-stat gauges appear only when the
        backend reports them — no fictitious limits on CPU.  Returns
        the device stats it sampled so per-step callers can derive the
        used fraction without a second accelerator probe."""
        owners = dict(self._owners)
        totals: Dict[str, float] = {}
        for (t, o), b in owners.items():
            registry.set_gauge("mem/owner_bytes", b, tier=t, owner=o)
            totals[t] = totals.get(t, 0.0) + b
        for t, total in totals.items():
            registry.set_gauge("mem/tier_bytes", total, tier=t)
        for t, peak in dict(self._tier_peak).items():
            registry.set_gauge("mem/tier_watermark_bytes", peak, tier=t)
        registry.set_counter("mem/alloc_failures",
                             float(self.alloc_failures))
        stats = self.observe_device()
        if stats:
            registry.set_gauge("mem/hbm_used_bytes",
                               float(used_bytes(stats)))
            if stats.get("bytes_limit"):
                registry.set_gauge("mem/hbm_limit_bytes",
                                   float(stats["bytes_limit"]))
            frac = hbm_used_fraction(stats)
            if frac is not None:
                registry.set_gauge("mem/hbm_used_fraction", round(frac, 4))
        return stats

    def publish_and_feed(self, registry, anomaly=None,
                         corr: Optional[str] = None):
        """The per-step tap both the engine and the serving scheduler
        run: publish the ``mem/*`` gauges and — where the backend
        reports device stats — feed the HBM used fraction into the
        rolling anomaly detector as ``mem_hbm`` (a leak flags as a
        one-sided outlier BEFORE the OOM).  One accelerator probe per
        call: the fraction derives from publish()'s own sample."""
        stats = self.publish(registry)
        if anomaly is None:
            return
        frac = hbm_used_fraction(stats) if stats else None
        if frac is not None:
            anomaly.observe("mem_hbm", frac, corr=corr)

    def reset(self):
        with self._lock:
            self._owners.clear()
            self._detail.clear()
            self._owner_peak.clear()
            self._tier_peak.clear()
            self._failures.clear()
            self._hbm_peak = 0.0
            self.alloc_failures = 0


# -------------------------------------------------- owner attribution
def attribute_params(ledger: MemoryLedger, params, *,
                     tier: str = "device", owner: str = "params",
                     stream: Optional[Dict[str, int]] = None,
                     nbytes: Optional[int] = None) -> Dict[str, int]:
    """Attribute a model's parameter bytes into the ledger, split
    dtype/quantized via the costmodel ``param_stream_bytes`` walk (the
    SAME math serve_bench/decode_profile floors use, so the ledger and
    the perf observatory can never disagree about param bytes).
    ``stream`` short-circuits the walk when the caller already holds a
    ``param_stream_bytes`` result (the serving scheduler's cost
    stream).  ``nbytes``: the row's bytes where they are not the
    walk's total (a training engine's are one chip's share of it,
    :func:`device_bytes`; the split stays as the row's detail)."""
    if stream is None:
        from deepspeed_tpu.telemetry.costmodel import param_stream_bytes
        stream = param_stream_bytes(params)
    total = (stream.get("dense_int8_bytes", 0)
             + stream.get("expert_int8_bytes", 0)
             + stream.get("plain_bytes", 0))
    ledger.set_bytes(
        tier, owner, total if nbytes is None else nbytes,
        dense_int8_bytes=int(stream.get("dense_int8_bytes", 0)),
        expert_int8_bytes=int(stream.get("expert_int8_bytes", 0)),
        plain_bytes=int(stream.get("plain_bytes", 0)))
    return stream


def tree_bytes(tree) -> int:
    """Concrete leaf bytes of a pytree (KV pools, optimizer state):
    ``size * itemsize`` per array leaf, non-arrays skipped."""
    import jax
    import numpy as np
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        try:
            total += int(leaf.size) * int(np.dtype(leaf.dtype).itemsize)
        except (TypeError, AttributeError, ValueError):
            continue
    return total


def device_bytes(tree) -> Dict[int, int]:
    """``{device id: bytes}`` of a pytree's array leaves on each local
    device, from every leaf's own sharding: the shard of its shape that
    the device holds (a replicated leaf counts whole on each).
    Arithmetic over shapes — no device read, no sync — so abstract
    leaves (``ShapeDtypeStruct`` with a sharding) count as arrays do.
    Leaves kept in pinned host memory and leaves that are no arrays
    are left out."""
    import jax
    import numpy as np
    per: Dict[int, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        sharding = getattr(leaf, "sharding", None)
        if sharding is None or getattr(sharding, "memory_kind",
                                       None) == "pinned_host":
            continue
        try:
            itemsize = int(np.dtype(leaf.dtype).itemsize)
        except TypeError:       # a typed key array: no numpy dtype
            continue
        shards = sharding.addressable_devices_indices_map(leaf.shape)
        for device, index in shards.items():
            held = itemsize
            for dim, part in zip(leaf.shape, index):
                held *= len(range(*part.indices(dim)))
            per[device.id] = per.get(device.id, 0) + held
    return per


def fullest_device_bytes(**owners) -> Dict[str, int]:
    """Each owner's :func:`device_bytes` on the ONE local device that
    holds most of them all together, so the owners add up to what a
    real device holds (all zeros where nothing is on a device)."""
    per = {owner: device_bytes(tree) for owner, tree in owners.items()}
    devices = {d for held in per.values() for d in held}
    fullest = max(devices, default=None, key=lambda d: sum(
        held.get(d, 0) for held in per.values()))
    return {owner: held.get(fullest, 0) for owner, held in per.items()}


def step_memory(name: str = TRAIN_STEP_PROGRAM,
                create: bool = True) -> Optional[Dict[str, Any]]:
    """Where one chip's memory goes while the step registered under
    ``name`` runs: the step's own account of its bytes, per device, or
    None where no step has run.  Made on request (nothing a step pays:
    the first asker pays the engine's ``memory_thunk`` — one load of
    the executable that runs, a full compile where no persistent cache
    holds it — and should be the thread that trains);
    :func:`peek_step_memory` is for a reader that must start nothing::

      state       {"params", "optimizer", "state_other"}: the engine's
                  state on its fullest device, counted from the arrays'
                  shards where they were placed (:func:`device_bytes`)
      batch       one step's batch, the same way
      program     {"argument", "output", "alias", "temp",
                  "generated_code", "peak"}: ``memory_analysis()`` of
                  the executable that runs, which XLA states per device
      gradients   the summed gradient tree as the step lays it out
                  (``tracing.gradient_bytes``: counted by
                  runtime/step_programs.py while the step is traced);
                  one of the temporaries.  None: none was counted
      temporaries the program's temporaries that are live when it is at
                  its fullest (:func:`live_temporaries`): program.peak
                  - argument - (output - alias), XLA's own peak less
                  what is no temporary — what a TPU's runtime reserves
                  for the program (``peak_bytes_reserved``).  ONE
                  definition: None where that peak is not known to
                  cover temporaries (the CPU backend's does not), never
                  ``program.temp``, the sum of the temporary
                  allocations, which reads higher (+ 0.85 GiB in a 760M
                  step) and is there to be read beside it
      workspace   temporaries - gradients: activations kept for the
                  backward, a micro-batch's gradients in flight, the
                  kernels' and collectives' buffers.  None without
                  ``temporaries``
      expected_peak   state + batch + (output - alias) + temporaries
                  + generated_code.  None without ``temporaries``
      layout_padding  argument - (state + batch): XLA's word for the
                  same arrays as the executable lays them out, less
                  the count by shards (the RNG key, tiles' padding)
      allocator   {"peak_bytes_in_use", "peak_bytes_reserved",
                  "bytes_limit"} of the local device whose peak is
                  highest; None where the backend reports none (CPU)
      unaccounted the allocator's peak - expected_peak (None without
                  either): the one term that measures a reading
                  against a count
    """
    counted = get_program_memory(name, create=create)
    if counted is None:
        return None
    program, gradients = counted["program"], counted["gradients"]
    held = sum(counted["state"].values()) + counted["batch"]
    temporaries = counted["temporaries"]
    account = dict(
        counted, workspace=None, expected_peak=None,
        layout_padding=program["argument"] - held,
        allocator=None, unaccounted=None)
    if temporaries is not None:
        account["workspace"] = temporaries - gradients
        account["expected_peak"] = held + program["output"] \
            - program["alias"] + temporaries + program["generated_code"]
    stats = fullest_device_stats(by=peak_bytes)
    if stats:
        account["allocator"] = {
            key: int(stats.get(key, 0) or 0) for key in
            ("peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")}
        if temporaries is not None:
            account["unaccounted"] = peak_bytes(stats) \
                - account["expected_peak"]
    return account


def peek_step_memory(name: str = TRAIN_STEP_PROGRAM
                     ) -> Optional[Dict[str, Any]]:
    """:func:`step_memory` where somebody has asked for it already, else
    None: no thunk is called, nothing is compiled or loaded and no
    ledger row is written — what ``/debug/memory`` and a post-mortem
    bundle show (a read-only GET and a crashing process start no load
    of the step's executable), as ``peek_iostat`` is for the swap
    I/O."""
    return step_memory(name, create=False)


def live_temporaries(program: Dict[str, int],
                     gradients: Optional[int]) -> Optional[int]:
    """:func:`step_memory`'s ``temporaries`` of a program's six numbers:
    XLA's peak less the arguments and the outputs that alias none.
    None where that peak cannot be held to cover temporaries: it leaves
    less than ``gradients``, the tree the step certainly holds among
    them (the CPU backend's peak is its arguments' and some hundred
    bytes), or no such tree was counted to hold it against."""
    live = program["peak"] - program["argument"] \
        - (program["output"] - program["alias"])
    if gradients is None or live < gradients:
        return None
    return live


def program_memory(executable) -> Optional[Dict[str, int]]:
    """``memory_analysis()`` of a compiled program as :func:`step_memory`
    keeps it, or None where the backend has no such analysis."""
    analysis = executable.memory_analysis()
    if analysis is None:
        return None
    sizes = {key: int(getattr(analysis, f"{key}_size_in_bytes"))
             for key in ("argument", "output", "alias", "temp",
                         "generated_code")}
    return dict(sizes, peak=int(analysis.peak_memory_in_bytes))


# ------------------------------------------------- process-wide ledger
_GLOBAL_LOCK = threading.Lock()
_GLOBAL: Optional[MemoryLedger] = None


def get_memory_ledger() -> MemoryLedger:
    """The process-wide ledger (created on first use).  Subsystems
    wanting isolation construct their own MemoryLedger (tests)."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = MemoryLedger()
        return _GLOBAL


def reset_memory_ledger():
    """Tests: drop the process-wide ledger so the next get() is
    fresh."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        _GLOBAL = None
