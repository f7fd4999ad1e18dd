"""Shared bench timing helpers — one measurement discipline in ONE
place (ISSUE 12 satellite).

A host-side timing of a short device program is mostly fixed cost:
dispatch, the blocking round trip, the fetch of whatever is returned.
These helpers sync by FETCHING A VALUE and time the SLOPE between two
on-device chained step counts, which cancels every fixed cost.

Every sweep/profile script imports these instead of growing its own
copy (decode_profile, serve_bench, qgemm_sweep, ggemm_sweep; the
original lives in scripts/flash_ab.py).

ISSUE 13 adds the **bench ledger**: a versioned BenchRecord schema
(git rev, device kind/count, per-metric direction) and an append-only
``BENCH/ledger.jsonl`` history every bench script can emit into
(``DS_BENCH_LEDGER=1``; ``DS_BENCH_DIR`` overrides the directory).
``bench_compare --history`` gates regressions against the rolling
baseline and refuses cross-device/cross-model diffs."""
import json
import os
import subprocess
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

#: BenchRecord schema version — bump on incompatible field changes;
#: bench_compare refuses to mix major versions
BENCH_SCHEMA = "ds-bench/1"
LEDGER_ENV = "DS_BENCH_LEDGER"
BENCH_DIR_ENV = "DS_BENCH_DIR"


def git_rev() -> str:
    """Short git revision of the working tree ("unknown" outside a
    checkout — records stay comparable either way)."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            capture_output=True, text=True, timeout=10)
        if out.returncode == 0:
            return out.stdout.strip()
    except (OSError, subprocess.SubprocessError):
        pass
    return "unknown"


def bench_meta() -> dict:
    """The BenchRecord envelope: where/when/what-hardware this record
    was measured on.  ``device_kind`` is the cross-device comparison
    guard bench_compare enforces (a CPU-smoke record must never gate an
    on-chip one)."""
    devs = jax.devices()
    return {
        "schema": BENCH_SCHEMA,
        "git_rev": git_rev(),
        "unix_ts": round(time.time(), 3),
        "platform": devs[0].platform,
        "device_kind": str(getattr(devs[0], "device_kind", "unknown")),
        "device_count": len(devs),
    }


def make_record(metric: str, value, unit=None, detail=None,
                direction=None) -> dict:
    """A schema'd BenchRecord.  ``direction`` ("lower_better" /
    "higher_better") makes the regression direction explicit instead of
    name-inferred — bench_compare honors it when present."""
    rec = {"metric": str(metric), "value": value, "meta": bench_meta()}
    if unit is not None:
        rec["unit"] = unit
    if detail:
        rec["detail"] = detail
    if direction is not None:
        if direction not in ("lower_better", "higher_better"):
            raise ValueError(f"direction={direction!r}: must be "
                             "lower_better or higher_better")
        rec["direction"] = direction
    return rec


def ledger_enabled() -> bool:
    return os.environ.get(LEDGER_ENV, "").strip() not in ("", "0")


def ledger_path() -> str:
    base = os.environ.get(BENCH_DIR_ENV, "").strip() or "BENCH"
    return os.path.join(base, "ledger.jsonl")


def append_ledger(record: dict, path=None) -> str:
    """Append one record (JSONL) to the bench ledger; creates the
    directory on first use.  Records without a ``meta`` envelope get
    one (so pre-schema emitters can still ride the history)."""
    if "meta" not in record:
        record = dict(record, meta=bench_meta())
    path = path or ledger_path()
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(record) + "\n")
    return path


def emit_ledger(record: dict) -> dict:
    """The one call bench scripts add beside their print: appends to
    the ledger iff DS_BENCH_LEDGER is armed.  Returns the record."""
    if ledger_enabled() and isinstance(record, dict) \
            and "metric" in record:
        append_ledger(record)
    return record


def fetch(x):
    """Value-fetch synchronization: materialize ``x`` on the host and
    return it as numpy.  This is the ONE sync primitive benches should
    use."""
    return np.asarray(x)


def mem_peak_fields() -> dict:
    """``mem_peak_*`` record fields from the memory observatory
    (ISSUE 14 satellite): per-tier high-watermarks (the scheduler /
    engine taps maintain them during the bench) plus the device HBM
    peak where the backend reports stats — so ``bench_compare
    --history`` gates memory regressions like latency ones.  Empty
    when the ledger never armed (DS_MEM_LEDGER=0)."""
    try:
        from deepspeed_tpu.telemetry.memory import get_memory_ledger
        led = get_memory_ledger()
        led.observe_device()            # fold the current HBM sample in
        out = {}
        payload = led.snapshot()
        for tier, t in payload["tiers"].items():
            out[f"mem_peak_{tier}_bytes"] = int(t["watermark_bytes"])
            for owner in ("kv_pool", "prefix_cache"):
                row = t["owners"].get(owner)
                if row is not None:
                    out[f"mem_peak_{owner}_bytes"] = \
                        int(row["watermark_bytes"])
        dev = payload.get("device_stats")
        if dev and dev.get("watermark_bytes"):
            out["mem_peak_hbm_bytes"] = int(dev["watermark_bytes"])
        if led.alloc_failures:
            out["mem_alloc_failures"] = int(led.alloc_failures)
        return out
    except Exception:
        return {}


def comm_fields() -> dict:
    """``comm_*`` record fields from the communication observatory
    (ISSUE 19 satellite): per-mesh-axis collective wire bytes summed
    over every registered cost-model program, the achieved GB/s per
    timed collective op, and the overlap fraction — so
    ``bench_compare --history`` gates a bench that silently started
    moving more bytes (or moving them slower) over the interconnect.
    Empty when neither the cost model nor CommStat ever armed."""
    try:
        from deepspeed_tpu.telemetry import costmodel as _cm
        from deepspeed_tpu.telemetry.commstat import peek_commstat
        out = {}
        per_axis = {}
        for report in _cm.get_reports().values():
            for key, row in report.collectives.items():
                axis = key.split("|")[1] if key.count("|") >= 1 else "?"
                per_axis[axis] = per_axis.get(axis, 0) \
                    + int(row.get("wire_bytes", 0))
        for axis, wire in sorted(per_axis.items()):
            if wire > 0:
                out[f"comm_wire_{axis}_bytes"] = wire
        cs = peek_commstat()
        if cs is not None:
            summ = cs.summary()
            for row in summ["ops"].values():
                if row.get("mean_gbps"):
                    out[f"comm_{row['op']}_gbps"] = row["mean_gbps"]
            if summ.get("overlap_fraction") is not None:
                out["comm_overlap_fraction"] = round(
                    summ["overlap_fraction"], 4)
        return out
    except Exception:
        return {}


def timed_chain(step_fn, state0, n, warmup=2):
    """On-device loop slope: run ``m`` and ``5m`` chained ``step_fn``
    applications inside one jitted ``fori_loop`` (a data dependency
    chains them), sync by fetching a scalar, and report the per-step
    SLOPE in seconds — fixed dispatch/round-trip costs cancel between the
    two step counts.  ``state0`` is a tuple whose first element is an
    array (reduced to the fetched scalar)."""
    @jax.jit
    def run(state, m):
        state = lax.fori_loop(0, m, lambda i, s: step_fn(s), state)
        return jnp.sum(state[0].astype(jnp.float32))

    float(run(state0, warmup))          # compile + warm (value fetch syncs)

    def once(m):
        t0 = time.time()
        float(run(state0, m))
        return time.time() - t0

    t_small = min(once(n), once(n))
    t_big = min(once(5 * n), once(5 * n))
    return (t_big - t_small) / (4 * n)


def timed_unrolled(step_fn, state0, n):
    """As :func:`timed_chain`, with the ``n`` and ``3n`` chained
    applications written out in one jitted program instead of a
    ``fori_loop``: a loop copies a carried buffer that a custom call (a
    Pallas kernel) cannot update in place — 0.65 ms a step for 268 MB on a
    v5e, as large as the call it times.  For steps whose whole output is
    the next step's input."""
    def run(m):
        @jax.jit
        def fn(state):
            for _ in range(m):
                state = step_fn(state)
            return jnp.sum(state[0].astype(jnp.float32))
        float(fn(state0))                # compile + warm

        def once():
            t0 = time.time()
            float(fn(state0))
            return time.time() - t0
        return min(once() for _ in range(3))

    return (run(3 * n) - run(n)) / (2 * n)


def timed_chain_ms(step_fn, state0, n, warmup=3):
    """``timed_chain`` in milliseconds (decode_profile's historical
    unit)."""
    return timed_chain(step_fn, state0, n, warmup=warmup) * 1e3
