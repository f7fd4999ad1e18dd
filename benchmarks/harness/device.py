"""The device the run is on: it has to be a TPU of a kind in peaks.json
and as many chips as the cell asks for, or there is no result."""
import os

import jax

from harness.manifest import BENCH_DIR, load_json


def require_device(chips):
    """Returns (devices, peaks of this device_kind); exits non-zero with
    no result line on anything but ``chips`` chips of a known TPU."""
    devices = jax.devices()
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU, jax found platform="
                         f"{devices[0].platform!r} ({kind})")
    if len(devices) != chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chip(s), "
                         f"jax sees {len(devices)}")
    return devices, peaks_for(kind)


def peaks_for(kind):
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["device_kinds"]
    if kind not in table:
        raise SystemExit(f"benchmark: device_kind {kind!r} is not in "
                         f"benchmarks/peaks.json")
    return table[kind]


def peak_bytes(devices):
    """Peak bytes of HBM taken on the fullest device: the buffers the
    runtime holds (``peak_bytes_in_use``: parameters, optimizer state,
    batches) plus what compiled programs reserve while they run
    (``peak_bytes_reserved``: activations and every other temporary —
    on a TPU these are NOT inside bytes_in_use; PERF.md, PR 23).  0 where
    the backend reports no memory statistics, as the CPU does."""
    def one(device):
        stats = device.memory_stats() or {}
        return stats.get("peak_bytes_in_use", 0) \
            + stats.get("peak_bytes_reserved", 0)
    return max(one(d) for d in devices)


def device_fields(devices):
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak_bytes(devices)}
