"""Is a run's standard output something the driver will accept?

    python3 benchmarks/run.py --workload <cell> ... --trace <0|1> > out.txt
    python3 benchmarks/tests/check_result_line.py --workload <cell> --trace <0|1> out.txt

The driver takes the **last line** of standard output and wants a JSON
object with ``correct``, ``attempted``, ``failed``, ``metrics`` and
``device``, where ``metrics`` gives each metric ``BENCHMARK.json`` lists
for the cell (end-to-end in an untraced run, per-layer in a traced one)
as ``{"value": finite number, "unit": the manifest's}``.  PR 24 was
refused for exactly this (``output_malformed``): a reader that finds
nothing has its metric left out of the line, ``json.dumps`` writes a NaN
as a bare word, and anything printed after the result line takes its
place.  ``check`` returns the complaints (none = the line will pass);
the command prints a verdict and exits non-zero on any."""
import argparse
import json
import math
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH_DIR]

from harness.manifest import Manifest     # noqa: E402

KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


def _refuse(word):
    raise ValueError(f"{word} is not JSON")


def _number(x):
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and math.isfinite(x)


def check(workload, stdout, traced, manifest=None):
    """Complaints about ``stdout`` (the whole of a run's standard output)
    as the result of ``workload``; an empty list = acceptable."""
    manifest = manifest or Manifest()
    cell = manifest.workload(workload)
    lines = stdout.rstrip("\n").split("\n")
    if not lines or not lines[-1].strip():
        return ["standard output is empty"]
    try:
        result = json.loads(lines[-1], parse_constant=_refuse)
    except ValueError as e:
        return [f"the last line is not strict JSON: {e}: {lines[-1][:120]!r}"]
    if not isinstance(result, dict):
        return [f"the last line is a {type(result).__name__}, not an object"]
    bad = [f"missing key {k!r}" for k in KEYS if k not in result]
    if bad:
        return bad
    if not isinstance(result["correct"], bool):
        bad.append(f"correct is {result['correct']!r}, not true or false")
    for k in ("attempted", "failed"):
        if not (isinstance(result[k], int) and result[k] >= 0):
            bad.append(f"{k} is {result[k]!r}")
    if result["attempted"] == 0:
        bad.append("attempted 0 operations")

    group = "per_layer" if traced else "end_to_end"
    wanted = {m["name"]: m["unit"] for m in manifest.metrics(group, workload)}
    have = result["metrics"]
    if not isinstance(have, dict):
        return bad + [f"metrics is a {type(have).__name__}"]
    for name in sorted(set(wanted) - set(have)):
        bad.append(f"metric {name} is listed for the cell and not on the line")
    for name in sorted(set(have) - set(wanted)):
        bad.append(f"metric {name} is on the line and not listed for the cell")
    for name in sorted(set(have) & set(wanted)):
        entry = have[name]
        if not (isinstance(entry, dict) and set(entry) == {"value", "unit"}):
            bad.append(f"metric {name} is {entry!r}, not value and unit")
        elif not _number(entry["value"]):
            bad.append(f"metric {name} has value {entry['value']!r}")
        elif entry["unit"] != wanted[name]:
            bad.append(f"metric {name} has unit {entry['unit']!r}, the "
                       f"manifest says {wanted[name]!r}")

    device = result["device"]
    if not isinstance(device, dict):
        return bad + [f"device is a {type(device).__name__}"]
    bad += [f"device lacks {k!r}" for k in DEVICE_KEYS if k not in device]
    if device.get("count") != cell["chips"]:
        bad.append(f"device count {device.get('count')!r}, the cell has "
                   f"{cell['chips']} chip(s)")
    if not (_number(device.get("memory_peak_bytes", 0))
            and device.get("memory_peak_bytes", 0) >= 0):
        bad.append(f"memory_peak_bytes {device.get('memory_peak_bytes')!r}")
    if traced:
        busy, window = device.get("busy_s"), device.get("window_s")
        if not (_number(busy) and _number(window) and 0 < busy <= window):
            bad.append(f"traced: busy_s {busy!r} and window_s {window!r} "
                       f"are not 0 < busy_s <= window_s")
    return bad


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("stdout", help="file holding the run's standard "
                                       "output ('-' = read it from stdin)")
    args = parser.parse_args()
    text = sys.stdin.read() if args.stdout == "-" else open(args.stdout).read()
    bad = check(args.workload, text, bool(args.trace))
    tag = f"{args.workload} --trace {args.trace}"
    if bad:
        print(f"RESULT LINE REFUSED  {tag}")
        for b in bad:
            print(f"  - {b}")
        raise SystemExit(1)
    result = json.loads(text.rstrip("\n").split("\n")[-1])
    print(f"RESULT LINE OK  {tag}: correct={str(result['correct']).lower()} "
          f"attempted={result['attempted']} failed={result['failed']} "
          f"{len(result['metrics'])} metrics, device "
          f"{result['device']['count']} x {result['device']['kind']}")


if __name__ == "__main__":
    main()
