"""Where-did-the-bytes-go report (ISSUE 14 satellite).

Renders the memory observatory's tier × owner table — live bytes,
high-watermarks, the device HBM stats, swap bandwidth vs the declared
``DS_NVME_GBPS`` floor, and the allocation-failure forensics tail —
from either a live ``/debug/memory`` endpoint or a post-mortem
bundle's ``memory.json``:

    python scripts/mem_report.py http://127.0.0.1:8080/debug/memory
    python scripts/mem_report.py postmortems/postmortem-step12/memory.json
    python scripts/mem_report.py memory.json --json   # re-emit raw JSON

Exit 0 on a rendered report, 2 on an unreadable/unparseable source.
"""
import argparse
import json
import os
import sys
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_payload(source: str) -> dict:
    """A /debug/memory URL or a memory.json path -> parsed payload."""
    if source.startswith(("http://", "https://")):
        with urllib.request.urlopen(source, timeout=10) as r:
            return json.loads(r.read())
    with open(source) as f:
        return json.load(f)


def fmt_bytes(n) -> str:
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return (f"{n:.0f} {unit}" if unit == "B"
                    else f"{n:.2f} {unit}")
        n /= 1024
    return f"{n:.2f} TiB"


def render(payload: dict) -> str:
    lines = ["# memory observatory report"]
    dev = payload.get("device_stats")
    if dev:
        frac = dev.get("used_fraction")
        lines.append(
            "device HBM: "
            f"{fmt_bytes(dev.get('used_bytes', dev.get('bytes_in_use', 0)))}"
            " in use + reserved"
            + (f" / {fmt_bytes(dev['bytes_limit'])} limit"
               if dev.get("bytes_limit") else "")
            + (f" ({frac:.1%})" if frac is not None else "")
            + (f", peak {fmt_bytes(dev['watermark_bytes'])}"
               if dev.get("watermark_bytes") else ""))
    else:
        lines.append("device HBM: no backend memory stats (CPU)")

    step = payload.get("step")
    if step:
        # the train step's own account of one chip (step_memory)
        held = sum(step["state"].values()) + step["batch"]
        lines.append(
            f"train step, one chip: state + batch {fmt_bytes(held)}"
            + (f", gradients {fmt_bytes(step['gradients'])}"
               if step["gradients"] is not None else "")
            + (f", temporaries {fmt_bytes(step['temporaries'])} live at "
               f"the program's peak (gradients among them), expected "
               f"peak {fmt_bytes(step['expected_peak'])}"
               if step["temporaries"] is not None else
               f", temporaries not known (this backend's peak does not "
               f"cover them; their allocations sum to "
               f"{fmt_bytes(step['program']['temp'])})")
            + (f", unaccounted {step['unaccounted'] / 2 ** 20:+.1f} MiB"
               if step["unaccounted"] is not None else ""))

    tiers = payload.get("tiers", {})
    if not tiers:
        lines.append("\n(no ledger entries — was the run armed with "
                     "DS_MEM_LEDGER / telemetry.memory?)")
    for tier, t in tiers.items():
        lines.append(f"\n## tier {tier} — {fmt_bytes(t['total_bytes'])} "
                     f"live, peak {fmt_bytes(t['watermark_bytes'])}")
        rows = [(o, r["bytes"], r["watermark_bytes"],
                 r.get("detail") or {})
                for o, r in sorted(t.get("owners", {}).items(),
                                   key=lambda kv: -kv[1]["bytes"])]
        if rows:
            w = max(len(o) for o, *_ in rows)
            lines.append(f"{'owner':<{w}}  {'bytes':>12}  "
                         f"{'watermark':>12}  detail")
            for o, b, peak, detail in rows:
                d = ", ".join(f"{k}={v}" for k, v in detail.items())
                lines.append(f"{o:<{w}}  {fmt_bytes(b):>12}  "
                             f"{fmt_bytes(peak):>12}  {d}")

    swap = payload.get("swap") or {}
    if swap.get("ops"):
        floor = swap.get("floor_gbps")
        lines.append("\n## swap I/O"
                     + (f" (declared floor {floor:g} GB/s)"
                        if floor else " (no DS_NVME_GBPS floor declared)"))
        for op, row in sorted(swap["ops"].items()):
            vs = (f", {row['vs_floor']:.2f}x of floor"
                  if "vs_floor" in row else "")
            lines.append(
                f"{op:>6}: {row['count']} ops, {fmt_bytes(row['bytes'])}, "
                f"mean {row['mean_gbps']:g} GB/s "
                f"(last {row['last_gbps']:g}){vs}")

    failures = payload.get("failures") or []
    lines.append(f"\n## allocation failures: "
                 f"{payload.get('alloc_failures', len(failures))}")
    for ev in failures[-8:]:
        owners = ", ".join(f"{k}={fmt_bytes(v)}"
                           for k, v in sorted(
                               (ev.get("owners") or {}).items(),
                               key=lambda kv: -kv[1])[:4])
        lines.append(f"- ts={ev.get('ts')} site={ev.get('site')} "
                     f"detail={ev.get('detail')} top owners: {owners}")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="mem_report",
        description="render the tier x owner byte table from "
                    "/debug/memory or a post-mortem memory.json")
    p.add_argument("source", help="URL (http://host:port/debug/memory) "
                                  "or path to memory.json")
    p.add_argument("--json", action="store_true",
                   help="emit the raw JSON payload instead of the table")
    args = p.parse_args(argv)
    try:
        payload = load_payload(args.source)
    except Exception as e:
        print(f"mem_report: cannot read {args.source!r}: {e}",
              file=sys.stderr)
        return 2
    if not isinstance(payload, dict) or "tiers" not in payload:
        print(f"mem_report: {args.source!r} is not a /debug/memory "
              "payload (no 'tiers' key)", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(payload, indent=2))
    else:
        print(render(payload))
    return 0


if __name__ == "__main__":
    sys.exit(main())
