"""Where did my start go: the set-up account as a table.

Renders ``deepspeed_tpu.telemetry.tracing.setup_account()`` — the spans
the program opened at its own boundaries as a tree with self times, and
under each the traces, lowerings and compiles it caused, by program and
stage — from a JSON file of the account:

    python scripts/setup_report.py account.json
    python scripts/setup_report.py account.json --until-step 3

To get one: ``json.dump(tracing.setup_account(), f)`` after the first
steps (or from an ``atexit`` hook).  Read it in the process that made it:
a start launched through ``runpy`` lowered its step 1.4-2.9 x slower on
the chip's host than the same command run directly (PERF.md section 6,
PR 36).  Exit 0 on a rendered report, 2 on an unreadable source.
"""
import argparse
import json
import sys


def by_cause(rows):
    """{(program, stage, retrace, recompile): [events, self seconds,
    cache misses]} of the rows directly under one span."""
    table = {}
    for r in rows:
        key = (r["program"], r["stage"], r["retrace"], r["recompile"])
        acc = table.setdefault(key, [0, 0.0, 0])
        acc[0] += r.get("count", 1)
        acc[1] += r["self_s"]
        acc[2] += r["missed"]
    return table


def render(account, until_step=None):
    """Lines of the table.  ``until_step``: only what began before the
    ``train/step`` at that step count (a benchmark's first timed step)."""
    spans, rows = account["spans"], account["rows"]
    if until_step is not None:
        cut = min((s["start"] for s in spans if s["name"] == "train/step"
                   and s["step"] == until_step), default=float("inf"))
        spans = [s for s in spans if s["start"] < cut]
        rows = [r for r in rows if r["start"] < cut]
    children = {}
    for s in spans:
        children.setdefault(s["parent"], []).append(s)
    lines = [f"# set-up account: {len(spans)} spans, "
             f"{sum(r.get('count', 1) for r in rows)} jax events in "
             f"{len(rows)} rows, {account['steps']} steps begun",
             f"{'span / program stage':<52}{'seconds':>10}{'self':>10}"]

    def rows_under(span_id, depth):
        mine = [r for r in rows if r["span"] == span_id]
        for (program, stage, retrace, recompile), (n, self_s, missed) \
                in sorted(by_cause(mine).items(), key=lambda kv: -kv[1][1]):
            tags = "".join((" retrace" if retrace else "",
                            " RECOMPILE" if recompile else "",
                            f" missed={missed}" if missed else ""))
            label = f"{'  ' * depth}. {program} {stage} x{n}{tags}"
            lines.append(f"{label:<52}{'':>10}{self_s:>10.3f}")

    def walk(span, depth):
        label = f"{'  ' * depth}{span['name']} @step {span['step']}"
        lines.append(f"{label:<52}{span['end'] - span['start']:>10.3f}"
                     f"{span['self_s']:>10.3f}")
        rows_under(span["id"], depth + 1)
        for child in children.get(span["id"], []):
            walk(child, depth + 1)

    for top in children.get(None, []):
        walk(top, 0)
    if any(r["span"] is None for r in rows):
        lines.append("(outside every span of the program: the caller's own)")
        rows_under(None, 0)
    return lines


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("account", help="JSON of setup_account()")
    parser.add_argument("--until-step", type=int)
    args = parser.parse_args()
    try:
        with open(args.account) as f:
            account = json.load(f)
    except (OSError, ValueError) as e:
        print(f"setup_report: cannot read {args.account!r}: {e}",
              file=sys.stderr)
        raise SystemExit(2)
    print("\n".join(render(account, args.until_step)))


if __name__ == "__main__":
    main()
