"""From a profiler trace (``.xplane.pb``) to intervals and seconds.

What a TPU trace holds (looked at by hand, PR 23): one plane per chip,
``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per program
run), ``XLA Ops`` (the core's own timeline: every HLO instruction as it
runs, nested — a ``while`` spans the ops of its body) and ``Async XLA
Ops`` (one event from each ``*-start`` to its ``*-done``: copies, slices
and asynchronous collectives).  An event's name is the instruction's HLO
text.  Host threads are lines of the plane ``/host:CPU``; a
``jax.profiler.TraceAnnotation`` is an event there under its own name, on
the same clock as the device's.

Busy time is the union of the intervals on ``XLA Ops``.  Time by kind of
op is *self* time: every instant goes to the innermost op running, so a
loop's body is not counted again for the loop.
"""
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench/"          # the benchmark's own spans (harness/spans.py)
OPS, ASYNC_OPS, MODULES = "XLA Ops", "Async XLA Ops", "XLA Modules"
_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


class DeviceTrace:
    def __init__(self, name, lines):
        self.name = name
        self.lines = lines              # line name -> [(start, end, text)]
        self._segments = None

    def events(self, *lines):
        return [e for l in lines for e in self.lines.get(l, [])]

    def segments(self):
        """``self_segments`` of the XLA Ops line, worked out once."""
        if self._segments is None:
            self._segments = self_segments(self.events(OPS))
        return self._segments

    def window(self):
        ops = self.lines.get(OPS, [])
        return (min(e[0] for e in ops), max(e[1] for e in ops)) if ops \
            else None


class Trace:
    def __init__(self, devices, host_spans):
        self.devices = devices          # [DeviceTrace], by plane name
        self.host_spans = host_spans    # name -> [(start, end)]


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path, span_prefix=SPAN_PREFIX):
    from jax.profiler import ProfileData
    return from_profile_data(ProfileData.from_file(path), span_prefix)


def from_profile_data(data, span_prefix=SPAN_PREFIX):
    devices, host = [], defaultdict(list)
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                lines[line.name] = [
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events]
            devices.append(DeviceTrace(plane.name, lines))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        host[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    devices.sort(key=lambda d: d.name)
    return Trace(devices, dict(host))


# ------------------------------------------------------------- intervals
def union(intervals):
    """Merged, sorted, disjoint [(start, end)]."""
    out = []
    for s, e in sorted((i[0], i[1]) for i in intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        elif e > s:
            out.append((s, e))
    return out


def total(merged):
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """The part of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(merged):
    return [(a[1], b[0]) for a, b in zip(merged, merged[1:])]


def self_segments(events):
    """Every instant of nested ``events`` [(start, end, text)] attributed
    to the innermost one running: disjoint [(start, end, text)]."""
    out, stack, cursor = [], [], 0

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1][1] <= t:
            _, end, text = stack.pop()
            if end > cursor:
                out.append((cursor, end, text))
                cursor = end

    for start, end, text in sorted(events, key=lambda e: (e[0], -e[1])):
        close_until(start)
        if stack:
            end = min(end, stack[-1][1])    # a child ends with its parent
            if start > cursor:
                out.append((cursor, start, stack[-1][2]))
        if end > start:
            stack.append((start, end, text))
            cursor = start
    close_until(float("inf"))
    return out


# ----------------------------------------------------------------- names
def short_name(text):
    """``%fusion.5 = bf16[..] fusion(...), kind=kLoop`` -> ``fusion.5
    (fusion)``; a custom call also names its target."""
    head, _, rest = text.partition(" = ")
    if not rest:
        return text[:80]
    op = _OPCODE.search(" " + rest)
    kind = op.group(1) if op else "?"
    target = _TARGET.search(rest)
    if target:
        kind += " " + target.group(1)
    return f"{head.lstrip('%')} ({kind})"


def top_device_ops(trace, n=10):
    """[(short name, seconds)]: self time on ``XLA Ops`` over the whole
    traced window, mean over devices, largest first.  (``Async XLA Ops``
    spans are waits in flight, not time taken: where one holds the core
    up it shows here as its ``*-done``.)"""
    acc = defaultdict(float)
    for dev in trace.devices:
        for s, e, text in dev.segments():
            acc[short_name(text)] += e - s
    k = max(len(trace.devices), 1)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k * 1e-9] for name, ns in ranked]


def idle_gaps(trace, n=5):
    """[(what the host was doing, seconds)] for the longest gaps on any
    device's ``XLA Ops`` line: the benchmark span that covers most of
    the gap, else ``no benchmark span``."""
    found = []
    for dev in trace.devices:
        for s, e in gaps(union(dev.events(OPS))):
            found.append((e - s, s, e, dev.name))
    out = []
    for dur, s, e, dev in sorted(found, reverse=True)[:n]:
        best, cover = "no benchmark span", 0
        for name, spans in trace.host_spans.items():
            c = total(union((max(s, a), min(e, b)) for a, b in spans
                            if a < e and b > s))
            if c > cover:
                best, cover = name, c
        out.append([f"{best} ({dev})", dur * 1e-9])
    return out


def busy_and_window(trace):
    """(busy seconds averaged over devices, window seconds): the union of
    op intervals, and the span from the first op to the last on the
    device where that is longest."""
    busy, windows = [], []
    for dev in trace.devices:
        w = dev.window()
        if w:
            busy.append(total(union(dev.events(OPS))))
            windows.append(w[1] - w[0])
    if not busy:
        return 0.0, 0.0
    return sum(busy) / len(busy) * 1e-9, max(windows) * 1e-9
