"""The reduction from a trace to seconds: interval arithmetic on hand-made
events with known answers, then the same readers on a small trace recorded
on the chip in this PR (data/zero3x4_toy.xplane.pb.gz: four v5e chips, a
2-layer d_model-256 ZeRO-3 model at S 256, 4 steps, the flash kernel and
every collective of the real cell in it), checked against a brute-force
count on a 1 us raster."""
import gzip
import importlib
import os
import re

import pytest

from harness import trace as tr
from harness.manifest import Manifest

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "zero3x4_toy.xplane.pb.gz")


def reader(name):
    return importlib.import_module("layer_metrics.readers." + name)


def spec(metric):
    return Manifest().layer_metric(metric)


def hlo(name, op, extra=""):
    return f"%{name} = bf16[8]{{0}} {op}(bf16[8]{{0}} %x){extra}"


KERNEL = ', custom_call_target="tpu_custom_call"'
# device_op_time's rules over the text of an op, which no metric file holds
# since "any Mosaic call" stopped standing for the flash kernel: every
# Mosaic call, and every op that is neither one nor a collective
MOSAIC = {"mode": "self", "include": KERNEL.lstrip(", ")}


def neither_mosaic_nor_collective():
    return {"mode": "self", "exclude": MOSAIC["include"] + "|" + spec(
        "step.forward_ms_per_step")["params"]["exclude"]}


def op_ms(ctx, params):
    return reader("device_op_time").read(ctx, params)


def synthetic():
    """One device, 2 'steps', times in ns:
    while 0..1000 { fusion 0..300, kernel 300..500, all-reduce 500..600
    (sync, nothing else runs: exposed), fusion 600..1000 }, an async
    all-gather 650..900 overlapped by that fusion, a gap 1000..1200 under a
    host span, then fusion 1200..1900, a wait for an asynchronous gather
    1900..1960 and a reduce-scatter fusion 1960..2000 (both exposed)."""
    ops = [(0, 1000, hlo("while.1", "while")),
           (0, 300, hlo("fusion.1", "fusion")),
           (300, 500, hlo("flash.1", "custom-call", KERNEL)),
           (500, 600, hlo("all-reduce.1", "all-reduce")),
           (600, 1000, hlo("fusion.2", "fusion")),
           (1200, 1900, hlo("fusion.3", "fusion")),
           (1900, 1960, hlo("async-collective-done.1", "fusion")),
           (1960, 2000, hlo("fusion.7", "fusion",
                            ", kind=kCustom, calls=%all-reduce-scatter.4"))]
    asyncs = [(650, 900, hlo("all-gather-start.1", "all-gather-start"))]
    dev = tr.DeviceTrace("/device:TPU:0", {tr.OPS: ops, tr.ASYNC_OPS: asyncs})
    return tr.Trace([dev], {"bench/input_wait": [(990, 1190)],
                            "bench/train_batch": [(0, 50), (1190, 1230)]})


def context(trace, steps):
    return {"trace": trace, "steps": steps}


def test_interval_arithmetic():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 9)]
    assert tr.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]) \
        == [(0, 2), (4, 8), (22, 29)]
    segments = tr.self_segments([(0, 10, "outer"), (2, 5, "a"), (5, 7, "b"),
                                 (20, 30, "alone")])
    assert segments == [(0, 2, "outer"), (2, 5, "a"), (5, 7, "b"),
                        (7, 10, "outer"), (20, 30, "alone")]


def test_known_answers_on_hand_made_events():
    ctx = context(synthetic(), steps=2)
    ms = lambda ns: ns * 1e-6 / 2
    value = lambda metric: reader(spec(metric)["reader"]).read(
        ctx, spec(metric)["params"])
    assert value("device.idle_pct") == pytest.approx(100 * 200 / 2000)
    assert value("device.longest_gap_ms") == pytest.approx(200e-6)
    assert op_ms(ctx, MOSAIC) == pytest.approx(ms(200))
    # self time: the while's own 0 ns, fusions 300 + 400 + 700
    assert op_ms(ctx, neither_mosaic_nor_collective()) \
        == pytest.approx(ms(1400))
    assert value("comm.collective_ms_per_step") == pytest.approx(ms(450))
    assert value("comm.exposed_ms_per_step") == pytest.approx(ms(200))
    busy, window = tr.busy_and_window(ctx["trace"])
    assert (busy, window) == (pytest.approx(1800e-9), pytest.approx(2000e-9))
    gaps = tr.idle_gaps(ctx["trace"])
    assert gaps[0][0].startswith("bench/input_wait")
    assert gaps[0][1] == pytest.approx(200e-9)
    top = dict(tr.top_device_ops(ctx["trace"]))
    assert top["fusion.3 (fusion)"] == pytest.approx(700e-9)
    assert top["flash.1 (custom-call tpu_custom_call)"] == pytest.approx(200e-9)
    assert "while.1 (while)" not in top or top["while.1 (while)"] == 0


def test_a_reader_with_nothing_to_read_returns_nothing():
    empty = tr.Trace([tr.DeviceTrace("/device:TPU:0", {})], {})
    for metric in ("device.idle_pct", "comm.collective_ms_per_step",
                   "comm.exposed_ms_per_step"):
        s = spec(metric)
        assert reader(s["reader"]).read(context(empty, 1), s["params"]) is None
    assert op_ms(context(empty, 1), MOSAIC) is None


def _raster(intervals, t0, t1):
    """Microseconds of [t0, t1) covered, by marking a 1 us raster."""
    n = int((t1 - t0) // 1000) + 1
    mark = bytearray(n)
    for s, e in intervals:
        for i in range(int((s - t0) // 1000), int((e - t0) // 1000)):
            mark[i] = 1
    return mark


def test_recorded_chip_trace(tmp_path):
    path = str(tmp_path / "recorded.xplane.pb")
    with gzip.open(RECORDED) as src, open(path, "wb") as dst:
        dst.write(src.read())
    trace = tr.load(path)
    assert len(trace.devices) == 4
    assert any(name.startswith("bench/") for name in trace.host_spans)
    ctx = context(trace, steps=4)
    value = lambda metric: reader(spec(metric)["reader"]).read(
        ctx, spec(metric)["params"])
    coll = re.compile(spec("comm.collective_ms_per_step")["params"]["include"])
    kern = re.compile(MOSAIC["include"])
    worst = {"idle": 0, "coll": 0, "exposed": 0, "kernel": 0}
    for dev in trace.devices:
        ops = dev.events(tr.OPS)
        t0, t1 = dev.window()
        busy = _raster([(s, e) for s, e, _ in ops], t0, t1)
        leaves = [e for e in ops if " while(" not in e[2]
                  and " conditional(" not in e[2] and " call(" not in e[2]]
        comm = _raster([(s, e) for s, e, t in dev.events(tr.OPS, tr.ASYNC_OPS)
                        if coll.search(t)], t0, t1)
        other = _raster([(s, e) for s, e, t in leaves if not coll.search(t)],
                        t0, t1)
        kernel = _raster([(s, e) for s, e, t in leaves if kern.search(t)],
                         t0, t1)
        worst["idle"] = max(worst["idle"], 100 * (1 - sum(busy) / len(busy)))
        worst["coll"] = max(worst["coll"], sum(comm))
        worst["exposed"] = max(worst["exposed"], sum(
            c and not o for c, o in zip(comm, other)))
        worst["kernel"] = max(worst["kernel"], sum(kernel))
    us_per_step = lambda metric: value(metric) * 1e3
    # the raster rounds every edge to 1 us: allow 2% and a few us
    close = lambda a, b: abs(a - b) <= 0.02 * max(a, b) + 5
    assert abs(value("device.idle_pct") - worst["idle"]) < 1.0
    assert close(us_per_step("comm.collective_ms_per_step") * 4, worst["coll"])
    assert close(us_per_step("comm.exposed_ms_per_step") * 4,
                 worst["exposed"])
    assert close(op_ms(ctx, MOSAIC) * 1e3 * 4, worst["kernel"])
    assert worst["kernel"] > 0 and worst["coll"] > 0
    assert 0 <= worst["exposed"] <= worst["coll"]
