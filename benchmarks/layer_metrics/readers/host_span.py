"""Median host milliseconds of one of the benchmark's own spans over the
traced steps (host clock; the span is recorded in the benchmark's file
around the call into the layer).
params: {"span": "<name>"}"""
import statistics


def read(ctx, params):
    durations = ctx["spans"].get(params["span"])
    if not durations:
        return None
    return statistics.median(durations) * 1e3
