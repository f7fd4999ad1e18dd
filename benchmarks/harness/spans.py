"""The benchmark's own spans, recorded around its calls into the program:
kept in memory on the host's clock and, while the profiler runs, written
into its trace as ``jax.profiler.TraceAnnotation`` events of the same
name, so that a device gap can be set beside what the host was doing."""
import contextlib
import time
from collections import defaultdict

import jax

from harness.trace import SPAN_PREFIX


class Spans:
    def __init__(self):
        self.durations = defaultdict(list)      # name -> [seconds]

    @contextlib.contextmanager
    def span(self, name):
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.durations[name].append(time.perf_counter() - t0)

    def get(self, name):
        return self.durations.get(name, [])

    def clear(self):
        self.durations.clear()
