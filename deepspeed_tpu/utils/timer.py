"""Wall-clock and throughput timers (reference capability: deepspeed/utils/timer.py:43
``SynchronizedWallClockTimer`` and :198 ``ThroughputTimer``).

On TPU, synchronisation is ``jax.block_until_ready`` on the step outputs rather than
CUDA events; the engine passes its step outputs to :meth:`SynchronizedWallClockTimer.
Timer.stop` via the optional ``sync_obj``.
"""
import time
from collections import OrderedDict
from typing import Optional

from deepspeed_tpu.utils.logging import log_dist

FORWARD_MICRO_TIMER = "fwd_microstep"
FORWARD_GLOBAL_TIMER = "fwd"
BACKWARD_MICRO_TIMER = "bwd_microstep"
BACKWARD_GLOBAL_TIMER = "bwd"
STEP_MICRO_TIMER = "step_microstep"
STEP_GLOBAL_TIMER = "step"
TRAIN_BATCH_TIMER = "train_batch"


def _sync(obj=None):
    if obj is not None:
        import jax
        jax.block_until_ready(obj)


class SynchronizedWallClockTimer:
    """Named timers with optional device synchronisation.

    With a span tracer attached (``attach_tracer``), every timer window
    doubles as a Chrome-trace span named ``timer/<name>`` — the
    fwd/bwd/step phase timers become trace phases for free
    (deepspeed_tpu/telemetry/tracing.py; docs monitoring-profiling.md).
    """

    class Timer:
        def __init__(self, name: str, tracer=None):
            self.name_ = name
            self.started_ = False
            self.start_time = 0.0
            self.elapsed_ = 0.0
            self.count = 0
            self.tracer = tracer

        def start(self):
            if self.started_:
                return
            self.started_ = True
            if self.tracer is not None:
                self.tracer.begin(f"timer/{self.name_}", cat="timer")
            self.start_time = time.time()

        def stop(self, reset: bool = False, sync_obj=None):
            if not self.started_:
                return
            _sync(sync_obj)
            elapsed = time.time() - self.start_time
            if self.tracer is not None:
                self.tracer.end(f"timer/{self.name_}")
            if reset:
                self.elapsed_ = elapsed
            else:
                self.elapsed_ += elapsed
            self.count += 1
            self.started_ = False

        def reset(self):
            self.elapsed_ = 0.0
            self.count = 0
            self.started_ = False

        def elapsed(self, reset: bool = True) -> float:
            started = self.started_
            if started:
                self.stop()
            out = self.elapsed_
            if reset:
                self.reset()
            if started:
                self.start()
            return out

        def mean(self) -> float:
            return self.elapsed_ / max(self.count, 1)

    def __init__(self):
        self.timers = OrderedDict()
        self.tracer = None

    def attach_tracer(self, tracer):
        """Mirror every timer window as a trace span (telemetry layer);
        existing timers pick the tracer up too."""
        self.tracer = tracer
        for t in self.timers.values():
            t.tracer = tracer

    def __call__(self, name: str) -> "SynchronizedWallClockTimer.Timer":
        if name not in self.timers:
            self.timers[name] = self.Timer(name, tracer=self.tracer)
        return self.timers[name]

    def has(self, name: str) -> bool:
        return name in self.timers

    def log(self, names, normalizer: float = 1.0, reset: bool = True, ranks=None):
        assert normalizer > 0.0
        parts = []
        for name in names:
            if name in self.timers:
                ms = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                parts.append(f"{name}: {ms:.2f}")
        if parts:
            log_dist("time (ms) | " + " | ".join(parts), ranks=ranks or [0])


class ThroughputTimer:
    """samples/sec + tokens/sec aggregation across steps."""

    def __init__(self, batch_size: int, start_step: int = 2,
                 steps_per_output: Optional[int] = None, monitor_memory: bool = False,
                 sync_every_step: bool = False):
        self.batch_size = max(batch_size, 1)
        self.start_step = start_step
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        #: wait for the device at every stop, not at report boundaries alone
        self.sync_every_step = sync_every_step
        self.epoch_count = 0
        self.global_step_count = 0
        self.total_elapsed_time = 0.0
        self.started = False
        self.start_time = 0.0
        #: the window between two stops that waited for the device (the
        #: first opens at the first start): where it opened, and the
        #: seconds it took if the LAST stop closed one — None after a stop
        #: that waited for nothing, whose own duration is a dispatch time
        self._window_start = None
        self.synced_window_s = None

    def update_epoch_count(self):
        self.epoch_count += 1

    def start(self):
        self.started = True
        self.start_time = time.time()
        if self._window_start is None:
            self._window_start = self.start_time

    def stop(self, global_step: bool = True, report_speed: bool = True, sync_obj=None):
        if not self.started:
            return
        self.started = False
        will_report = (report_speed and self.steps_per_output and
                       (self.global_step_count + 1) % self.steps_per_output == 0)
        # Only fence the device at report boundaries: a per-step sync would
        # serialise the async dispatch pipeline.  Between reports the
        # wall-clock durations still sum correctly because the boundary sync
        # closes the window.
        synced = bool((will_report or self.sync_every_step)
                      and sync_obj is not None)
        if synced:
            _sync(sync_obj)
        now = time.time()
        duration = now - self.start_time
        self.synced_window_s = now - self._window_start if synced else None
        if synced:
            self._window_start = now
        if global_step:
            self.global_step_count += 1
        if self.global_step_count > self.start_step:
            self.total_elapsed_time += duration
            if will_report:
                log_dist(
                    f"step={self.global_step_count}, "
                    f"samples/sec={self.avg_samples_per_sec():.2f}", ranks=[0])

    def avg_samples_per_sec(self) -> float:
        counted = self.global_step_count - self.start_step
        if counted > 0 and self.total_elapsed_time > 0:
            return self.batch_size / (self.total_elapsed_time / counted)
        return -1.0
