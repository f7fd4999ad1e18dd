"""Counts backend compilations (persistent-cache loads included) and their
seconds from jax's own monitoring events, as chip_smoke.CompileLog does."""
import jax

EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == EVENT:
            self.count += 1
            self.seconds += duration
