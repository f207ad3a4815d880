"""Counts XLA backend compiles by JAX's own monitoring events (copied
from the program's ``chip_smoke.CompileClock``), so that a run can show
that nothing compiles inside its measured window."""
from __future__ import annotations


class CompileClock:
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, monitoring):
        self.seconds = 0.0
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1
