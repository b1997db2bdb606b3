"""Counts JAX's backend compiles (a persistent-cache hit records its
retrieval as one)."""
from __future__ import annotations

import jax

EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == EVENT:
            self.count += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on)
