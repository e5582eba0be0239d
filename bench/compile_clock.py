"""Counts JAX's backend compiles and persistent-cache hits in a process.

Copied from the program's ``chip_smoke.CompileClock``: it listens to JAX's
monitoring events, so a compile anywhere in the process is seen.
"""

from __future__ import annotations

import jax


class CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit records
    only its retrieval) and counts compiles and cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
