"""Compile activity as JAX reports it, counted from the harness.

Like the bring-up check's ``CompileClock``: the listener counts backend
compiles (and their seconds) and persistent-cache hits, so a run can say
how much of its set-up was compilation and that nothing compiled inside
its window.
"""
from __future__ import annotations

import jax.monitoring as mon

COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        self.compiles, self.compile_s, self.cache_hits = 0, 0.0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits}

    def _duration(self, event, secs, **_):
        if event == COMPILE:
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1
