"""Compile events from JAX's own monitoring hooks (copied from the
program's ``chip_smoke.CompileLog``): backend compiles, their seconds, and
persistent-cache hits."""
from __future__ import annotations

import collections

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Counts XLA compiles and persistent-cache hits from JAX's monitoring
    events. Listeners cannot be removed, so make one per process."""

    def __init__(self):
        import jax
        self.events = collections.Counter()
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        self.events[event] += 1

    def _duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.events["compiles"] += 1
            self.compile_s += secs

    def snapshot(self) -> dict:
        return {"compiles": self.events["compiles"],
                "cache_hits": self.events[CACHE_HIT_EVENT],
                "compile_s": self.compile_s}

    @staticmethod
    def delta(after: dict, before: dict) -> dict:
        return {k: after[k] - before[k] for k in after}
