"""Counts JAX's backend compiles (copied from ``chip_smoke.CompileMeter``).

``requests`` are programs JAX asked the backend for, whether built or read
from the persistent cache; ``hits`` are those served from the cache; the
difference is what was really compiled."""

from __future__ import annotations


class CompileMeter:
    def __init__(self):
        import jax

        self.seconds = 0.0
        self.requests = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.requests += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def read(self) -> dict:
        return {
            "seconds": self.seconds,
            "requests": self.requests,
            "hits": self.hits,
            "misses": self.misses,
            "compiled": self.requests - self.hits,
        }

    @staticmethod
    def between(before: dict, after: dict) -> dict:
        return {k: after[k] - before[k] for k in after}
