"""Compilation accounting from ``jax.monitoring`` events.

Backend-compile seconds and persistent-cache hits and misses, summed over
the life of the process; :meth:`CompileStats.mark` and
:meth:`CompileStats.since` count what happened after a point, which is how
a run proves that nothing compiled inside its measured window.
"""
from __future__ import annotations

import jax

_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


class CompileStats:
    def __init__(self):
        self.seconds, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == _COMPILE:
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1

    def mark(self) -> tuple[float, int, int, int]:
        return self.seconds, self.compiles, self.hits, self.misses

    def since(self, mark) -> dict:
        s, c, h, m = mark
        return {"compile_s": self.seconds - s, "compiles": self.compiles - c,
                "cache_hits": self.hits - h, "cache_misses": self.misses - m}
