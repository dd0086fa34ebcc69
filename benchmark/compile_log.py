"""Every executable JAX builds or loads, with the time it happened.

A copy of `chip_smoke.py CompileLog`: listeners on `jax.monitoring`'s
compile and compilation-cache events. The harness asks it how many
compiles fell inside the measured window (there must be none) and how
many of a run's programs came out of the persistent cache.
"""

from __future__ import annotations

import time
from typing import List, Tuple


class CompileLog:
    def __init__(self) -> None:
        import jax
        self.events: List[Tuple[float, str, float]] = []
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(),
                                kw.get("fun_name", "?"), float(secs)))

    def _ev(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def between(self, t0: float, t1: float):
        """Compiles that ended in (t0, t1] of `time.perf_counter()`."""
        return [e for e in self.events if t0 < e[0] <= t1]

    @property
    def total_s(self) -> float:
        return sum(e[2] for e in self.events)
