"""JAX's own build work as program counters.

JAX reports through `jax.monitoring` how long it spent tracing a function
to a jaxpr, lowering the jaxpr to an MLIR module, compiling the module,
and reading an executable from the persistent compilation cache, and
whether a cache lookup hit. `watch()` registers one listener per process
(`telemetry.span()` calls it at the first span) that adds these into the
registry:

| counter | from the event |
|---|---|
| `jax.trace_s` | `/jax/core/compile/jaxpr_trace_duration` |
| `jax.lower_s` | `/jax/core/compile/jaxpr_to_mlir_module_duration` |
| `jax.compile_s` | `/jax/core/compile/backend_compile_duration`, less the cache read inside it |
| `jax.cache_load_s` | `/jax/compilation_cache/cache_retrieval_time_sec` |
| `jax.traces` | one a trace event |
| `jax.cache_hits`, `jax.cache_misses` | `/jax/compilation_cache/cache_hits`, `cache_misses` |

The phases nest: a jitted function called inside a trace is traced inside
it, an eager operation inside a trace builds a program of its own, and the
backend compile holds the cache read. JAX marks a phase's start with a
scalar event of the phase's name, so each phase is counted here without
the phases that ran inside it on the same thread: the seconds add up to
wall time and are never counted twice. `jax.traces` counts every trace,
nested ones too.

The listener fires only when JAX builds something, which a steady-state
step never does.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

# a span record's field -> the registry counter it is the delta of
COUNTERS: Dict[str, str] = {
    "trace_s": "jax.trace_s",
    "lower_s": "jax.lower_s",
    "compile_s": "jax.compile_s",
    "cache_load_s": "jax.cache_load_s",
    "traces": "jax.traces",
    "cache_hits": "jax.cache_hits",
    "cache_misses": "jax.cache_misses",
}

_PHASES = {TRACE_EVENT: "jax.trace_s", LOWER_EVENT: "jax.lower_s",
           COMPILE_EVENT: "jax.compile_s"}
_COUNTS = {CACHE_HIT_EVENT: "jax.cache_hits",
           CACHE_MISS_EVENT: "jax.cache_misses"}


class _Listener:
    """Per thread, a stack of the open phases, each with the seconds of
    the phases that ran inside it."""

    def __init__(self, registry: Callable) -> None:
        self._registry = registry
        self._local = threading.local()

    def _open(self) -> List[list]:
        stack = getattr(self._local, "open", None)
        if stack is None:
            stack = self._local.open = []
        return stack

    def started(self, event: str, value, **kw) -> None:
        if event in _PHASES:
            self._open().append([event, 0.0])

    def ended(self, event: str, secs: float, **kw) -> None:
        if event == CACHE_LOAD_EVENT:
            name = "jax.cache_load_s"
        elif event in _PHASES:
            name = _PHASES[event]
        else:
            return
        stack = self._open()
        inner = 0.0
        if event in _PHASES and stack and stack[-1][0] == event:
            inner = stack.pop()[1]
        if stack:
            stack[-1][1] += secs
        reg = self._registry()
        reg.counter(name).inc(max(secs - inner, 0.0))
        if event == TRACE_EVENT:
            reg.counter("jax.traces").inc()

    def counted(self, event: str, **kw) -> None:
        name = _COUNTS.get(event)
        if name is not None:
            self._registry().counter(name).inc()


_lock = threading.Lock()
_watching = False


def watch(registry: Callable) -> None:
    """Register the listener once per process; `registry()` returns the
    MetricsRegistry to count into."""
    global _watching
    if _watching:
        return
    with _lock:
        if _watching:
            return
        import jax.monitoring as monitoring
        listener = _Listener(registry)
        monitoring.register_scalar_listener(listener.started)
        monitoring.register_event_duration_secs_listener(listener.ended)
        monitoring.register_event_listener(listener.counted)
        _watching = True


def totals(registry) -> Dict[str, float]:
    """The counters now, by span-record field (0 before the first)."""
    out = {}
    for field, name in COUNTERS.items():
        inst = registry.get(name)
        out[field] = inst.value if inst is not None else 0
    return out
