"""Compile time and persistent-cache hits, from JAX's own monitoring events.

``take()`` returns what accrued since the last call, so the harness can
count set-up compiles and refuse a window in which anything compiled or was
loaded from the cache.
"""
from __future__ import annotations

import jax

_TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    def __init__(self):
        self._reset()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _reset(self):
        self.trace_s = self.backend_s = 0.0
        self.backend_compiles = self.cache_hits = 0

    def _duration(self, event, secs, **_):
        if event in _TRACE_EVENTS:
            self.trace_s += secs
        elif event == _BACKEND_EVENT:
            self.backend_s += secs
            self.backend_compiles += 1

    def _event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def take(self) -> dict:
        out = {"compile_s": self.trace_s + self.backend_s,
               "backend_compile_s": self.backend_s,
               "backend_compiles": self.backend_compiles,
               "persistent_cache_hits": self.cache_hits}
        self._reset()
        return out
