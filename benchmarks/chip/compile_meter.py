"""Compile and cache-load time from JAX's monitoring events.

`backend_compile` fires around every call into XLA's compiler, a
persistent-cache hit included (then it times the read); `cache_hits`
counts the hits, so compiles minus hits are real compiles.  Tracing and
lowering are timed apart: a program that builds a new `jax.jit` per call
pays them on every call, cache or no cache.
"""
from __future__ import annotations

import threading
import time

import jax

EVENTS = {
    "/jax/core/compile/backend_compile_duration": "backend_s",
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
}


class CompileMeter:
    """Totals since construction; `mark()` and `since(mark)` give the
    totals inside a span, such as the measured window."""

    def __init__(self):
        self._lock = threading.Lock()
        self.totals = {"backend_s": 0.0, "trace_s": 0.0, "lower_s": 0.0,
                       "compiles": 0, "cache_hits": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        key = EVENTS.get(event)
        if key is None:
            return
        with self._lock:
            self.totals[key] += duration_secs
            if key == "backend_s":
                self.totals["compiles"] += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.totals["cache_hits"] += 1

    def mark(self) -> dict:
        with self._lock:
            return {**self.totals, "t": time.perf_counter()}

    def since(self, mark: dict) -> dict:
        now = self.mark()
        out = {k: now[k] - mark[k] for k in self.totals}
        out["wall_s"] = now["t"] - mark["t"]
        out["real_compiles"] = out["compiles"] - out["cache_hits"]
        out["seconds"] = out["backend_s"] + out["trace_s"] + out["lower_s"]
        return out
