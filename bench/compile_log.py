"""Counts backend compiles and persistent-cache hits from JAX's
monitoring events (the same listener as the bring-up smoke's)."""
from __future__ import annotations


class CompileLog:
    def __init__(self, jax):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
