"""Compiles seen by this process, from JAX's own monitoring events."""

from __future__ import annotations

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    """Backend compiles and persistent-cache hits, with compile seconds."""

    def __init__(self) -> None:
        import jax

        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            self.n += 1
            self.seconds += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT:
            self.cache_hits += 1
