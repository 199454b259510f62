"""Compiles, counted from JAX's own monitoring events."""
from __future__ import annotations


class CompileClock:
    """Seconds JAX spends getting executables (compiling, or reading them
    back from the persistent cache), how many it got, and the
    persistent-cache hits among them."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
