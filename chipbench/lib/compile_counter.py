"""XLA compiles counted from JAX's own monitoring events.

Copied from ``chip_smoke.py`` ``CompileCounter`` (PR 21)."""

from __future__ import annotations

from typing import Dict


class CompileCounter:
    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_requests = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += seconds

    def _on_event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1

    def snapshot(self) -> Dict[str, float]:
        return {
            "compiles": self.compiles,
            "compile_seconds": self.seconds,
            "persistent_cache_hits": self.cache_hits,
            "persistent_cache_requests": self.cache_requests,
        }

    def since(self, before: Dict[str, float]) -> Dict[str, float]:
        now = self.snapshot()
        return {k: now[k] - before[k] for k in now}
