"""Seconds JAX spent tracing, lowering and compiling (or reading its
persistent cache), and the cache's hits and misses, from ``jax.monitoring``
events. Read it before and after a phase to get the phase's share."""

from __future__ import annotations

from typing import Any, Dict

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")


class CompileClock:
    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_: Any) -> None:
        if event in EVENTS:
            self.seconds += secs

    def _event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def read(self) -> Dict[str, float]:
        return {"seconds": self.seconds, "hits": self.hits, "misses": self.misses}

    @staticmethod
    def since(before: Dict[str, float], after: Dict[str, float]) -> Dict[str, float]:
        return {k: after[k] - before[k] for k in after}
