"""Compile work in a window, from JAX's monitoring events.

``chip_smoke.CompileClock`` summed ``backend_compile_duration``; this one
also sums tracing (jaxpr) and lowering (jaxpr to MLIR) and counts the
persistent-cache hits among the backend compiles. A backend-compile event
covers a cache load as well as a compile, so ``compiles - cache_hits`` is
the number of programs XLA actually compiled.
"""

from __future__ import annotations

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Sums trace, lower and backend-compile seconds between resets."""

    def __init__(self):
        import jax

        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def reset(self):
        self.trace_s = self.lower_s = self.compile_s = 0.0
        self.compiles = self.cache_hits = 0

    def _on_duration(self, event, duration, **_):
        if event == TRACE:
            self.trace_s += duration
        elif event == LOWER:
            self.lower_s += duration
        elif event == COMPILE:
            self.compile_s += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"trace_s": self.trace_s, "lower_s": self.lower_s,
                "compile_s": self.compile_s, "compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "xla_compiles": self.compiles - self.cache_hits}
