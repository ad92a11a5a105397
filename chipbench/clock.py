"""Compile seconds and counts, from ``jax.monitoring``.

Copied from ``chip_smoke.py``'s ``CompileClock``: the seconds JAX spends
lowering and compiling (persistent-cache loads included), the number of
backend compiles, and the persistent-cache hits and misses.  The harness
reads it at the window's edges to count what compiles inside the window.
"""

from __future__ import annotations


class CompileClock:
    """Running totals of compile work in this process."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, *args, **kwargs):
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.EVENTS[1]:
            self.compiles += 1

    def _event(self, event, *args, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}

    def since(self, mark: dict) -> dict:
        now = self.mark()
        return {k: now[k] - mark[k] for k in now}
