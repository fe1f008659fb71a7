"""Seconds XLA spent compiling or reading its cache, from jax.monitoring.
Copied from chip_smoke.py's CompileClock (PERF.md, Open questions)."""

from __future__ import annotations


class CompileClock:
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon

        self.durations: list[float] = []
        self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == self.BACKEND:
            self.durations.append(float(secs))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> int:
        return len(self.durations)

    def since(self, mark: int) -> dict:
        ds = self.durations[mark:]
        return {"programs": len(ds), "compile_s_total": sum(ds),
                "compile_s_largest": max(ds, default=0.0)}
