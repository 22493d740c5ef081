"""Counts what jax compiled or loaded from the persistent cache.

A copy of ``chip_smoke.CompileMeter`` (the yardstick may not move when
the smoke is edited): ``jax.monitoring`` events, so a window can say
how many programs it compiled — the answer has to be none.
"""

from __future__ import annotations


class CompileMeter:
    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _EVENTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        import jax.monitoring as mon
        self.n = dict(compiles=0, compile_s=0.0, cache_hits=0,
                      cache_misses=0)
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_kw):
        key = self._EVENTS.get(event)
        if key:
            self.n[key] += 1

    def _on_duration(self, event, duration, **_kw):
        if event == self._BACKEND:
            self.n["compiles"] += 1
            self.n["compile_s"] += duration

    def mark(self) -> dict:
        return dict(self.n)

    def since(self, before: dict) -> dict:
        return {k: self.n[k] - before[k] for k in self.n}
