"""Counts JAX's own compile events (copied from chip_smoke.py's
CompileMeter, see PERF.md Open questions): backend compiles and
persistent-cache hits and misses, from `jax.monitoring`."""
import collections


class CompileMeter:
    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring

        self.count = collections.Counter()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        self.count[event] += 1

    def _on_duration(self, event, secs, **_):
        self.count[event] += 1

    def mark(self):
        return {"backend_compiles": self.count[self._BACKEND],
                "cache_hits": self.count[self._HIT],
                "cache_misses": self.count[self._MISS]}

    def since(self, mark):
        now = self.mark()
        return {k: now[k] - mark[k] for k in now}
