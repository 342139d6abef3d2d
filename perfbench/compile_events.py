"""The benchmark's own count of XLA compiles and persistent-cache reads, from
``jax.monitoring``. Inside the measured window there should be none."""

from __future__ import annotations

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileEvents:
    """Counters a run reads before and after a phase. jax keeps listeners for
    the life of the process, so a run makes one of these."""

    def __init__(self):
        import jax.monitoring

        self.counts = {"backend_compiles": 0, "cache_reads": 0, "cache_hits": 0, "cache_misses": 0,
                       "backend_compile_s": 0.0, "cache_read_s": 0.0}
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_):
        if event == CACHE_HIT:
            self.counts["cache_hits"] += 1
        elif event == CACHE_MISS:
            self.counts["cache_misses"] += 1

    def _on_duration(self, event: str, seconds: float, **_):
        if event == BACKEND_COMPILE:
            self.counts["backend_compiles"] += 1
            self.counts["backend_compile_s"] += seconds
        elif event == CACHE_READ:
            self.counts["cache_reads"] += 1
            self.counts["cache_read_s"] += seconds

    def snapshot(self) -> dict:
        return dict(self.counts)

    def since(self, before: dict) -> dict:
        return {k: self.counts[k] - before[k] for k in self.counts}
