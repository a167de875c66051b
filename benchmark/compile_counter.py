"""Counts what XLA was asked to compile and what the persistent cache
answered, from JAX's own monitoring events (copied from
chip_smoke.CompileCounter)."""


class CompileCounter:
    def __init__(self):
        from jax import monitoring
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}
