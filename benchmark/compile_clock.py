"""Seconds JAX spent tracing, lowering and compiling, and how many backend
compiles its persistent cache served instead: JAX's own account, read off
``jax.monitoring`` (copied from ``chip_smoke.py``'s ``_CompileClock``)."""
import types

_DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_):
        if name in _DURATIONS:
            self.seconds += secs

    def _on_event(self, name, **_):
        if name == _CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self):
        """The counts as they stand: what the process compiled so far."""
        return types.SimpleNamespace(seconds=self.seconds,
                                     cache_hits=self.cache_hits)
