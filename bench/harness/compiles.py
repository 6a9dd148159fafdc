"""Count the programs JAX compiles or loads from its persistent cache, so
that the window can prove it ran none."""
from __future__ import annotations

_EVENTS = ("/jax/core/compile/backend_compile_duration",
           "/jax/compilation_cache/cache_retrieval_time_sec")


class CompileCounter:
    def __init__(self):
        from jax import monitoring
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, duration: float = 0.0, **kw) -> None:
        if name in _EVENTS:
            self.count += 1
