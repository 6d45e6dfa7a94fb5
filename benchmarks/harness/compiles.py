"""Count the programs JAX compiles (or loads from its cache) while a window runs."""

from __future__ import annotations

_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts backend compile requests: each new executable this process
    needs, whether XLA built it or the persistent cache held it."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == _EVENT:
            self.count += 1
