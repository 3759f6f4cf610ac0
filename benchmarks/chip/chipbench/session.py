"""The harness's host spans and its count of compilations."""
from __future__ import annotations

import contextlib
import threading

SPAN_PREFIX = "bench."
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's trace (free when no trace runs):
    the trace reduction labels device idle gaps by these."""
    import jax
    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        yield


class CompileCounter:
    """Counts the programs JAX lowers while `counting` is on: every new
    executable, a persistent-cache hit included, is lowered first."""

    def __init__(self):
        self.counting = False
        self.count = 0
        self._lock = threading.Lock()
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event == _LOWER_EVENT and self.counting:
            with self._lock:
                self.count += 1
