"""Host-side bookkeeping of a run: JAX's compile events and the percentile
the end-to-end tails use."""
from __future__ import annotations

import math


class CompileClock:
    """Sums JAX's own compile events: backend compile (a persistent-cache
    hit counts only its read), tracing and lowering, and cache hits."""

    def __init__(self):
        import jax
        self.totals = {"compiles": 0, "compile_s": 0.0, "trace_lower_s": 0.0,
                       "cache_hits": 0, "cache_requests": 0}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.totals["compiles"] += 1
            self.totals["compile_s"] += secs
        elif event in ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            self.totals["trace_lower_s"] += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.totals["cache_hits"] += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.totals["cache_requests"] += 1

    def snapshot(self) -> dict:
        """A copy of the running totals."""
        return dict(self.totals)


def percentile(values, p: float) -> float:
    """The ``p``-th percentile by the nearest rank: a value that was
    measured, never an interpolation between two."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    rank = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[rank - 1]
