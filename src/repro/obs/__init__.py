"""Observability subsystem: metrics + tracing, with no dependency
beyond ``jax.profiler``'s annotations for the engine's step phases.

``Observability`` is the bundle the serving stack threads around — a
``MetricsRegistry`` (always) plus an optional ``TraceRecorder``.  The
engines wrap it in ``EngineObs`` (``obs/engine.py``) so the step loop
pays one attribute check, and a shared no-op context per step phase,
when instrumentation is off.

    from repro.obs import Observability
    obs = Observability.create(trace=True)          # wall-clock trace
    engine = PagedLLMEngine(model, params, obs=obs)
    ...
    print(obs.metrics.render())                     # Prometheus text
    obs.trace.export("trace.json")                  # open in Perfetto

The step-phase spans (``step.schedule`` ... ``step.account``, ``gc``)
land in a ``jax.profiler`` trace when one is recording.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.obs.engine import EngineObs
from repro.obs.metrics import (DEFAULT_BUCKETS, Counter, Gauge, Histogram,
                               MetricsRegistry, log_bucket_bounds,
                               summarize_latencies)
from repro.obs.trace import (TraceRecorder, span_report,
                             validate_chrome_trace)

__all__ = [
    "Observability", "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "TraceRecorder", "EngineObs", "DEFAULT_BUCKETS", "log_bucket_bounds",
    "summarize_latencies", "span_report", "validate_chrome_trace",
]


@dataclasses.dataclass
class Observability:
    """Metrics registry + optional trace recorder, passed as one unit.

    ``mode`` is the caller's clock: ``"wall"`` (seconds; results are
    stamped when they reached the host) or ``"sim"`` (a logical clock;
    results keep the step's ``now``).  It defaults to the trace's mode,
    else ``"wall"``."""

    metrics: MetricsRegistry
    trace: Optional[TraceRecorder] = None
    mode: Optional[str] = None

    def __post_init__(self):
        if self.mode is None:
            self.mode = self.trace.mode if self.trace else "wall"

    @classmethod
    def create(cls, trace: bool = False,
               trace_mode: str = "wall") -> "Observability":
        """``trace_mode="sim"`` (a discrete-event or step clock) zeroes
        measured wall durations so exports are deterministic, and keeps
        result stamps on the caller's ``now``, with or without a
        trace."""
        return cls(MetricsRegistry(),
                   TraceRecorder(mode=trace_mode) if trace else None,
                   trace_mode)
