"""Observability for the serve/query stack: metrics and spans.

One :class:`Observability` bundle travels with a scheduler/engine pair:

* ``metrics`` — a :class:`~repro.obs.metrics.MetricsRegistry` (always
  on by default; counter cost matches the old ``stats`` dict it
  replaced),
* ``tracer`` — a :class:`~repro.obs.trace.Tracer` (its span list is
  off by default; enable with ``Observability(tracing=True)`` or
  ``obs.tracer.enabled = True``).  Its scoped spans are always
  ``serve.*`` annotations of a ``jax.profiler`` trace, and
  ``kernels.ops`` adds ``ops.<op>.<phase>`` ones (:func:`scope`), so a
  profiler session shows them on the device ops' timeline.

The scheduler binds its clock seam into the tracer (``bind_clock``), so
``VirtualClock`` replays produce deterministic traces, and exports go
through :mod:`repro.obs.export` (Perfetto JSON + text metrics).
"""
from __future__ import annotations

from .export import (
    metrics_text,
    spans_to_trace_events,
    write_metrics,
    write_trace,
)
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NULL_INSTRUMENT,
    format_metric,
    merge_snapshots,
    quantile_from_snapshot,
)
from .trace import NULL_SPAN, Span, Tracer, scope


class Observability:
    """Metrics + tracer, bundled per serve component."""

    def __init__(self, metrics=None, tracer=None, *, tracing=False):
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer(enabled=tracing)

    def bind_clock(self, clock) -> None:
        """Point the tracer at a component's clock seam (first bind wins)."""
        if self.tracer.clock is None:
            self.tracer.clock = clock

    def snapshot(self) -> dict:
        return self.metrics.snapshot()


__all__ = [
    "Observability",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "NULL_INSTRUMENT",
    "format_metric",
    "merge_snapshots",
    "quantile_from_snapshot",
    "Tracer",
    "Span",
    "NULL_SPAN",
    "scope",
    "spans_to_trace_events",
    "write_trace",
    "metrics_text",
    "write_metrics",
]
