"""Unified telemetry: spans, counters, and Perfetto traces for the
search → engine → rules pipeline.

Zero-dependency observability layer threaded through every subsystem:
the search driver's round loop, the evaluator batch path, the
persistent evaluation store, the kernel wallclock measurement phases,
and the rules distillation stages all emit hierarchical spans and
typed counters/gauges into one process-wide :class:`Telemetry`
registry with pluggable exporters (Chrome trace-event / Perfetto JSON,
in-memory, plus a human :meth:`~repro.obs.telemetry.Telemetry.summary`
table). Once JAX is imported, an enabled registry's spans also land in
a running ``jax.profiler`` trace, on the clock of the device events.

The default registry is *disabled*: instrumentation points cost one
attribute check + a no-op call, and telemetry never feeds back into
what it observes — search results are byte-identical with or without
an exporter attached (locked by tests/test_obs.py). See README.md in
this package for the span taxonomy and how to open a trace in
Perfetto.
"""
from repro.obs.exporters import (Exporter, MemoryExporter,
                                 PerfettoExporter, load_trace)
from repro.obs.telemetry import (DISABLED, Counter, Gauge, Span,
                                 Telemetry, counter, current, enabled,
                                 event, gauge, set_current, span, use)

__all__ = [
    "Telemetry", "DISABLED", "Span", "Counter", "Gauge",
    "current", "set_current", "use", "span", "counter", "gauge",
    "event", "enabled",
    "Exporter", "MemoryExporter", "PerfettoExporter",
    "load_trace",
]
