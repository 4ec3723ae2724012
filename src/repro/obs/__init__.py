"""Observability: the simulator's sim-clock tracing, metrics and
exporters, and the chip path's host-clock spans.

The fabric side (``trace``, ``metrics``, ``export``) is driven by the
fabric sim clock (``fabric.now``, seconds = ``step * STEP_S``), never a
wall clock, so its output is as deterministic as the fabric itself.
Tracing is off by default and every hook in the core is a single
``tracer is None`` check; ``MetricsRegistry`` is always on, but it *is*
the old ``fabric.stats`` dict (same object), so the always-on cost is
unchanged.

The chip side (``repro.obs.host``) times the serving engine and the
checkpoint path on the host clock, ``time.perf_counter``, into a bounded
ring that is always on; each span is also a profiler annotation, so a
profiler trace puts it on the device ops' clock. Its one
``jax.monitoring`` listener counts program loads.

See ``docs/observability.md`` for the event taxonomy, the span names,
exporter usage, and the overhead contracts.
"""
from repro.obs.export import (build_migration_report, chrome_trace,
                              render_timeline, write_chrome_trace)
from repro.obs.metrics import MetricsRegistry, WindowedHistogram
from repro.obs.trace import EventKind, TraceEvent, Tracer, record_phase

__all__ = [
    "EventKind", "TraceEvent", "Tracer", "record_phase",
    "MetricsRegistry", "WindowedHistogram",
    "chrome_trace", "write_chrome_trace",
    "build_migration_report", "render_timeline",
]
