"""Profiling: event tracing, cross-process merge, Chrome-trace export, XPlane hooks.

Parity: reference ``include/profiling/`` — ``Event{type, start, end, name, source}``
(event.hpp:11,30), thread-safe ``Profiler`` accumulator with cross-process merge that
re-bases timestamps (profiler.hpp:52-63), process-global ``GlobalProfiler``
(profiler.hpp:132). Rendered by visualizers/visualize_profiler.py as a Gantt chart; here
the export is standard Chrome trace JSON (chrome://tracing / Perfetto) instead.

TPU-first addition: ``span`` writes a host span into the JAX profiler's own trace
(``jax.profiler.TraceAnnotation``), so that under ``jax.profiler.start_trace`` the
program's phases sit on one clock with the per-op HLO timing of the device.
"""
from .profiler import (
    Event,
    EventType,
    GlobalProfiler,
    Profiler,
    profiled,
    span,
)

__all__ = [
    "Event",
    "EventType",
    "Profiler",
    "GlobalProfiler",
    "profiled",
    "span",
]
