"""Observability: flight recorder, link/node timelines, planner profiling,
spans on the profiler's clock.

The fleet simulator only allocates a :class:`FlightRecorder` when
``Scenario.trace`` is set, and the planning core only calls into a
:class:`PlannerProfile` when one is passed as ``plan(..., profile=)``.
The spans of :mod:`repro.obs.spans`, the one module of this package that
``repro.core`` imports, are always in the code and record only while a
``jax.profiler`` trace runs.  None of them touches an rng stream —
tracing is observation, not perturbation (the goldens pin this bitwise).

See ``src/README.md`` ("Observability") for the trace format, the
Perfetto how-to, and the profiling hook contract; ``repro.obs.report``
is the analysis CLI.
"""
from .profile import PlannerProfile
from .timeline import LinkUsageTracer
from .trace import (FlightRecorder, SCHEMA_VERSION, TRACE_KIND,
                    chrome_trace, finished_transfer_spans, json_sanitize)

__all__ = [
    "FlightRecorder", "LinkUsageTracer", "PlannerProfile",
    "SCHEMA_VERSION", "TRACE_KIND", "chrome_trace",
    "finished_transfer_spans", "json_sanitize",
]
