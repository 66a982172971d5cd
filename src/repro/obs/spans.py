"""Named spans on the profiler's own clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` called
``"repro." + name``; its numeric ``args`` become stats of the trace event.
While no profiler runs, entering one records nothing and costs about a
microsecond, so the spans stay in the code with no switch.  A trace taken
with ``jax.profiler.trace(dir)`` then puts the program's host steps on the
same clock as the device's operations (``src/README.md``, "Spans").

This module is the one part of ``repro.obs`` that ``repro.core`` imports.
JAX is imported on the first span, so importing the planners stays cheap;
without JAX a span is an empty context.
"""
from __future__ import annotations

import contextlib

_annotation = None


def span(name: str, **args):
    """A context that marks ``repro.<name>`` on the profiler's trace."""
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation as _annotation
        except ImportError:
            def _annotation(name, **args):
                return contextlib.nullcontext()
    return _annotation("repro." + name, **args)
