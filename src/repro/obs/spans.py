"""Named spans on the profiler's own clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` called
``"repro." + name``; its numeric ``args`` become stats of the trace event,
and so do those given to ``set_metadata`` on the entered span.
While no profiler runs, entering one records nothing and costs about a
microsecond, so the spans stay in the code with no switch.  A trace taken
with ``jax.profiler.trace(dir)`` then puts the program's host steps on the
same clock as the device's operations (``src/README.md``, "Spans").

This module is the one part of ``repro.obs`` that ``repro.core`` imports.
JAX is imported on the first span, so importing the planners stays cheap;
without JAX a span is an empty context.  Threads may open spans at once:
the first spans of several threads may each look JAX up, and bind the
same class.
"""
from __future__ import annotations

_annotation = None


class _NoSpan:
    """A span where JAX is missing: it records nothing."""

    def __init__(self, name: str, **args):
        pass

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set_metadata(self, **args) -> None:
        pass


def span(name: str, **args):
    """A context that marks ``repro.<name>`` on the profiler's trace."""
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation as _annotation
        except ImportError:
            _annotation = _NoSpan
    return _annotation("repro." + name, **args)
