"""Named host spans on the fit and serve paths, for the profiler.

``span("serve", batch=3)`` is a ``jax.profiler.TraceAnnotation`` named
``hck.serve`` whose keyword ids the profiler stores as the event's stats.
It records only while a profiler trace is active; otherwise entering and
leaving it costs about a microsecond on the host.  Spans wrap host code
outside ``jit`` only, so every compiled program is the same with or
without them.  The benchmark's ``bench/progtrace.py`` reads them.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **ids) -> TraceAnnotation:
    """A host span ``hck.<name>`` carrying ``ids`` as its stats."""
    return TraceAnnotation("hck." + name, **ids)
