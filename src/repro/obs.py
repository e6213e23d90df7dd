"""Named host spans in the profiler's own trace (docs/serving.md,
"Tracing").

``span("engine.wait", chunk=0)`` opens a ``jax.profiler.TraceAnnotation``
named ``repro.engine.wait``.  Spans land in the same trace as the
device's operations, on the same clock, and only while a profiler
session runs (``jax.profiler.trace``/``start_trace``); with no session a
span records nothing and costs about a microsecond.  Attributes are
scalars.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

_NAMES: dict = {}                       # name -> "repro." + name, built once


def span(name: str, **attrs) -> TraceAnnotation:
    full = _NAMES.get(name)
    if full is None:
        full = _NAMES[name] = "repro." + name
    return TraceAnnotation(full, **attrs)
