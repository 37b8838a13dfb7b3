"""Profiler spans of the served path.

Each span is a ``jax.profiler.TraceAnnotation`` named ``harmony.<name>``: it
lands on the profiler's host plane, on the same clock as the device
planes, and nests by thread (``frontend.batch`` ⊃ ``engine`` ⊃
``executor`` ⊃ ``executor.*``). With no profiler running a span costs
about a microsecond; spans open once per batch (``index.kmeans`` once
per index build), never per request, cluster or chunk. ``docs/ARCHITECTURE.md`` lists them and how to capture
them.
"""

from __future__ import annotations

import jax

PREFIX = "harmony."


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A profiler span ``harmony.<name>`` carrying ``args`` as its stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
