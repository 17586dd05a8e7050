"""Named host spans at the program's layer boundaries.

``span(name, **counts)`` marks a stretch of host work as
``repro:<name>`` in a ``jax.profiler`` trace, on the same clock as the
device's operations, with ``counts`` (plain ints or floats the caller
already holds) as the span's arguments.  The profiler session is the
only switch: with none active a span records nothing and costs about a
microsecond.

A span never reads a device value or waits for the device, so it adds
no synchronisation: the only syncs are the ones the program already
makes.
"""
from __future__ import annotations

import jax

PREFIX = "repro:"


def span(name: str, **counts) -> jax.profiler.TraceAnnotation:
    """A context manager for the span ``repro:<name>``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **counts)
