"""Spans of the served path, on the JAX profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` while a
profiler session runs, so the span lands in the session's trace beside the
device's operations, with ``args`` as its event stats. With no session it
is one shared null context, costing an ``is_enabled()`` call. Arguments are
ints or short strings already at hand: computing one must never wait on the
device.

There is no store of its own: the profiler's buffer holds the spans and
``jax.profiler.stop_trace`` writes them out.
"""
from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation

_OFF = contextlib.nullcontext()


def span(name: str, **args):
    """A trace annotation ``name`` with ``args`` while the profiler runs;
    otherwise the shared null context."""
    if TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **args)
    return _OFF
