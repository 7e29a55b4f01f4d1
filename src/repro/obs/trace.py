"""Profiler trace annotations for the MCA hot paths.

Thin wrappers over ``jax.profiler`` so call sites name their spans in
one place.

Annotations name trace-time work.  Under ``jax.jit`` the Python body runs
once per compilation, so a span around jitted code brackets *dispatch*,
not per-call device time — put spans around the blocking call sites
(e.g. ``block_until_ready`` loops, prefill/decode steps) when you want
wall-clock, and rely on ``annotate_function`` to label compiled regions
in the profiler timeline.
"""
from __future__ import annotations

from typing import Callable

from jax.profiler import TraceAnnotation, annotate_function


def trace(name: str):
    """Context manager emitting a named profiler span."""
    return TraceAnnotation(name)


def annotate(name: str) -> Callable:
    """Decorator labelling a function's compiled region in profiler output."""
    def deco(fn: Callable) -> Callable:
        return annotate_function(fn, name=name)
    return deco
