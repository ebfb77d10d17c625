"""Observability: the program's host spans, on the profiler's clock, and the
trace-time report of the columns each Segment SpMM call computes.

:func:`span` names a region of host work ``segfold.<name>`` through
``jax.profiler.TraceAnnotation``: while a profiler trace is recording
(``jax.profiler.start_trace``) the span lands on the trace's host plane, on
the clock the device's ops are placed on; otherwise it costs well under a
microsecond.  There is no span store and no switch.

Counters live on the objects that own the work (``Engine.counters()``).  The
one thing only the executor knows is how wide the Segment SpMM kernel really
runs: :func:`spmm_columns` collects, while a program is traced, the N each
SpMM call was given and the N its kernel computes after the executor's
N-tile padding (:func:`repro.api.pick_bn`); :func:`repeated` marks calls
traced once that run several times (a scanned layer stack).
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Iterator, List, Tuple

import jax

PREFIX = "segfold."

#: (n_given, n_computed, runs) of each Segment SpMM call traced
SpmmCall = Tuple[int, int, int]

_calls: contextvars.ContextVar = contextvars.ContextVar("spmm_calls",
                                                        default=None)
_repeats: contextvars.ContextVar = contextvars.ContextVar("spmm_repeats",
                                                          default=1)


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A host span ``segfold.<name>`` with ``meta`` as its trace arguments:
    ``with span("engine.admit", rid=7): ...``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)


@contextlib.contextmanager
def spmm_columns() -> Iterator[List[SpmmCall]]:
    """Collect the Segment SpMM calls traced inside the block."""
    calls: List[SpmmCall] = []
    token = _calls.set(calls)
    try:
        yield calls
    finally:
        _calls.reset(token)


@contextlib.contextmanager
def repeated(n: int) -> Iterator[None]:
    """Calls traced inside the block run ``n`` times each (a ``lax.scan``
    over ``n`` layers traces its body once)."""
    token = _repeats.set(_repeats.get() * int(n))
    try:
        yield
    finally:
        _repeats.reset(token)


def report_spmm(n_given: int, n_computed: int) -> None:
    """Called by the executor as it traces a Segment SpMM call."""
    calls = _calls.get()
    if calls is not None:
        calls.append((int(n_given), int(n_computed), _repeats.get()))
