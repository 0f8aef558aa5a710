"""Host spans of the sweep engine: in the profiler's trace and in memory.

``span(name, **attrs)`` marks one stretch of host work.  It enters a
``jax.profiler.TraceAnnotation``, so whenever a profile is being taken
(``jax.profiler.trace``, or the benchmark's ``--trace 1``) the span lands
on the profiler's host plane, on the same clock as the device planes.  It
also appends a ``Span`` record to a bounded in-memory buffer that
``recorded()`` returns, so the host time of recent calls can be read with
no profiler running.

Spans nest: a span opened while another is open on the same context is
its child and shares its call id, and a span opened with none open starts
a new call.  A thread started from ``contextvars.copy_context().run``
inherits the caller's open span, so its spans join the caller's call.

Spans are opened only around host code — never inside a function that can
be traced under ``jax.jit``, where they would time the tracing once and
never the execution.  Recording is always on: a span costs a few
microseconds and the sweep engine opens four per grid call.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import threading
import time
from typing import NamedTuple, Optional

import jax

MAXLEN = 4096


class Span(NamedTuple):
    """One finished span; ``start`` and ``end`` on ``time.perf_counter``."""

    name: str
    call: int                 # shared by every span of one top-level call
    parent: Optional[str]     # name of the enclosing span, None at the top
    thread: str
    start: float
    end: float
    attrs: dict


_recorded: collections.deque = collections.deque(maxlen=MAXLEN)
_open: contextvars.ContextVar = contextvars.ContextVar("repro_span",
                                                       default=None)
_calls = itertools.count(1)


def recorded() -> collections.deque:
    """The newest finished spans, oldest first (at most ``MAXLEN``)."""
    return _recorded


@contextlib.contextmanager
def span(name: str, **attrs):
    """Record the enclosed host work as span ``name``.

    Yields the span's attribute dict: entries added inside the block (a
    count known only once the work has run) are recorded with the rest.
    """
    outer = _open.get()
    call, parent = (next(_calls), None) if outer is None else outer
    token = _open.set((call, name))
    start = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name) as trace:
            yield attrs
            trace.set_metadata(**attrs)
    finally:
        end = time.perf_counter()
        _open.reset(token)
        _recorded.append(Span(name, call, parent,
                              threading.current_thread().name, start, end,
                              attrs))
