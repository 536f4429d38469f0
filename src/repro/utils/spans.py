"""Program spans and executable counters, on the profiler's clock.

``with span("sample.stage"):`` marks one stage of the program's host code.
The span opens ``jax.profiler.TraceAnnotation(name)``, so while a profiler
runs it lands in the trace's host plane on the trace's own clock, beside the
device operations it caused: an idle gap on the device can be put down to
the span open on the host at that moment. Whether or not a profiler runs, a
span that closes appends a :class:`Span` record to a bounded in-memory ring,
which :func:`records` returns. ``span`` also decorates a function, whose
every call then runs inside the span.

Counters: :func:`count` adds to the innermost span open on the calling
thread (each thread keeps its own stack of open spans), and one
``jax.monitoring`` listener adds what JAX reports while a span is open:

- ``executables``: one per executable obtained, compiled or read from the
  persistent compilation cache (JAX reports ``backend_compile_duration``
  around either);
- ``backend_compile_s``: the seconds of those ``backend_compile_duration``
  events.

Events that arrive while no span is open on their thread are dropped. When
a span closes, its counters are added to its parent's, so a span's counters
cover the spans nested in it.

A span belongs in host code only. Inside a function that ``jit``, ``scan``
or ``vmap`` traces it would open once, while tracing, and never again.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Deque, Dict, Iterator, List, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation

RING_SIZE = 4096  # closed spans kept; a posterior job closes fewer than 10

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@dataclass
class Span:
    """One span: its name, its parent's name, its ``time.perf_counter_ns``
    bounds (``end_ns`` is 0 while it is open) and its counters."""

    name: str
    parent: Optional[str]
    start_ns: int
    end_ns: int = 0
    counters: Dict[str, float] = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


_RING: Deque[Span] = deque(maxlen=RING_SIZE)
_LOCAL = threading.local()
_LISTENING = threading.Lock()
_listener_registered = False


def _stack() -> List[Span]:
    stack = getattr(_LOCAL, "stack", None)
    if stack is None:
        stack = _LOCAL.stack = []
    return stack


def count(key: str, n: float = 1) -> None:
    """Add ``n`` to counter ``key`` of the innermost span open on this
    thread; a no-op when none is open."""
    stack = _stack()
    if stack:
        counters = stack[-1].counters
        counters[key] = counters.get(key, 0) + n


def _on_duration(event: str, duration: float, **_) -> None:
    if event == BACKEND_COMPILE:
        count("executables")
        count("backend_compile_s", duration)


def _listen() -> None:
    """Register the monitoring listener, once per process."""
    global _listener_registered
    if _listener_registered:
        return
    with _LISTENING:
        if not _listener_registered:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listener_registered = True


@contextmanager
def span(name: str) -> Iterator[Span]:
    """Open span ``name`` on this thread; yields its record, which is
    complete once the block exits."""
    _listen()
    stack = _stack()
    rec = Span(name, stack[-1].name if stack else None, time.perf_counter_ns())
    stack.append(rec)
    try:
        with TraceAnnotation(name):
            yield rec
    finally:
        rec.end_ns = time.perf_counter_ns()
        stack.pop()
        if stack:
            parent = stack[-1].counters
            for key, n in rec.counters.items():
                parent[key] = parent.get(key, 0) + n
        _RING.append(rec)


def records() -> Tuple[Span, ...]:
    """The closed spans still in the ring, oldest first."""
    return tuple(_RING)
