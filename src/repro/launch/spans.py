"""Spans and counters of one serving call, on the host's clock.

A :class:`Recorder` belongs to one call.  Each span it opens records its
name, its start and end (``time.perf_counter_ns``), the span it sits in
and a few attributes, and opens a ``jax.profiler.TraceAnnotation`` of the
same name with the same attributes: under a running profiler the span is
also a host event of the trace, on the clock the device's events are
aligned to; with none running the annotation does nothing.  Nothing here
waits for the device.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, Iterator, List

import jax


@dataclasses.dataclass
class Span:
    name: str
    start_ns: int
    end_ns: int
    parent: int                 # index of the enclosing span; -1 at the root
    call: int                   # the call id every span of a call shares
    attrs: Dict[str, object]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


def seconds(first: Span, last: Span) -> float:
    """From the start of ``first`` to the end of ``last``."""
    return (last.end_ns - first.start_ns) * 1e-9


class Recorder:
    """The spans and counters of call ``call``, in the order the spans
    were opened."""

    def __init__(self, call: int):
        self.call = call
        self.spans: List[Span] = []
        self.counters: Dict[str, int] = {}
        self._open: List[int] = []

    @contextlib.contextmanager
    def span(self, name: str, **attrs) -> Iterator[Span]:
        rec = Span(name, 0, 0, self._open[-1] if self._open else -1,
                   self.call, attrs)
        self._open.append(len(self.spans))
        self.spans.append(rec)
        with jax.profiler.TraceAnnotation(name, **attrs):
            rec.start_ns = time.perf_counter_ns()
            try:
                yield rec
            finally:
                rec.end_ns = time.perf_counter_ns()
                self._open.pop()

    def count(self, name: str, n: int) -> None:
        self.counters[name] = self.counters.get(name, 0) + int(n)
