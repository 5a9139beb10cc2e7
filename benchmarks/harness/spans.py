"""The harness's own spans around its calls into the program.

Each span is recorded twice: on the host clock, kept in memory for the
host-side metrics, and as a ``jax.profiler.TraceAnnotation`` named
``bench.<name>``, which puts it on the profiler's clock beside the
device's operations while a trace is being taken (and costs a flag test
while none is).  Spans inside the program are a later change.
"""

from __future__ import annotations

import contextlib
import time
from typing import List, NamedTuple

import jax

PREFIX = "bench."


class Span(NamedTuple):
    name: str
    start_s: float   # time.perf_counter()
    end_s: float


class Spans:
    def __init__(self):
        self.events: List[Span] = []

    @contextlib.contextmanager
    def span(self, name: str):
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            yield
        self.events.append(Span(name, start, time.perf_counter()))

    def between(self, start_s: float, end_s: float, name: str) -> List[Span]:
        """Spans called ``name`` that lie whole inside the interval."""
        return [e for e in self.events if e.name == name
                and e.start_s >= start_s and e.end_s <= end_s]
