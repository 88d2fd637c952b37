"""In-memory spans of the serving engine's layers.

A span is one stretch of host time spent in one layer of a step: its name,
its start and end on ``time.perf_counter``, the span open around it (its
parent) and attributes such as the request uid it served. While the log is
on, every span is also entered as a ``jax.profiler.TraceAnnotation`` of the
same name, so a profiler capture shows it beside the device's work without
another exporter.

The log starts off. A span site costs a test of ``on`` where the span opens
and a test of the id it left where it closes; nothing else runs until a
caller turns the log on::

    sid = spans.on and spans.open("decode.drain")
    ...
    if sid:
        spans.close(sid, tokens=n)

Finished spans go to a bounded buffer (the newest ``cap`` are kept) that the
caller empties with ``take``.
"""
from __future__ import annotations

import collections
import time
from typing import Dict, List, NamedTuple

import jax


class Span(NamedTuple):
    sid: int                  # > 0, unique within the log
    name: str
    t0: float                 # time.perf_counter seconds
    t1: float
    parent: int               # sid of the enclosing span; 0 at a root
    attrs: Dict[str, object]


class SpanLog:
    def __init__(self, cap: int = 1 << 16):
        self.on = False
        self._done: "collections.deque[Span]" = collections.deque(maxlen=cap)
        self._open: List[tuple] = []   # (sid, name, t0, parent, attrs, ann)
        self._next = 1

    def enable(self) -> None:
        self.on = True

    def open(self, name: str, **attrs) -> int:
        """Open a span inside the innermost open one; returns its id."""
        sid, self._next = self._next, self._next + 1
        parent = self._open[-1][0] if self._open else 0
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        self._open.append((sid, name, time.perf_counter(), parent, attrs,
                           ann))
        return sid

    def close(self, sid: int, **attrs) -> None:
        """Close span ``sid``, adding ``attrs``. Spans opened inside it and
        left open (an exception passed through them) close with it."""
        while self._open:
            top, name, t0, parent, own, ann = self._open.pop()
            t1 = time.perf_counter()
            ann.__exit__(None, None, None)
            if top == sid:
                own.update(attrs)
            self._done.append(Span(top, name, t0, t1, parent, own))
            if top == sid:
                return

    def take(self) -> List[Span]:
        """The finished spans, oldest first; the buffer is emptied."""
        out = list(self._done)
        self._done.clear()
        return out
