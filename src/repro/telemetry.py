"""In-process telemetry: named host spans and counters.

One recorder per process, always on.  A span records
``(name, parent, call_id, start, end, counts)`` on ``time.perf_counter``'s
clock into a bounded buffer (the oldest records make way, and are counted
in ``dropped``); its parent is the span open around it on the same thread,
and ``call_id`` is the id of the outermost span of that nest.  Each span is
also a ``jax.profiler.TraceAnnotation`` of the same name, so under a
profiler its start and end sit on the device trace's clock.  With no
profiler running a span costs two clock reads, an inactive TraceMe and an
append.

Counters are named running totals; ``host_syncs`` counts blocking
device->host transfers (:func:`repro.core.adapt.host_sync_count`) and
``arrays_fetched`` the arrays they copied.

    with span("adapt_many", tasks=32) as root:
        with span("adapt_many.bucket"):
            ...
        root.counts["host_syncs"] = ...
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Deque, Dict, Iterable, List, Tuple

from jax.profiler import TraceAnnotation

CAPACITY = 1 << 16


class Span:
    """One span: a context manager while open, a record once closed."""

    __slots__ = ("name", "id", "parent", "call_id", "start", "end",
                 "counts", "_recorder", "_annotation")

    def __init__(self, recorder: "Recorder", name: str,
                 counts: Dict[str, Any]):
        self._recorder = recorder
        self.name = name
        self.counts = counts
        self.id = self.parent = self.call_id = None
        self.start = self.end = None

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "Span":
        rec = self._recorder
        stack = rec._stack()
        self.id = next(rec._ids)
        if stack:
            self.parent = stack[-1].id
            self.call_id = stack[-1].call_id
        else:
            self.call_id = self.id
        stack.append(self)
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._annotation.__exit__(*exc)
        self._annotation = None
        self._recorder._close(self)


class Recorder:
    """Closed spans in a bounded buffer, and named counters."""

    def __init__(self, capacity: int = CAPACITY):
        self.records: Deque[Span] = collections.deque(maxlen=capacity)
        self.dropped = 0
        self.counters: Dict[str, int] = collections.defaultdict(int)
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._lock = threading.Lock()

    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _close(self, span: Span) -> None:
        stack = self._stack()
        if stack and stack[-1] is span:
            stack.pop()
        with self._lock:
            if len(self.records) == self.records.maxlen:
                self.dropped += 1
            self.records.append(span)

    def span(self, name: str, **counts: Any) -> Span:
        return Span(self, name, counts)

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] += n

    def counter(self, name: str) -> int:
        return self.counters.get(name, 0)

    def reset_counter(self, name: str) -> None:
        with self._lock:
            self.counters[name] = 0


RECORDER = Recorder()


def span(name: str, **counts: Any) -> Span:
    """A span of the process's recorder; ``counts`` start its counts."""
    return RECORDER.span(name, **counts)


def count(name: str, n: int = 1) -> None:
    RECORDER.count(name, n)


def counter(name: str) -> int:
    return RECORDER.counter(name)


def reset_counter(name: str) -> None:
    RECORDER.reset_counter(name)


def covered(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, None
    for a, b in sorted(intervals):
        if reach is None or a > reach:
            total += b - a
            reach = b
        elif b > reach:
            total += b - reach
            reach = b
    return total


def self_seconds(span: Span, records: Iterable[Span]) -> float:
    """A span's duration less the part its children cover."""
    kids = [(r.start, r.end) for r in records if r.parent == span.id]
    return span.seconds - covered(kids)
