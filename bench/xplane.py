"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

An event is ``(plane, line, name, start_ns, duration_ns)``.  Device planes
are named ``/device:<KIND>:<n>``; on each, the line of operations
(``XLA Ops`` where it exists) gives the intervals in which the device
worked.  The bench's host spans are events named ``bench:<span>`` on the
host plane, on the same clock.  From these the reduction gives:

- busy seconds: the union of operation intervals inside the window,
  averaged over the device planes;
- device time per operation name;
- the idle gaps inside the window, each labelled by the host span that
  overlapped it most (``none`` where no span was open).
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from common import SPAN_PREFIX

OPS_LINES = ("XLA Ops",)
# lines of a device plane that summarise rather than list operations
SUMMARY_LINES = ("XLA Modules", "Steps", "XLA TraceMe", "Framework Ops",
                 "Framework Name Scope", "Source code", "SparseCore")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load_events(path: str) -> List[Event]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def _merge(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def device_ops(events: List[Event]) -> Dict[str, List[Event]]:
    """Operation events per device plane (the ``XLA Ops`` line where a
    plane has one, else every line that is not a summary)."""
    by_plane: Dict[str, List[Event]] = collections.defaultdict(list)
    lines: Dict[str, set] = collections.defaultdict(set)
    for e in events:
        if e.plane.startswith("/device:"):
            lines[e.plane].add(e.line)
    for e in events:
        if not e.plane.startswith("/device:"):
            continue
        have_ops = any(ln in OPS_LINES for ln in lines[e.plane])
        if (e.line in OPS_LINES) if have_ops else (
                e.line not in SUMMARY_LINES):
            by_plane[e.plane].append(e)
    return dict(by_plane)


def host_spans(events: List[Event]) -> List[Event]:
    return [e for e in events if not e.plane.startswith("/device:")
            and e.name.startswith(SPAN_PREFIX)]


def window_of(events: List[Event], span: str = "window"
              ) -> Optional[Tuple[float, float]]:
    """The bench's ``window`` span on the trace clock."""
    for e in host_spans(events):
        if e.name == SPAN_PREFIX + span:
            return e.start_ns, e.end_ns
    return None


def short_name(name: str) -> str:
    """An HLO op event's instruction name (its text up to `` = ``)."""
    head = name.split(" = ", 1)[0].strip()
    return head.lstrip("%") if len(head) < len(name) else head[:80]


def module_name(name: str) -> str:
    """A module event's program name without its fingerprint."""
    return name.split("(", 1)[0] or "module"


def self_times(evs: List[Event]) -> List[Tuple[Event, float]]:
    """Each event with its own time, less the time of the events nested
    in it on the same line (a loop holds its body's operations)."""
    out: List[List] = []
    stack: List[List] = []
    for e in sorted(evs, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1][0].end_ns <= e.start_ns:
            stack.pop()
        rec = [e, e.dur_ns]
        if stack:
            stack[-1][1] -= e.dur_ns
        stack.append(rec)
        out.append(rec)
    return [(e, max(t, 0.0)) for e, t in out]


def reduce(events: List[Event], window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> Dict[str, object]:
    """Busy and idle time, device time per op, and the longest idle gaps.

    Returns ``{"window_s", "busy_s", "device_ops": [[name, s]...],
    "idle_gaps": [[label, s]...], "op_seconds": {name: s}, "planes": n}``;
    ``busy_s`` is None when no device plane holds an operation.  An op is
    named ``<program>/<instruction>`` and counted by its own time.
    """
    ops = device_ops(events)
    if window is None:
        window = window_of(events)
    if window is None:
        spans = [(e.start_ns, e.end_ns) for e in events]
        window = (min(a for a, _ in spans), max(b for _, b in spans))
    lo, hi = window
    modules = sorted((e for e in events if e.plane.startswith("/device:")
                      and e.line == "XLA Modules"),
                     key=lambda e: e.start_ns)
    starts = [m.start_ns for m in modules]
    busy_per_plane = []
    op_seconds: Dict[str, float] = collections.defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for plane, evs in sorted(ops.items()):
        busy = _merge(_clip([(e.start_ns, e.end_ns) for e in evs], lo, hi))
        busy_per_plane.append(sum(b - a for a, b in busy))
        for e, own in self_times(evs):
            if e.end_ns <= lo or e.start_ns >= hi:
                continue
            i = bisect.bisect_right(starts, e.start_ns) - 1
            mod = (module_name(modules[i].name)
                   if i >= 0 and modules[i].plane == plane
                   and modules[i].end_ns >= e.end_ns else "")
            key = f"{mod}/{short_name(e.name)}" if mod else short_name(e.name)
            op_seconds[key] += own / len(ops) * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    spans = [e for e in host_spans(events)
             if e.name != SPAN_PREFIX + "window"]
    labelled = []
    for a, b in gaps:
        best, label = 0.0, "none"
        for s in spans:
            ov = min(b, s.end_ns) - max(a, s.start_ns)
            if ov > best:
                best, label = ov, s.name[len(SPAN_PREFIX):]
        labelled.append([label, (b - a) * 1e-9])
    labelled.sort(key=lambda x: -x[1])
    ranked = sorted(op_seconds.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": (sum(busy_per_plane) / len(busy_per_plane) * 1e-9
                   if busy_per_plane else None),
        "device_ops": [[n, s] for n, s in ranked[:top]],
        "idle_gaps": labelled[:top],
        "op_seconds": dict(op_seconds),
        "planes": len(ops),
    }


def kernel_seconds(reduced: Dict[str, object], patterns: Iterable[str]
                   ) -> Optional[float]:
    """Device seconds of the ops whose name holds any of ``patterns``;
    None where the trace holds none."""
    total, seen = 0.0, False
    for name, s in reduced["op_seconds"].items():
        if any(p in name for p in patterns):
            total += s
            seen = True
    return total if seen else None
