"""Device idle time inside a traced window, by the program phase the host
was in.

The program's spans (``repro.telemetry``) are profiler annotations on the
trace's host plane, named ``<root>`` and ``<root>.<phase>``.  At each
instant of the window the innermost such span open names the phase, or
"between calls" where none is.  The device's idle intervals (the window
less the union of its operations, per device plane) are cut along those
phases, so each idle second goes to one phase.

    python bench/idle_phases.py <trace dir or .xplane.pb> [--root adapt_many]

prints one JSON object: the window's seconds, the device's idle seconds
(averaged over device planes, as ``xplane.reduce`` averages busy time),
idle seconds per phase, the longest idle gaps each under the phase that
holds most of it, the host's seconds outside the ``run`` phases, and the
largest share of a root span that none of its children covers.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from xplane import (  # noqa: E402
    Event, _clip, _merge, device_ops, find_xplane, load_events, window_of)

BETWEEN = "between calls"
RUN_PHASES = ("probe.run", "finetune.run")


def program_spans(events: Sequence[Event], roots: Sequence[str]
                  ) -> List[Event]:
    """Host-plane events named ``<root>`` or ``<root>.<phase>``."""
    return [e for e in events if not e.plane.startswith("/device:")
            and any(e.name == r or e.name.startswith(r + ".")
                    for r in roots)]


def innermost(spans: Sequence[Event], lo: float, hi: float
              ) -> List[Tuple[float, float, str]]:
    """Disjoint ``(start, end, label)`` segments tiling ``[lo, hi]``: the
    innermost span open (spans of one thread nest), else ``BETWEEN``."""
    segs: List[Tuple[float, float, str]] = []
    stack: List[Event] = []
    t = lo

    def emit(upto: float) -> None:
        nonlocal t
        upto = min(max(upto, lo), hi)
        if upto > t:
            segs.append((t, upto, stack[-1].name if stack else BETWEEN))
            t = upto

    for s in sorted(spans, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1].end_ns <= s.start_ns:
            emit(stack[-1].end_ns)
            stack.pop()
        emit(s.start_ns)
        stack.append(s)
    while stack:
        emit(stack[-1].end_ns)
        stack.pop()
    emit(hi)
    return segs


def idle_intervals(events: Sequence[Event], lo: float, hi: float
                   ) -> Dict[str, List[Tuple[float, float]]]:
    """Per device plane, the parts of ``[lo, hi]`` with no operation."""
    out = {}
    for plane, evs in sorted(device_ops(list(events)).items()):
        busy = _merge(_clip([(e.start_ns, e.end_ns) for e in evs], lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        out[plane] = [(edges[i], edges[i + 1])
                      for i in range(0, len(edges), 2)
                      if edges[i + 1] > edges[i]]
    return out


def _cut(gap: Tuple[float, float], segs, starts) -> Dict[str, float]:
    """Nanoseconds of ``gap`` under each label of ``segs``."""
    a, b = gap
    out: Dict[str, float] = collections.defaultdict(float)
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    while i < len(segs) and segs[i][0] < b:
        s0, s1, label = segs[i]
        ov = min(b, s1) - max(a, s0)
        if ov > 0:
            out[label] += ov
        i += 1
    return out


def idle_phases(events: Sequence[Event],
                window: Optional[Tuple[float, float]] = None,
                roots: Sequence[str] = ("adapt_many",), top: int = 10
                ) -> Optional[Dict[str, object]]:
    """Idle seconds per innermost program span in the window (None where
    the trace has no window or no device plane)."""
    events = list(events)
    window = window or window_of(events)
    if window is None:
        return None
    lo, hi = window
    idle = idle_intervals(events, lo, hi)
    if not idle:
        return None
    spans = program_spans(events, roots)
    segs = innermost(spans, lo, hi)
    starts = [s[0] for s in segs]
    by_span: Dict[str, float] = collections.defaultdict(float)
    gaps = []
    for plane_gaps in idle.values():
        for gap in plane_gaps:
            cut = _cut(gap, segs, starts)
            for label, ns in cut.items():
                by_span[label] += ns / len(idle)
            label = max(cut, key=cut.get) if cut else BETWEEN
            gaps.append([label, (gap[1] - gap[0]) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    run_ns = sum(min(e.end_ns, hi) - max(e.start_ns, lo) for e in spans
                 if any(e.name == f"{r}.{p}" for r in roots
                        for p in RUN_PHASES)
                 and e.end_ns > lo and e.start_ns < hi)
    in_window = [e for e in spans if lo <= e.start_ns <= hi]
    return {
        "window_s": (hi - lo) * 1e-9,
        "idle_s": sum(by_span.values()) * 1e-9,
        "idle_by_span_s": {k: v * 1e-9 for k, v in sorted(
            by_span.items(), key=lambda kv: -kv[1])},
        "idle_gaps": gaps[:top],
        "host_outside_runs_s": (hi - lo - run_ns) * 1e-9,
        "calls": sum(1 for e in in_window if e.name in roots),
        "root_self_share_max": _root_self_share(in_window, roots),
    }


def _root_self_share(spans: Sequence[Event], roots: Sequence[str]
                     ) -> Optional[float]:
    """Largest share of a root span's duration that its children leave
    uncovered (children: spans that start and end inside it)."""
    worst = None
    for r in spans:
        if r.name not in roots or r.dur_ns <= 0:
            continue
        kids = [(e.start_ns, e.end_ns) for e in spans if e is not r
                and r.start_ns <= e.start_ns and e.end_ns <= r.end_ns
                and e.name.startswith(r.name + ".")]
        cover = sum(b - a for a, b in _merge(kids))
        share = 1.0 - cover / r.dur_ns
        worst = share if worst is None else max(worst, share)
    return worst


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="a trace directory or an .xplane.pb")
    ap.add_argument("--root", action="append",
                    help="root span name (default adapt_many)")
    args = ap.parse_args(argv)
    path = (args.trace if args.trace.endswith(".xplane.pb")
            else find_xplane(args.trace))
    if not path:
        print(f"no .xplane.pb under {args.trace}", file=sys.stderr)
        return 2
    out = idle_phases(load_events(path), roots=args.root or ["adapt_many"])
    if out is None:
        print("the trace has no window or no device plane", file=sys.stderr)
        return 3
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
