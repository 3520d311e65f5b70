"""The program's own spans (``repro.telemetry``) of the calls whose root
span started inside the measured window.

A reader of a per-layer metric asks for the seconds of some phases per
task of the window.  A program without the recorder, a window with no
root span, or a recorder that dropped records gives None.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Optional


def ms_per_task(reading: Dict[str, Any], phases: Iterable[str],
                root: str = "adapt_many") -> Optional[float]:
    """Milliseconds of the spans named ``phases`` in the window's calls,
    over ``reading["tasks"]``."""
    try:
        from repro import telemetry
        rec = telemetry.RECORDER
        records, dropped = list(rec.records), rec.dropped
    except (ImportError, AttributeError):
        return None
    window, tasks = reading.get("window_t"), reading.get("tasks")
    if not window or not tasks or dropped:
        return None
    lo, hi = window
    calls = {r.id for r in records
             if r.name == root and r.parent is None and lo <= r.start <= hi}
    if not calls:
        return None
    names = set(phases)
    seconds = sum(r.end - r.start for r in records
                  if r.call_id in calls and r.name in names)
    return 1e3 * seconds / tasks
