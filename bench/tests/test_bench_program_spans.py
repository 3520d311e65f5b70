"""The readers of the program's spans on synthetic span records (the
window filter, the division by tasks, None with no root or with dropped
records), and the split of device idle time by program phase on a
synthetic trace."""
from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)

import common  # noqa: E402
import idle_phases  # noqa: E402
import xplane  # noqa: E402
from repro import telemetry  # noqa: E402

READERS = {
    "adapt.prep_ms_per_task": ("bucket", "probe.stack", "finetune.stack"),
    "adapt.select_ms_per_task": ("select",),
    "adapt.finetune_ms_per_task": ("finetune.run",),
    "adapt.fetch_ms_per_task": ("probe.fetch", "finetune.fetch"),
    "adapt.finish_ms_per_task": ("finish",),
}
# seconds of each phase in every synthetic call
PHASE_S = {"bucket": 0.001, "probe.stack": 0.002, "probe.run": 0.004,
           "probe.fetch": 0.008, "select": 0.016, "finetune.stack": 0.032,
           "finetune.run": 0.064, "finetune.fetch": 0.128,
           "finish": 0.256}


def reader(name):
    return common.load_module(os.path.join(BENCH, "metrics", name + ".py"))


def _span(rec, name, start, end, parent=None, call_id=None):
    s = telemetry.Span(rec, name, {})
    s.id = next(rec._ids)
    s.parent, s.call_id = parent, call_id if call_id else s.id
    s.start, s.end = start, end
    rec.records.append(s)
    return s


def synthetic_recorder(call_starts, capacity=telemetry.CAPACITY):
    """One ``adapt_many`` call at each start, its phases back to back."""
    rec = telemetry.Recorder(capacity)
    for t0 in call_starts:
        root = _span(rec, "adapt_many", t0, t0 + sum(PHASE_S.values()))
        t = t0
        for phase, s in PHASE_S.items():
            _span(rec, "adapt_many." + phase, t, t + s, root.id, root.id)
            t += s
    return rec


@pytest.fixture
def recorder(monkeypatch):
    def use(rec):
        monkeypatch.setattr(telemetry, "RECORDER", rec)
        return rec
    return use


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_sums_its_phases_of_the_windows_calls_per_task(name,
                                                               recorder):
    # calls at 1, 2 and 3 s; the window [1.5, 3.5] holds the last two
    recorder(synthetic_recorder([1.0, 2.0, 3.0]))
    got = reader(name).read({"window_t": (1.5, 3.5), "tasks": 64})
    want = 1e3 * 2 * sum(PHASE_S[p] for p in READERS[name]) / 64
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_silent_without_a_root_or_with_dropped_records(name,
                                                                  recorder):
    rec = recorder(synthetic_recorder([1.0, 2.0]))
    r = reader(name)
    assert r.read({"window_t": (5.0, 6.0), "tasks": 32}) is None
    assert r.read({"window_t": (0.5, 3.0), "tasks": 0}) is None
    assert r.read({"tasks": 32}) is None
    assert r.read({"window_t": (0.5, 3.0), "tasks": 32}) is not None
    rec.dropped = 1
    assert r.read({"window_t": (0.5, 3.0), "tasks": 32}) is None


def test_readers_are_silent_on_a_program_without_the_recorder(
        monkeypatch, recorder):
    import repro

    recorder(synthetic_recorder([1.0]))
    reading = {"window_t": (0, 10), "tasks": 32}
    assert all(reader(n).read(reading) is not None for n in READERS)
    monkeypatch.delattr(repro, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    for name in READERS:
        assert reader(name).read(reading) is None


def test_readers_skip_spans_of_other_roots(recorder):
    rec = recorder(synthetic_recorder([1.0]))
    other = _span(rec, "serve", 1.2, 1.3)
    _span(rec, "adapt_many.select", 1.21, 1.29, other.id, other.id)
    got = reader("adapt.select_ms_per_task").read(
        {"window_t": (0.0, 2.0), "tasks": 1})
    assert got == pytest.approx(1e3 * PHASE_S["select"])


DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, name, start_ms, dur_ms, line="XLA Ops"):
    return xplane.Event(plane, line, name, start_ms * 1e6, dur_ms * 1e6)


def synthetic_trace():
    """A 20 ms window with two calls (2-9 ms, 11-18 ms).  Each call: bucket
    (1 ms), probe.run (1 ms; device busy), select (2 ms), finetune.run
    (2 ms; device busy), finish (1 ms).  The device also runs one op in
    the first call's bucket (0.5 ms)."""
    out = [ev(HOST, "bench:window", 0, 20, "python")]
    for t in (2, 11):
        out += [ev(HOST, "adapt_many", t, 7, "python"),
                ev(HOST, "adapt_many.bucket", t, 1, "python"),
                ev(HOST, "adapt_many.probe.run", t + 1, 1, "python"),
                ev(HOST, "adapt_many.select", t + 2, 2, "python"),
                ev(HOST, "adapt_many.finetune.run", t + 4, 2, "python"),
                ev(HOST, "adapt_many.finish", t + 6, 1, "python"),
                ev(HOST, "bench:adapt_many", t, 7, "python"),
                ev(DEV, "%fusion.1 = f32[8] fusion(...)", t + 1, 1),
                ev(DEV, "%fusion.2 = f32[8] fusion(...)", t + 4, 2)]
    out.append(ev(DEV, "%pad.3 = f32[8] pad(...)", 2.25, 0.5))
    return out


def test_idle_phases_split_idle_time_by_innermost_span():
    r = idle_phases.idle_phases(synthetic_trace())
    assert r["window_s"] == pytest.approx(20e-3)
    busy = 2 * 3e-3 + 0.5e-3
    assert r["idle_s"] == pytest.approx(20e-3 - busy)
    # idle agrees with the trace reduction the benchmark already has
    red = xplane.reduce(synthetic_trace())
    assert r["idle_s"] == pytest.approx(red["window_s"] - red["busy_s"])
    by = r["idle_by_span_s"]
    assert by["adapt_many.bucket"] == pytest.approx(2 * 1e-3 - 0.5e-3)
    assert by["adapt_many.select"] == pytest.approx(2 * 2e-3)
    assert by["adapt_many.finish"] == pytest.approx(2 * 1e-3)
    # before the first call, between the calls, after the last
    assert by[idle_phases.BETWEEN] == pytest.approx((2 + 2 + 2) * 1e-3)
    assert "adapt_many.probe.run" not in by
    assert "adapt_many" not in by  # the children tile the root
    assert r["calls"] == 2
    assert r["root_self_share_max"] == pytest.approx(0.0, abs=1e-12)
    assert r["host_outside_runs_s"] == pytest.approx(20e-3 - 2 * 3e-3)


def test_idle_gaps_each_fall_under_a_named_span_or_between_calls():
    r = idle_phases.idle_phases(synthetic_trace(), top=10)
    names = {"adapt_many.bucket", "adapt_many.select", "adapt_many.finish",
             idle_phases.BETWEEN}
    labels = [label for label, _ in r["idle_gaps"]]
    assert len(labels) == 6
    assert all(label in names for label in labels)
    # the longest: the first call's finish (1 ms), the time between the
    # calls (2 ms) and the second call's bucket (1 ms), mostly "between"
    assert r["idle_gaps"][0] == [idle_phases.BETWEEN, pytest.approx(4e-3)]
    assert labels.count("adapt_many.select") == 2


def test_innermost_segments_tile_the_window():
    spans = [ev(HOST, "a", 1, 8, "python"), ev(HOST, "a.x", 2, 2, "python"),
             ev(HOST, "a.x.y", 2.5, 0.5, "python"),
             ev(HOST, "a.z", 5, 1, "python")]
    segs = idle_phases.innermost(spans, 0, 10e6)
    assert [(a / 1e6, b / 1e6, n) for a, b, n in segs] == [
        (0, 1, idle_phases.BETWEEN), (1, 2, "a"), (2, 2.5, "a.x"),
        (2.5, 3, "a.x.y"), (3, 4, "a.x"), (4, 5, "a"), (5, 6, "a.z"),
        (6, 9, "a"), (9, 10, idle_phases.BETWEEN)]


def test_root_self_share_counts_what_children_leave_uncovered():
    spans = [ev(HOST, "adapt_many", 0, 10, "python"),
             ev(HOST, "adapt_many.bucket", 0, 4, "python"),
             ev(HOST, "adapt_many.finish", 5, 4, "python")]
    assert idle_phases._root_self_share(
        spans, ("adapt_many",)) == pytest.approx(0.2)
