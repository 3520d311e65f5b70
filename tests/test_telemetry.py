"""The in-process recorder: span nesting and parent ids, self time, the
bounded buffer, the counter behind ``host_sync_count``, and each span also
being a profiler annotation on the trace's host plane."""
import glob
import os
import threading

import jax
import jax.numpy as jnp
import pytest

from repro import telemetry
from repro.core import adapt as adapt_mod


def test_spans_nest_with_parent_and_call_ids():
    rec = telemetry.Recorder()
    with rec.span("call", tasks=3) as root:
        with rec.span("call.a") as a:
            with rec.span("call.a.inner") as inner:
                pass
        with rec.span("call.b") as b:
            pass
    with rec.span("other") as other:
        pass
    assert [r.name for r in rec.records] == [
        "call.a.inner", "call.a", "call.b", "call", "other"]
    assert root.parent is None and root.call_id == root.id
    assert a.parent == root.id and b.parent == root.id
    assert inner.parent == a.id
    assert {a.call_id, b.call_id, inner.call_id} == {root.id}
    assert other.parent is None and other.call_id == other.id != root.id
    assert root.counts == {"tasks": 3}
    for kid in (a, b):
        assert root.start <= kid.start <= kid.end <= root.end
    assert a.end <= b.start


def test_parents_are_per_thread():
    rec = telemetry.Recorder()
    seen = {}

    def worker():
        with rec.span("worker") as w:
            seen["w"] = w

    with rec.span("main") as m:
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=30)
    assert not t.is_alive()
    assert seen["w"].parent is None and seen["w"].call_id != m.id


def test_self_time_is_duration_less_children_cover():
    rec = telemetry.Recorder()
    with rec.span("root") as root:
        with rec.span("root.a"):
            pass
        with rec.span("root.b"):
            pass
    kids = [r for r in rec.records if r.parent == root.id]
    own = telemetry.self_seconds(root, rec.records)
    assert own == pytest.approx(
        root.seconds - sum(k.seconds for k in kids), abs=1e-12)
    assert 0 <= own <= root.seconds
    # synthetic: children overlapping each other count once
    s = telemetry.Span(rec, "s", {})
    s.id, s.start, s.end = 10**9, 0.0, 10.0
    rows = []
    for a, b in ((1.0, 4.0), (3.0, 5.0), (7.0, 8.0)):
        k = telemetry.Span(rec, "s.k", {})
        k.parent, k.start, k.end = s.id, a, b
        rows.append(k)
    assert telemetry.self_seconds(s, rows) == pytest.approx(10.0 - 5.0)
    assert telemetry.covered([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3)


def test_buffer_is_bounded_and_counts_what_it_dropped():
    rec = telemetry.Recorder(capacity=4)
    for i in range(10):
        with rec.span(f"s{i}"):
            pass
    assert len(rec.records) == 4
    assert [r.name for r in rec.records] == ["s6", "s7", "s8", "s9"]
    assert rec.dropped == 6


def test_host_sync_count_reads_the_recorders_counter():
    adapt_mod.reset_host_sync_count()
    assert adapt_mod.host_sync_count() == 0
    assert telemetry.counter("host_syncs") == 0
    arrays0 = telemetry.counter("arrays_fetched")
    out = adapt_mod._fetch({"a": jnp.ones(3), "b": (jnp.zeros(2), 1.0)})
    assert adapt_mod.host_sync_count() == 1
    assert telemetry.counter("arrays_fetched") - arrays0 == 3
    assert float(out["a"].sum()) == 3.0
    adapt_mod._fetch_local((jnp.ones(2),))
    adapt_mod._fetch_scalar(jnp.float32(2.0))
    assert adapt_mod.host_sync_count() == telemetry.counter("host_syncs") == 3
    adapt_mod.reset_host_sync_count()
    assert telemetry.counter("host_syncs") == 0


def test_span_is_a_trace_annotation_on_the_host_plane(tmp_path):
    from jax.profiler import ProfileData

    name = "telemetry_test.span"
    x = jnp.arange(8.0)
    jax.block_until_ready(x * 2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span(name):
            jax.block_until_ready(x * 3)
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert paths
    pd = ProfileData.from_file(paths[-1])
    hits = [e for plane in pd.planes if not plane.name.startswith("/device:")
            for line in plane.lines for e in line.events if e.name == name]
    assert len(hits) == 1
    assert hits[0].duration_ns > 0
