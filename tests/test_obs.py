"""The in-program span recorder (``repro.obs``).

Pins its contract: it records only while the JAX profiler runs
(off, a span or a count records nothing); on, spans nest by thread with
parent ids, self time is a span's duration less its children's, counters
add, and every span also lands by name on the host plane of the
profiler's own trace.
"""
from __future__ import annotations

import glob
import os

import jax
import pytest

from repro import obs


@pytest.fixture(autouse=True)
def clean():
    obs.reset()
    yield
    obs.reset()


@pytest.fixture
def recording(monkeypatch):
    """The recorder as it is inside a profiler trace, without one."""
    monkeypatch.setattr(obs, "enabled", lambda: True)


def _rec(i, parent, name, start, end, **attrs):
    return obs.SpanRecord(i, parent, name, start, end, attrs)


def test_self_time_is_duration_less_children():
    snap = obs.Snapshot.of([
        _rec(1, 0, "a", 0, 100),
        _rec(2, 1, "b", 10, 30),
        _rec(3, 2, "c", 12, 20),
        _rec(4, 1, "b", 40, 90),
        _rec(5, 0, "a", 200, 210),
    ], {"n": 3})
    assert snap.count == {"a": 2, "b": 2, "c": 1}
    assert snap.total_s["a"] == pytest.approx(110e-9)
    assert snap.self_s["a"] == pytest.approx((100 - 20 - 50 + 10) * 1e-9)
    assert snap.total_s["b"] == pytest.approx(70e-9)
    assert snap.self_s["b"] == pytest.approx(62e-9)
    assert snap.self_s["c"] == pytest.approx(8e-9)
    assert snap.counters == {"n": 3}


def test_off_records_nothing():
    assert not obs.enabled()
    with obs.span("outer", request=1):
        with obs.span("inner"):
            obs.count("rows", 4)
    snap = obs.snapshot()
    assert snap.spans == () and snap.counters == {} and snap.count == {}


def test_spans_nest_with_parent_ids(recording):
    with obs.span("outer", request=7):
        with obs.span("inner"):
            obs.count("rows", 4)
        with obs.span("inner"):
            obs.count("rows")
    with obs.span("outer", request=8):
        pass
    snap = obs.snapshot()
    outer1, outer2 = [s for s in snap.spans if s.name == "outer"]
    inner = [s for s in snap.spans if s.name == "inner"]
    assert outer1.parent == 0 and outer2.parent == 0
    assert [s.parent for s in inner] == [outer1.id, outer1.id]
    assert outer1.attrs == {"request": 7} and outer2.attrs == {"request": 8}
    assert len({s.id for s in snap.spans}) == 4
    for s in inner:
        assert outer1.start_ns <= s.start_ns <= s.end_ns <= outer1.end_ns
    assert snap.counters == {"rows": 5}
    covered = sum(s.end_ns - s.start_ns for s in inner) * 1e-9
    assert snap.self_s["outer"] == pytest.approx(
        snap.total_s["outer"] - covered, abs=1e-12
    )


def test_span_records_and_unwinds_on_error(recording):
    with pytest.raises(ValueError):
        with obs.span("outer"):
            with obs.span("inner"):
                raise ValueError("boom")
    with obs.span("after"):
        pass
    snap = obs.snapshot()
    assert snap.count == {"inner": 1, "outer": 1, "after": 1}
    assert [s.parent for s in snap.spans if s.name == "after"] == [0]


def test_reset_clears(recording):
    with obs.span("a"):
        obs.count("n")
    obs.reset()
    snap = obs.snapshot()
    assert snap.spans == () and snap.counters == {}


def test_records_under_the_profiler_and_into_its_trace(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        assert obs.enabled()
        with obs.span("test.outer", request=3):
            with obs.span("test.inner"):
                jax.numpy.ones(4).block_until_ready()
            obs.count("test.rows", 2)
    assert not obs.enabled()
    with obs.span("test.after"):
        pass
    snap = obs.snapshot()
    assert snap.count == {"test.inner": 1, "test.outer": 1}
    assert snap.counters == {"test.rows": 2}

    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    host = {
        e.name: e
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
        if e.name.startswith("test.")
    }
    assert set(host) == {"test.outer", "test.inner"}
    outer, inner = host["test.outer"], host["test.inner"]
    assert outer.start_ns <= inner.start_ns
    assert (inner.start_ns + inner.duration_ns
            <= outer.start_ns + outer.duration_ns)
    assert dict(outer.stats)["request"] == 3
