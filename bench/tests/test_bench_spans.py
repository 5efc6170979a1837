"""The per-layer readers of the program's spans and counters, on a
fabricated snapshot of ``repro.obs``, and over a program without it."""
import sys

import pytest

import benchkit
from repro import obs


def _rec(i, parent, name, start_ms, end_ms):
    return obs.SpanRecord(i, parent, name, int(start_ms * 1e6), int(end_ms * 1e6), {})


# Two requests in a 100 ms window: the second fills a batch that re-fits.
SERVE = [
    _rec(1, 0, "serve.submit", 0, 6),
    _rec(2, 1, "serve.admit", 0, 1),
    _rec(3, 1, "serve.encode", 1, 5),
    _rec(4, 0, "serve.submit", 10, 30),
    _rec(5, 4, "serve.admit", 10, 11.5),
    _rec(6, 4, "serve.encode", 11.5, 14.5),
    _rec(7, 4, "serve.execute", 15, 29),
    _rec(8, 7, "serve.batch_xs", 15, 15.5),
    _rec(9, 7, "serve.assign", 15.5, 17),
    _rec(10, 7, "serve.complete", 17, 17.5),
    _rec(11, 7, "serve.refit", 18, 29),
    _rec(12, 11, "serve.refit_xs", 18, 18.5),
    _rec(13, 11, "serve.fit", 18.5, 25),
    _rec(14, 11, "serve.refit_check", 25, 26),
    _rec(15, 11, "serve.commit", 26, 28),
    _rec(16, 0, "serve.flush", 40, 44),
    _rec(17, 16, "serve.execute", 40, 44),
    _rec(18, 17, "serve.batch_xs", 40, 41.5),
    _rec(19, 17, "serve.assign", 41.5, 43),
    _rec(20, 17, "serve.complete", 43, 44),
]
SERVE_COUNTERS = {"serve.rows_live": 5, "serve.rows_slots": 32}

# One explore in a 50 ms window.
SWEEP = [
    _rec(1, 0, "dse.explore", 0, 40),
    _rec(2, 1, "dse.init", 1, 3),
    _rec(3, 1, "sim.many", 3, 37),
    _rec(4, 3, "sim.encode", 3, 5),
    _rec(5, 3, "sim.bucket", 5, 30),
    _rec(6, 5, "sim.pad", 5, 8),
    _rec(7, 5, "sim.fit", 8, 9),
    _rec(8, 5, "sim.lowering", 9, 25),
    _rec(9, 5, "sim.assign", 25, 28),
    _rec(10, 3, "sim.score", 30, 33),
    _rec(11, 3, "dse.record", 33, 34),
    _rec(12, 1, "dse.pareto", 37, 38),
]

CASES = [
    ("encode_ms_per_req.serve", "serve", (4 + 3) / 2),
    ("admit_ms_per_req.serve", "serve", ((1 + 1.5) + (1 + 1.5)) / 2),
    ("assemble_ms_per_batch.serve", "serve", (0.5 + 1.5) / 2),
    ("batch_fill.serve", "serve", 100 * 5 / 32),
    ("refit_commit_ms.serve", "serve", 1 + 2),
    ("host_prep.sweep", "sweep", 100 * (2 + 2 + 3) / 50),
    ("host_wait.sweep", "sweep", 100 * (1 + 16 + 3) / 50),
    ("host_score.sweep", "sweep", 100 * (3 + 1 + 1) / 50),
]
CTX = {"serve": {"requests": 2, "window_s": 0.1}, "sweep": {"window_s": 0.05}}


def _reader(name):
    return benchkit.load_module(f"metrics/{name}", "bench_metric_" + name.replace(".", "_"))


@pytest.mark.parametrize("name,kind,want", CASES)
def test_reader_on_a_fabricated_snapshot(name, kind, want, monkeypatch):
    snap = obs.Snapshot.of(SERVE, SERVE_COUNTERS) if kind == "serve" else obs.Snapshot.of(SWEEP)
    monkeypatch.setattr(obs, "snapshot", lambda: snap)
    assert _reader(name).read(CTX[kind]) == pytest.approx(want)


@pytest.mark.parametrize("name,kind,want", CASES)
def test_reader_reports_nothing_without_the_recorder(name, kind, want, monkeypatch):
    import repro

    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # import fails
    assert _reader(name).read(CTX[kind]) is None


@pytest.mark.parametrize("name,kind,want", CASES)
def test_reader_reads_zero_when_nothing_was_recorded(name, kind, want, monkeypatch):
    monkeypatch.setattr(obs, "snapshot", lambda: obs.Snapshot.of([]))
    assert _reader(name).read(CTX[kind]) == 0.0
