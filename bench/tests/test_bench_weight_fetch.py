"""The reader of the sweep's weight-fetch counters, ``weight_fetch_share.sweep``.

The sweep fetches each envelope bucket's trained weight block once and
counts the designs whose weights a bucket returned
(``sim.weight_designs``) and the block fetches (``sim.weight_fetches``);
the reader turns the two into the share of designs whose weights shared a
fetch with another design, and reads nothing for a program that does not
declare the counters.
"""
import sys

import pytest

import benchkit
from repro import obs

NAME = "weight_fetch_share.sweep"
OLDER = ("sim.assign_mosaic", "sim.assign_reference", "sim.solver_designs",
         "sim.encode_designs", "sim.encode_runs")


def _reader():
    return benchkit.load_module(f"metrics/{NAME}", "bench_metric_" + NAME.replace(".", "_"))


@pytest.mark.parametrize("counters,want", [
    ({"sim.weight_designs": 64, "sim.weight_fetches": 1}, 98.4375),
    ({"sim.weight_designs": 56, "sim.weight_fetches": 7}, 87.5),
    ({"sim.weight_designs": 256, "sim.weight_fetches": 1}, 99.609375),
    ({"sim.weight_designs": 6, "sim.weight_fetches": 6}, 0.0),
    ({}, 0.0),
])
def test_weight_fetch_share_reads_the_counters(counters, want, monkeypatch):
    monkeypatch.setattr(obs, "snapshot", lambda: obs.Snapshot.of([], counters))
    assert _reader().read({"window_s": 1.0}) == pytest.approx(want)


def test_weight_fetch_share_reports_nothing_for_a_program_without_its_counters(monkeypatch):
    """A program that declares the older sweep counters but not the weight
    counters (one that fetches each design's weights on its own) reads
    nothing, whatever was counted."""
    from repro.core import simulator

    counts = {"sim.weight_designs": 64, "sim.weight_fetches": 1}
    monkeypatch.setattr(obs, "snapshot", lambda: obs.Snapshot.of([], counts))
    monkeypatch.setattr(simulator, "SWEEP_COUNTERS", OLDER)
    assert _reader().read({"window_s": 1.0}) is None


def test_weight_fetch_share_reports_nothing_without_obs(monkeypatch):
    import repro

    monkeypatch.setattr(obs, "snapshot", lambda: obs.Snapshot.of([], {}))
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert _reader().read({"window_s": 1.0}) is None
