"""The reader of the sweep's encode-reuse counters, ``encode_reuse_share.sweep``.

The sweep encodes its stream once per distinct t_max among a call's
designs and counts the designs (``sim.encode_designs``) and the encodes
computed (``sim.encode_runs``); the reader turns the two into the share of
designs that reused another design's encode, and reads nothing for a
program that does not declare the counters.
"""
import sys

import pytest

import benchkit
from repro import obs

NAME = "encode_reuse_share.sweep"
OTHERS = ("sim.assign_mosaic", "sim.assign_reference", "sim.solver_designs")


def _reader():
    return benchkit.load_module(f"metrics/{NAME}", "bench_metric_" + NAME.replace(".", "_"))


@pytest.mark.parametrize("counters,want", [
    ({"sim.encode_designs": 56, "sim.encode_runs": 7}, 87.5),
    ({"sim.encode_designs": 64, "sim.encode_runs": 2}, 96.875),
    ({"sim.encode_designs": 256, "sim.encode_runs": 2}, 100 * 254 / 256),
    ({"sim.encode_designs": 6, "sim.encode_runs": 6}, 0.0),
    ({}, 0.0),
])
def test_encode_reuse_share_reads_the_counters(counters, want, monkeypatch):
    monkeypatch.setattr(obs, "snapshot", lambda: obs.Snapshot.of([], counters))
    assert _reader().read({"window_s": 1.0}) == pytest.approx(want)


def test_encode_reuse_share_reports_nothing_for_a_program_without_its_counters(monkeypatch):
    """A program that declares the assign and solver counters but not the
    encode counters (one that encodes once per design) reads nothing."""
    from repro.core import simulator

    counts = {"sim.encode_designs": 56, "sim.encode_runs": 7}
    monkeypatch.setattr(obs, "snapshot", lambda: obs.Snapshot.of([], counts))
    monkeypatch.setattr(simulator, "SWEEP_COUNTERS", OTHERS)
    assert _reader().read({"window_s": 1.0}) is None


def test_encode_reuse_share_reports_nothing_without_sweep_counters_or_obs(monkeypatch):
    from repro.core import simulator

    monkeypatch.setattr(obs, "snapshot", lambda: obs.Snapshot.of([], {}))
    monkeypatch.delattr(simulator, "SWEEP_COUNTERS")
    assert _reader().read({"window_s": 1.0}) is None
    import repro

    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert _reader().read({"window_s": 1.0}) is None
