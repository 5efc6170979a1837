"""The reader of the exploration's initial-draw counters, ``init_batch_share.sweep``.

An exploration draws its pending candidates' initial weights (and stream
keys) in one program per candidate shape and counts the candidates
(``dse.init_designs``) and the programs run (``dse.init_runs``); the
reader turns the two into the share of candidates drawn in a shared
program, and reads nothing for a program that does not declare the
counters.
"""
import importlib
import sys

import pytest

import benchkit
from repro import obs

NAME = "init_batch_share.sweep"


def _reader():
    return benchkit.load_module(f"metrics/{NAME}", "bench_metric_" + NAME.replace(".", "_"))


def _explore_module():
    return importlib.import_module("repro.dse.explore")


@pytest.mark.parametrize("counters,want", [
    ({"dse.init_designs": 64, "dse.init_runs": 1}, 98.4375),
    ({"dse.init_designs": 56, "dse.init_runs": 7}, 87.5),
    ({"dse.init_designs": 256, "dse.init_runs": 1}, 99.609375),
    ({"dse.init_designs": 6, "dse.init_runs": 6}, 0.0),
    ({}, 0.0),
])
def test_init_batch_share_reads_the_counters(counters, want, monkeypatch):
    monkeypatch.setattr(obs, "snapshot", lambda: obs.Snapshot.of([], counters))
    assert _reader().read({"window_s": 1.0}) == pytest.approx(want)


def test_init_batch_share_reports_nothing_for_a_program_without_its_counters(monkeypatch):
    """A program that draws each candidate on its own declares no
    exploration counters and reads nothing, whatever was counted."""
    counts = {"dse.init_designs": 64, "dse.init_runs": 1}
    monkeypatch.setattr(obs, "snapshot", lambda: obs.Snapshot.of([], counts))
    monkeypatch.delattr(_explore_module(), "DSE_COUNTERS")
    assert _reader().read({"window_s": 1.0}) is None


def test_init_batch_share_reports_nothing_without_obs(monkeypatch):
    import repro

    monkeypatch.setattr(obs, "snapshot", lambda: obs.Snapshot.of([], {}))
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert _reader().read({"window_s": 1.0}) is None
