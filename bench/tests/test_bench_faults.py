"""A run whose timed path is broken reads ``correct`` false.

The harness's look for a chip is skipped (the host's CPU stands in, with
the fused paths on the Pallas interpreter); everything else is a tiny run
of each kind with one fault planted in the program underneath:

* a step that returns its state unchanged (the fit hands back its input
  weights);
* half of the batch left out (the fit trains on the first half of the
  volleys it is given);
* an answer altered where it is produced (the assign's first answer of
  every call moves to the next cluster).

No cell exchanges anything between chips (the four-chip sweep's shards
are independent designs), so that fault has no place to be planted.
"""
import os

import pytest

import benchkit
from repro.core import backend


def _unchanged(orig):
    def fit(w, xs, *a, **k):
        return w
    return fit


def _half(orig):
    def fit(w, xs, *a, **k):
        return orig(w, xs[: max(1, xs.shape[0] // 2)], *a, **k)
    return fit


def _altered(orig):
    def assign(w, xs, thresholds, t_maxes, q_actives, **k):
        ids = orig(w, xs, thresholds, t_maxes, q_actives, **k)
        first = (ids[:, 0] + 1) % (q_actives + 1)
        return ids.at[:, 0].set(first.astype(ids.dtype))
    return assign


FAULTS = {
    "unchanged": ("fit_padded", _unchanged),
    "half": ("fit_padded", _half),
    "altered": ("assign_padded", _altered),
}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return benchkit.tiny_checkout(str(tmp_path_factory.mktemp("faults")))


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", ["tiny-sweep", "tiny-serve"])
def test_a_planted_fault_reads_incorrect(cell, fault, checkout, monkeypatch):
    monkeypatch.setattr(backend, "pallas_lowering", lambda: "interpret")
    monkeypatch.setattr(backend, "compile_cache", lambda *a, **k: None)
    name, plant = FAULTS[fault]
    monkeypatch.setattr(backend, name, plant(getattr(backend, name)))
    run = benchkit.load_run(checkout)
    spec = run.Spec(root=checkout, bench=os.path.join(checkout, "bench"))
    result = run.run_cell(cell, 11, 0.5, False, platform="cpu", spec=spec)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["check"].values())
