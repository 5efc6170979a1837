"""The control, the reference in bfloat16 in the program's place, fails
the cells' limits (kept at a size a test run holds; the readings at the
cells' own sizes on the chip are in PERF.md)."""
import json
import os

import pytest

import benchkit
import control


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return benchkit.tiny_checkout(str(tmp_path_factory.mktemp("control")))


def _real_limits(traffic):
    with open(os.path.join(benchkit.BENCH, "traffic", f"{traffic}.json")) as f:
        return json.load(f)["limits"]


@pytest.mark.parametrize("cell,real", [("tiny-sweep", "ws-sweep"), ("tiny-serve", "fleet-serve")])
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_the_control_fails_the_limits(cell, real, seed, checkout, monkeypatch):
    from repro.core import backend

    monkeypatch.setattr(backend, "pallas_lowering", lambda: "interpret")
    monkeypatch.setattr(backend, "compile_cache", lambda *a, **k: None)
    run = benchkit.load_run(checkout)
    spec = run.Spec(root=checkout, bench=os.path.join(checkout, "bench"))
    got = control.readings(cell, seed, 0.5, platform="cpu", spec=spec)
    limits = _real_limits(real)
    assert set(got) == set(limits)
    assert any(v > limits[k] for k, v in got.items()), got
