"""``chip_smoke.py`` rehearsed on the host at a tiny size.

The smoke run's phases are plain functions of their sizes and of the
lowering they expect.  Here the backend's lowering policy is steered to
the Pallas interpreter (what ``mosaic`` is on a TPU), so every phase runs
the same kernels, entry points and checks as on the chip — at a few
volleys and small designs.  ``main()`` itself must refuse to run off TPU.
"""
import importlib.util
import os

import pytest

from repro.core import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def interpret(monkeypatch):
    """The fused paths lower to the Pallas interpreter, as they lower to
    Mosaic on a TPU."""
    monkeypatch.setattr(backend, "pallas_lowering", lambda: "interpret")
    return "interpret"


def test_phases_run_tiny_on_the_interpreter(smoke, interpret, capsys):
    info = smoke.phase_device(platform="cpu")
    assert info["platform"] == "cpu" and info["count"] >= 1
    smoke.phase_single(
        interpret, ("SonyAIBORobotSurface2", "Beef"), n_series=8, epochs=1,
        t_max=16,
    )
    smoke.phase_sweep(
        interpret, ("SonyAIBORobotSurface2",), scales=(1.0, 1.25),
        n_series=8, epochs=1, t_max=16,
    )
    smoke.phase_service(
        interpret, ("SonyAIBORobotSurface2", "ECG200"), requests=24,
        batch_size=4, refit_every=8, refit_window=4, t_max=16,
    )
    out = capsys.readouterr().out
    assert "integer grid == cycle" in out
    assert "integer grid == reference" in out
    assert "answers == assign_time_series" in out


def test_a_check_that_fails_stops_the_phase(smoke, interpret):
    with pytest.raises(smoke.SmokeFailure, match="expected 'mosaic'"):
        smoke.phase_single(
            "mosaic", ("SonyAIBORobotSurface2",), n_series=4, epochs=1,
            t_max=16,
        )


def test_main_exits_nonzero_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
