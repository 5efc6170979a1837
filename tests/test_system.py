"""End-to-end behaviour of the paper's system (TNNGen): PyTorch-model-spec
-> functional simulation -> clustering metrics -> hardware flow -> forecast."""
import tempfile

import numpy as np

from repro.configs.tnn_columns import column_config, hardware_spec
from repro.core import simulator
from repro.data import ucr
from repro.hwgen import run_flow
from repro.hwgen.forecast import PaperForecaster


def test_tnngen_end_to_end_small():
    """The paper's Fig. 1 flow on one benchmark: simulate + cluster, then
    generate hardware and forecast — every stage producing sane output."""
    name = "ECG200"
    ds = ucr.load(name)
    x, y = ds.x[:120], ds.y[:120]
    cfg = column_config(name)
    cfg = cfg.with_threshold(simulator.suggest_threshold(cfg))
    res = simulator.cluster_time_series(x, y, cfg, epochs=3)
    assert np.isfinite(res.rand_index)
    # a trained TNN column must beat chance (random 2-class RI ~0.5 - eps)
    assert res.rand_index > 0.45

    with tempfile.TemporaryDirectory() as d:
        fr = run_flow(hardware_spec(name), "tnn7", build_root=d)
        assert fr.area_um2 > 0 and fr.leakage_uw > 0
        fc = PaperForecaster()
        # forecast within 20% of the flow's post-layout area (Table V regime)
        assert abs(fc.area_um2(fr.synapses) - fr.area_um2) / fr.area_um2 < 0.2


def test_tnn_beats_untrained_column():
    name = "SonyAIBORobotSurface2"
    ds = ucr.load(name)
    x, y = ds.x[:160], ds.y[:160]
    cfg = column_config(name).with_threshold(
        simulator.suggest_threshold(column_config(name))
    )
    trained = simulator.cluster_time_series(x, y, cfg, epochs=4)
    untrained = simulator.cluster_time_series(x, y, cfg, epochs=0)
    assert trained.rand_index >= untrained.rand_index - 0.05


def test_cluster_modes_agree():
    """Event-driven and cycle-accurate simulation produce identical
    clusterings (the paper's hybrid timing claim, end-to-end)."""
    name = "ECG200"
    ds = ucr.load(name)
    x = ds.x[:60]
    cfg = column_config(name).with_threshold(
        simulator.suggest_threshold(column_config(name))
    )
    a = simulator.cluster_time_series(x, ds.y[:60], cfg, epochs=2, mode="event")
    b = simulator.cluster_time_series(x, ds.y[:60], cfg, epochs=2, mode="cycle")
    np.testing.assert_array_equal(a.assignments, b.assignments)
