"""The design sweep's trained weights leave the device once per bucket.

The contract under test:
  * every sweep member's ``params['w']`` is a host ``np.ndarray`` [p, q]
    float32, cropped from its bucket's weight block, bit-identical to the
    same design swept alone and to the single-global-envelope sweep, under
    ``on_error='raise'`` and ``'isolate'``;
  * traced, a sweep counts the designs whose weights a bucket returned
    (``sim.weight_designs``) and one weight-block fetch per bucket run
    (``sim.weight_fetches``), the solo re-runs of a failed bucket included;
  * on a forced four-device host the design-sharded block is fetched once
    per bucket and gives the unsharded sweep's host weights (subprocess —
    the device count must be set before jax initializes).
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import column, simulator
from repro.core.types import ColumnConfig
from repro.kernels import fused_column
from repro.testing import faults


def _cfg(p, q, t_max, scale=1.0):
    c = ColumnConfig(p=p, q=q, t_max=t_max)
    return c.with_threshold(scale * simulator.suggest_threshold(c))


def _stream(n=14, length=10, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, length)), rng.integers(0, classes, n)


# two envelope buckets (q 2-3 at t_max 16, q 8-10 at t_max 64), the
# smaller members of each cropped from a larger envelope; every threshold
# differs, so a poisoned threshold names one design
CFGS = [
    _cfg(10, 2, 16, 0.8), _cfg(10, 3, 16, 1.0), _cfg(10, 2, 16, 1.2),
    _cfg(10, 8, 64, 0.9), _cfg(10, 10, 64, 1.1),
]
SEED = 3


def _alone(x, y, cfgs, seed, **kw):
    """Every design swept by itself, with the init weights the whole sweep
    gives it."""
    _, init_key = jax.random.split(jax.random.key(seed))
    keys = jax.random.split(init_key, len(cfgs))
    out = []
    for k, c in zip(keys, cfgs):
        w0 = np.asarray(column.init_params(k, c)["w"])
        out += simulator.cluster_time_series_many(
            x, y, [c], seed=seed, w_init=[w0], **kw
        )
    return out


@pytest.mark.parametrize("on_error", ["raise", "isolate"])
def test_swept_weights_are_host_arrays_identical_to_each_design_alone(
    on_error,
):
    x, y = _stream(seed=1)
    res = simulator.cluster_time_series_many(
        x, y, CFGS, epochs=2, seed=SEED, on_error=on_error
    )
    assert res[0].buckets == 2
    alone = _alone(x, y, CFGS, SEED, epochs=2, on_error=on_error)
    one_envelope = simulator.cluster_time_series_many(
        x, y, CFGS, epochs=2, seed=SEED, on_error=on_error,
        waste_cap=float("inf"),
    )
    assert one_envelope[0].buckets == 1
    for i, c in enumerate(CFGS):
        w = res[i].params["w"]
        assert type(w) is np.ndarray, f"design {i}: {type(w)}"
        assert w.dtype == np.float32 and w.shape == (c.p, c.q)
        assert w.flags.c_contiguous
        for ref in (alone[i], one_envelope[i]):
            assert type(ref.params["w"]) is np.ndarray
            np.testing.assert_array_equal(
                w, ref.params["w"], err_msg=f"design {i}: trained weights"
            )
            np.testing.assert_array_equal(
                res[i].assignments, ref.assignments,
                err_msg=f"design {i}: assignments",
            )
            assert res[i].rand_index == ref.rand_index


@pytest.mark.parametrize("on_error,poison,designs,fetches", [
    # one fetch per bucket, whatever its size
    ("raise", None, 5, 2),
    ("isolate", None, 5, 2),
    # the q 2-3 bucket fails on every rung: its two healthy members re-run
    # alone, a fetch each, and the poisoned one returns no weights
    ("isolate", 1, 4, 3),
])
def test_traced_sweep_counts_one_weight_fetch_per_bucket(
    on_error, poison, designs, fetches, monkeypatch, tmp_path,
):
    x, y = _stream(seed=2)
    names = (simulator.WEIGHT_DESIGNS, simulator.WEIGHT_FETCHES)
    assert set(names) <= set(simulator.SWEEP_COUNTERS)
    if poison is not None:
        monkeypatch.setattr(
            fused_column, "fit_scan_padded",
            faults.fail_on_threshold(
                fused_column.fit_scan_padded, CFGS[poison].neuron.threshold
            ),
        )
    obs.reset()
    simulator.cluster_time_series_many(
        x, y, CFGS, epochs=1, seed=SEED, on_error=on_error
    )
    assert not set(names) & set(obs.snapshot().counters)

    with jax.profiler.trace(str(tmp_path)):
        res = simulator.cluster_time_series_many(
            x, y, CFGS, epochs=1, seed=SEED, on_error=on_error
        )
    counters = obs.snapshot().counters
    obs.reset()
    if poison is not None:
        assert isinstance(res[poison], simulator.EvalFailure)
    assert counters[simulator.WEIGHT_DESIGNS] == designs
    assert counters[simulator.WEIGHT_FETCHES] == fetches


def test_sharded_sweep_fetches_each_bucket_once_subprocess():
    """4 forced host devices: every bucket's design-sharded weight block is
    fetched once and cropped to the unsharded sweep's host weights."""
    code = textwrap.dedent("""
        import os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np, jax
        from repro import obs
        from repro.core import simulator, backend
        from repro.core.types import ColumnConfig

        assert jax.local_device_count() == 4

        def cfg(q, t, scale):
            c = ColumnConfig(p=12, q=q, t_max=t)
            return c.with_threshold(scale * simulator.suggest_threshold(c))

        rng = np.random.default_rng(0)
        x = rng.normal(size=(14, 12)); y = rng.integers(0, 3, 14)
        cfgs = [cfg(q, 16, s) for q in (2, 3) for s in (0.9, 1.1)]
        cfgs += [cfg(q, 64, s) for q in (8, 10) for s in (0.9, 1.1)]
        with tempfile.TemporaryDirectory() as d, jax.profiler.trace(d):
            res_s = simulator.cluster_time_series_many(x, y, cfgs, epochs=2)
        counters = obs.snapshot().counters
        assert res_s[0].buckets == 2, res_s[0].buckets
        assert [r.shards for r in res_s] == [4] * 8, [r.shards for r in res_s]
        assert counters[simulator.WEIGHT_DESIGNS] == 8, counters
        assert counters[simulator.WEIGHT_FETCHES] == 2, counters
        backend.design_mesh = lambda d: None  # force the unsharded path
        res_u = simulator.cluster_time_series_many(x, y, cfgs, epochs=2)
        assert [r.shards for r in res_u] == [1] * 8
        for c, a, b in zip(cfgs, res_s, res_u):
            w = a.params["w"]
            assert type(w) is np.ndarray and w.dtype == np.float32
            assert w.shape == (c.p, c.q)
            np.testing.assert_array_equal(w, b.params["w"])
            np.testing.assert_array_equal(a.assignments, b.assignments)
        print("FETCH_OK")
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=dict(os.environ, PYTHONPATH="src"),
        timeout=600,
    )
    assert "FETCH_OK" in r.stdout, r.stderr[-3000:]
