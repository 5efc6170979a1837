"""Envelope-bucketed, sharded design-space exploration (ISSUE 5 acceptance).

The contract under test:
  * the bucketed design sweep is BIT-IDENTICAL per design to the old
    single-global-envelope path on a heterogeneous (varying q, t_max,
    threshold) sweep — bucketing (and sharding) are throughput knobs,
    never semantic ones;
  * buckets with equal envelope shapes share ONE compiled trace (the jit
    cache keys on the envelope, not the bucket);
  * the sweep encodes its stream once per distinct t_max, with every
    design bit-identical to the same design swept alone;
  * the central bucket policy (``backend.envelope_buckets``) respects the
    waste cap and ``max_bucket``, and covers every design exactly once;
  * the shard policy falls back cleanly on a single device, and on a
    forced multi-device host shards the design axis with bit-identical
    results (subprocess — device count must be set before jax init);
  * degenerate streams: N=0 raises a clear up-front ValueError everywhere,
    ``epochs=0`` trivially returns the init weights;
  * ``backend.assign_lowering`` survives abstract (traced) weights on
    current JAX without touching deprecated tracer internals;
  * ``ClusteringResult.params`` has one dict shape across all front-ends;
  * ``dse.explore`` pairs each design's Rand index with a
    ``hwgen.forecast`` area/leakage estimate and emits a nondominated
    Pareto set.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import dse, obs
from repro.core import backend, simulator
from repro.core.types import ColumnConfig, TIME_DTYPE
from repro.hwgen.forecast import PaperForecaster
from repro.kernels import fused_column


def _cfg(p, q, t_max, scale=1.0):
    c = ColumnConfig(p=p, q=q, t_max=t_max)
    return c.with_threshold(scale * simulator.suggest_threshold(c))


def _stream(n=18, length=10, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, length)), rng.integers(0, classes, n)


# ------------------------------------------------------------ bucket policy
def test_envelope_buckets_respects_waste_cap_and_covers_all():
    shapes = [(16, 2, 16), (16, 3, 16), (16, 8, 64), (16, 10, 64)]
    buckets = backend.envelope_buckets(shapes)
    covered = sorted(i for _, idxs in buckets for i in idxs)
    assert covered == [0, 1, 2, 3], "every design in exactly one bucket"
    assert len(buckets) == 2, "small designs must not ride the big envelope"
    for env, idxs in buckets:
        vol = env[0] * env[1] * env[2]
        for i in idxs:
            p, q, t = shapes[i]
            assert vol <= backend.ENVELOPE_WASTE_CAP * p * q * t
    # an infinite cap reproduces the old single-global-envelope behavior
    buckets_inf = backend.envelope_buckets(shapes, waste_cap=float("inf"))
    assert len(buckets_inf) == 1
    assert buckets_inf[0][0] == (16, 10, 64)


def test_envelope_buckets_max_bucket_splits_equal_envelopes():
    shapes = [(8, 3, 16)] * 5
    buckets = backend.envelope_buckets(shapes, max_bucket=2)
    assert [len(idxs) for _, idxs in buckets] == [2, 2, 1]
    assert all(env == (8, 3, 16) for env, _ in buckets)


# --------------------------------------------- bucketed sweep bit-identity
def test_bucketed_sweep_bit_identical_to_global_envelope():
    """Acceptance: a heterogeneous sweep (varying q, t_max, threshold)
    split into envelope buckets reproduces the single-global-envelope
    sweep bit for bit, per design."""
    x, y = _stream(seed=1)
    cfgs = [
        _cfg(10, 2, 16, 0.8), _cfg(10, 3, 16, 1.0),
        _cfg(10, 8, 64, 1.2), _cfg(10, 10, 64, 1.0),
    ]
    res_b = simulator.cluster_time_series_many(x, y, cfgs, epochs=2, seed=3)
    res_g = simulator.cluster_time_series_many(
        x, y, cfgs, epochs=2, seed=3, waste_cap=float("inf")
    )
    assert res_b[0].buckets == 2 and res_g[0].buckets == 1
    for i, (a, b) in enumerate(zip(res_b, res_g)):
        np.testing.assert_array_equal(
            a.assignments, b.assignments,
            err_msg=f"design {i}: bucketing changed assignments",
        )
        np.testing.assert_array_equal(
            np.asarray(a.params["w"]), np.asarray(b.params["w"]),
            err_msg=f"design {i}: bucketing changed trained weights",
        )
        assert a.params["w"].shape == (cfgs[i].p, cfgs[i].q)
        assert a.rand_index == b.rand_index


def test_equal_envelope_buckets_share_one_trace(compile_counter):
    """Acceptance: at most one compiled executable per distinct bucket
    envelope — a max_bucket split into equal envelopes reuses the first
    bucket's AOT executable for fit AND assignment.

    The single-device sweep dispatches through the envelope-keyed AOT
    cache (``backend.fit_padded`` / ``backend.assign_padded``), so the
    invariant is pinned at the true compile seam: the whole sweep
    compiles the fit program once and the assignment program once."""
    x, _ = _stream(n=11, length=9, seed=2)
    # unique geometry (prime-ish sizes) so the cache keys in this test
    # are not shared with other tests
    cfgs = [_cfg(9, 3, 17) for _ in range(4)]
    backend.aot_cache_clear()
    aot_before = backend.aot_cache_size()
    res = simulator.cluster_time_series_many(
        x, None, cfgs, epochs=1, max_bucket=2
    )
    assert res[0].buckets == 2
    assert compile_counter.named("fit_scan_padded") == 1, (
        "equal-envelope buckets must share one compiled fit executable"
    )
    assert compile_counter.named("assign_padded") == 1, (
        "equal-envelope buckets must share one compiled assignment "
        "executable"
    )
    assert backend.aot_cache_size() == aot_before + 2  # one fit + one assign


# ------------------------------------------------------ shared encodes
def _counting_encode(monkeypatch):
    """Wrap ``encoding.encode`` as the sweep sees it; returns the list of
    the t_max each call encoded for."""
    calls = []
    real = simulator.encoding.encode

    def encode(x, t_max, encoder="latency"):
        calls.append(t_max)
        return real(x, t_max, encoder)

    monkeypatch.setattr(simulator.encoding, "encode", encode)
    return calls


def _each_alone(x, y, cfgs, seed, **kw):
    """Every design swept by itself (one encode each), with the init
    weights and stream key the whole sweep gives it."""
    from repro.core import column as column_lib
    from repro.core import stdp as stdp_lib

    rng, init_key = jax.random.split(jax.random.key(seed))
    keys = jax.random.split(init_key, len(cfgs))
    out = []
    for i, (k, c) in enumerate(zip(keys, cfgs)):
        w0 = np.asarray(column_lib.init_params(k, c)["w"])
        sk = None
        if c.stdp.mode == "stochastic":
            sk = [np.asarray(stdp_lib.stream_key(jax.random.fold_in(rng, i)))]
        out += simulator.cluster_time_series_many(
            x, y, [c], seed=seed, w_init=[w0], stream_keys=sk, **kw
        )
    return out


def _assert_same_outcomes(got, want):
    for i, (a, b) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(
            a.assignments, b.assignments, err_msg=f"design {i}: assignments"
        )
        np.testing.assert_array_equal(
            np.asarray(a.params["w"]), np.asarray(b.params["w"]),
            err_msg=f"design {i}: trained weights",
        )
        assert a.rand_index == b.rand_index


@pytest.mark.parametrize("on_error", ["raise", "isolate"])
def test_sweep_encodes_once_per_distinct_t_max(on_error, monkeypatch):
    """A grid over t_max (8, 32) x 3 threshold scales encodes the stream
    twice, and every design trains and is assigned bit-identically to the
    same design swept alone."""
    x, y = _stream(n=14, length=10, seed=11)
    cfgs = [_cfg(10, 3, t, s) for t in (8, 32) for s in (0.8, 1.0, 1.2)]
    calls = _counting_encode(monkeypatch)
    res = simulator.cluster_time_series_many(
        x, y, cfgs, epochs=2, seed=7, on_error=on_error
    )
    assert calls == [8, 32]
    alone = _each_alone(x, y, cfgs, 7, epochs=2, on_error=on_error)
    assert len(calls) == 2 + len(cfgs), "alone, each design encodes once"
    assert all(isinstance(r, simulator.ClusteringResult) for r in res)
    _assert_same_outcomes(res, alone)


def test_stochastic_sweep_keeps_its_stream_keys_with_shared_encodes(
    monkeypatch,
):
    """Under stochastic STDP each design keeps the stream key of its
    position: the shared-encode sweep matches each design swept alone
    under that key."""
    from repro.core.types import STDPConfig

    def cfg(t_max, scale):
        c = ColumnConfig(p=10, q=3, t_max=t_max,
                         stdp=STDPConfig(mode="stochastic"))
        return c.with_threshold(scale * simulator.suggest_threshold(c))

    x, y = _stream(n=14, length=10, seed=12)
    cfgs = [cfg(t, s) for t in (16, 8) for s in (0.9, 1.1)]
    calls = _counting_encode(monkeypatch)
    res = simulator.cluster_time_series_many(x, y, cfgs, epochs=2, seed=5)
    assert calls == [16, 8]
    _assert_same_outcomes(res, _each_alone(x, y, cfgs, 5, epochs=2))


@pytest.mark.parametrize("bad_first", [False, True])
def test_wrong_width_raises_when_sharing_a_t_max(bad_first):
    """A design whose p is not the encoded width raises, even when a valid
    design shares its t_max and so its encode."""
    x, y = _stream(n=8, length=10)
    good, bad = _cfg(10, 2, 16), _cfg(12, 2, 16)
    cfgs = [bad, good] if bad_first else [good, bad]
    with pytest.raises(ValueError, match="encoded width 10 != design input "
                                         "width 12"):
        simulator.cluster_time_series_many(x, y, cfgs, epochs=1)


# ------------------------------------------------------------ shard policy
def test_design_shard_single_device_fallback():
    """On a single-device host the policy is a clean no-op: no mesh,
    shard count 1, arrays left untouched, sweep results tagged shards=1."""
    if jax.local_device_count() != 1:
        pytest.skip("host has multiple devices")
    assert backend.design_shards(4) == 1
    assert backend.design_mesh(4) is None
    x = jnp.arange(6.0)
    assert backend.shard_design_axis(None, x) is x
    series, y = _stream(n=8, length=8, seed=4)
    res = simulator.cluster_time_series_many(
        series, y, [_cfg(8, 2, 16)], epochs=1
    )
    assert res[0].shards == 1


def test_design_shards_divisor_policy():
    """Shard count is the largest divisor of D fitting the device count —
    exercised against a fake device count (the mesh itself needs real
    devices and is covered by the subprocess test)."""
    n_dev = jax.local_device_count()
    assert backend.design_shards(1) == 1
    assert backend.design_shards(n_dev) == n_dev
    assert 1 <= backend.design_shards(7) <= 7


def test_sharded_sweep_bit_identical_multi_device_subprocess():
    """4 forced host devices: the design axis shards 4 ways and the sweep
    stays bit-identical to the unsharded path (subprocess — the device
    count must be set before jax initializes)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np, jax
        from repro.core import simulator, backend
        from repro.core.types import ColumnConfig

        assert jax.local_device_count() == 4
        assert backend.design_shards(4) == 4
        assert backend.design_shards(6) == 3
        assert backend.design_shards(5) == 1  # no divisor -> fallback

        def cfg(q, t):
            c = ColumnConfig(p=12, q=q, t_max=t)
            return c.with_threshold(simulator.suggest_threshold(c))

        rng = np.random.default_rng(0)
        x = rng.normal(size=(14, 12)); y = rng.integers(0, 3, 14)
        cfgs = [cfg(3, 16), cfg(4, 16), cfg(3, 24), cfg(4, 24)]
        res_s = simulator.cluster_time_series_many(x, y, cfgs, epochs=2)
        assert [r.shards for r in res_s] == [4, 4, 4, 4], res_s[0].shards
        backend.design_mesh = lambda d: None  # force the unsharded path
        res_u = simulator.cluster_time_series_many(x, y, cfgs, epochs=2)
        for a, b in zip(res_s, res_u):
            np.testing.assert_array_equal(a.assignments, b.assignments)
            np.testing.assert_array_equal(
                np.asarray(a.params["w"]), np.asarray(b.params["w"]))
        print("SHARD_OK")
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=dict(os.environ, PYTHONPATH="src"),
        timeout=600,
    )
    assert "SHARD_OK" in r.stdout, r.stderr[-3000:]


# ------------------------------------------------------ degenerate streams
def test_empty_stream_raises_up_front():
    cfg = _cfg(8, 2, 16)
    with pytest.raises(ValueError, match="N=0"):
        simulator.cluster_time_series_many(
            np.zeros((0, 8)), None, [cfg], epochs=1
        )
    w = jnp.ones((1, 8, 2))
    xs0 = jnp.zeros((0, 1, 8), TIME_DTYPE)
    th = jnp.asarray([5.0], jnp.float32)
    tm = jnp.asarray([16], TIME_DTYPE)
    qa = jnp.asarray([2], TIME_DTYPE)
    with pytest.raises(ValueError, match="empty stream"):
        fused_column.fit_scan_padded(
            w, xs0, th, tm, qa, t_window=16, w_max=7, wta_k=1,
            mu_capture=1.0, mu_backoff=1.0, mu_search=1.0, stabilize=False,
            response="rnl", epochs=1, lowering="reference",
        )
    with pytest.raises(ValueError, match="empty stream"):
        fused_column.assign_padded(
            w, xs0, th, tm, qa, t_window=16, wta_k=1, response="rnl",
            lowering="reference",
        )


def test_zero_epochs_returns_init_weights_trivially():
    """epochs=0 is well-defined: no training pass, weights unchanged —
    for the raw padded scan and through the sweep front-end (whose
    assignments then come from the init weights)."""
    rng = np.random.default_rng(7)
    w0 = jnp.asarray(rng.integers(0, 8, (2, 8, 3)), jnp.float32)
    xs = jnp.asarray(rng.integers(0, 16, (5, 2, 8)), TIME_DTYPE)
    th = jnp.asarray([5.0, 4.0], jnp.float32)
    tm = jnp.asarray([16, 12], TIME_DTYPE)
    qa = jnp.asarray([3, 2], TIME_DTYPE)
    w = fused_column.fit_scan_padded(
        jnp.array(w0, copy=True), xs, th, tm, qa, t_window=16, w_max=7,
        wta_k=1, mu_capture=1.0, mu_backoff=1.0, mu_search=1.0,
        stabilize=False, response="rnl", epochs=0, lowering="reference",
    )
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w))

    series, y = _stream(n=6, length=8, seed=8)
    cfg = _cfg(8, 2, 16)
    res = simulator.cluster_time_series_many(series, y, [cfg], epochs=0)
    assert res[0].assignments.shape == (6,)
    # the returned params are exactly the seeded init weights
    import jax as _jax
    from repro.core import column as column_lib
    rng_ = _jax.random.key(0)
    _, init_key = _jax.random.split(rng_)
    (key,) = _jax.random.split(init_key, 1)
    w_init = column_lib.init_params(key, cfg)["w"]
    np.testing.assert_array_equal(
        np.asarray(w_init), np.asarray(res[0].params["w"])
    )


# --------------------------------------------------- assign_lowering (jax)
def test_assign_lowering_abstract_weights_fall_back(monkeypatch):
    """Tracers (abstract values) must fall back to 'reference' without
    touching deprecated jax.core internals — probed via eval_shape, which
    hands the probe abstract arrays exactly like a jit trace would."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    seen = []

    def probe(w):
        seen.append(backend.assign_lowering("rnl", w))
        return w

    jax.eval_shape(probe, jax.ShapeDtypeStruct((2, 2), jnp.float32))
    assert seen == ["reference"]
    # concrete weights still pick the kernel on the integer grid
    assert backend.assign_lowering("rnl", jnp.asarray([[2.0]])) == "mosaic"
    assert (
        backend.assign_lowering("rnl", jnp.asarray([[2.5]])) == "reference"
    )


# ------------------------------------------------------- params unification
def test_clustering_result_params_shape_unified():
    """One dict contract across front-ends: {'w'} for single columns and
    sweep members (cropped to design size), {'layers': [{'w'}, ...]} for
    networks."""
    from repro.core.types import LayerConfig, NetworkConfig

    series, y = _stream(n=8, length=8, seed=9)
    cfg = _cfg(8, 2, 16)
    single = simulator.cluster_time_series(series, y, cfg, epochs=1)
    assert set(single.params) == {"w"}
    (swept,) = simulator.cluster_time_series_many(
        series, y, [cfg], epochs=1
    )
    assert set(swept.params) == {"w"}
    assert swept.params["w"].shape == single.params["w"].shape

    l2 = _cfg(4, 2, 16)
    net = NetworkConfig(layers=(
        LayerConfig(columns=2, column=_cfg(8, 2, 16)),
        LayerConfig(columns=1, column=l2),
    ))
    net_res = simulator.cluster_time_series_network(
        series, y, net, epochs=1
    )
    assert set(net_res.params) == {"layers"}
    assert [set(lp) for lp in net_res.params["layers"]] == [{"w"}, {"w"}]
    assert net_res.params["layers"][0]["w"].shape == (2, 8, 2)


# ----------------------------------------------------------- dse.explore
def test_explore_pairs_rand_index_with_forecast_and_emits_pareto():
    """Acceptance: dse.explore sweeps the space, pairs every design's
    Rand index with the hwgen.forecast area/leakage for its synapse
    count, and returns a nondominated Pareto set."""
    x, y = _stream(n=16, length=8, seed=5)
    space = dse.DesignSpace(
        q=(2, 4), t_max=(16,), threshold_scale=(0.8, 1.2),
    )
    res = dse.explore(x, y, space, epochs=1, seed=1)
    assert len(res.points) == space.size() == 4
    fc = PaperForecaster()
    for p in res.points:
        assert p.synapses == p.cfg.p * p.cfg.q
        assert p.area_um2 == pytest.approx(fc.area_um2(p.synapses))
        assert p.leakage_uw == pytest.approx(fc.leakage_uw(p.synapses))
        assert not np.isnan(p.rand_index)
        assert set(p.params) == {"w"}
    assert res.pareto, "a labeled sweep must yield a frontier"
    for p in res.pareto:
        assert not any(
            dse.dominates(o, p) for o in res.points if o is not p
        ), "pareto point is dominated"
    best = res.best()
    assert best in res.pareto
    assert res.meta["buckets"] == {"latency": 1}
    assert "explored" in dse.summarize(res)


def test_explore_random_search_and_guards():
    x, y = _stream(n=10, length=8, seed=6)
    space = dse.DesignSpace(q=(2, 3), t_max=(16, 24))
    res = dse.explore(
        x, y, space, epochs=1, search="random", budget=2, seed=2
    )
    assert len(res.points) == 2
    with pytest.raises(ValueError, match="labels"):
        dse.explore(x, None, space, epochs=1)
    with pytest.raises(ValueError, match="budget"):
        dse.explore(x, y, space, epochs=1, search="random")
    with pytest.raises(ValueError, match="search"):
        dse.explore(x, y, space, epochs=1, search="anneal")


def test_pareto_front_excludes_dominated_and_nan():
    def pt(i, ri, area, leak=1.0):
        return dse.DesignPoint(
            index=i, cfg=_cfg(8, 2, 16), encoder="latency", rand_index=ri,
            synapses=16, area_um2=area, leakage_uw=leak, params={},
        )

    a = pt(0, 0.9, 100.0)
    b = pt(1, 0.8, 200.0)      # worse RI, bigger area: dominated by a
    c = pt(2, 0.95, 300.0)     # better RI at more area: frontier
    d = pt(3, float("nan"), 1.0)
    front = dse.pareto_front([a, b, c, d])
    assert front == [a, c]
    assert dse.dominates(a, b) and not dse.dominates(b, a)
    assert not dse.dominates(a, c)


# ---------------------------------------------------------------- spans
def test_traced_explore_gives_the_span_tree(monkeypatch, tmp_path):
    """Under the profiler an exploration is one ``dse.explore`` root; each
    envelope bucket one ``sim.bucket`` with its pad, fit dispatch,
    lowering wait and assign, then one ``sim.score`` and the journal
    callback's ``dse.record``.  Untraced, nothing is recorded."""
    monkeypatch.setattr(backend, "pallas_lowering", lambda: "interpret")
    x, y = _stream(n=12, length=10)
    space = dse.DesignSpace(q=(2, 3), t_max=(8, 32),
                            threshold_scale=(1.0,))
    obs.reset()
    dse.explore(x, y, space, epochs=1, seed=4)
    assert obs.snapshot().spans == ()

    with jax.profiler.trace(str(tmp_path)):
        res = dse.explore(x, y, space, epochs=1, seed=5)
    snap = obs.snapshot()
    obs.reset()

    def kids(span):
        return [s.name for s in snap.spans if s.parent == span.id]

    root, = [s for s in snap.spans if s.parent == 0]
    assert root.name == "dse.explore" and root.attrs == {"candidates": 4}
    assert kids(root) == ["dse.init", "sim.many", "dse.pareto"]
    many, = [s for s in snap.spans if s.name == "sim.many"]
    n_buckets = res.meta["buckets"]["latency"]
    assert n_buckets == 2
    assert kids(many) == ["sim.encode"] + [
        "sim.bucket", "sim.score", "dse.record"
    ] * n_buckets
    buckets = [s for s in snap.spans if s.name == "sim.bucket"]
    for b in buckets:
        assert kids(b) == ["sim.pad", "sim.fit", "sim.lowering", "sim.assign"]
        assert b.attrs["lowering"] == "interpret"
        assert b.attrs["volleys"] == len(x)
    assert sum(b.attrs["designs"] for b in buckets) == 4


def test_traced_explore_counts_the_encodes(tmp_path):
    """Traced, an exploration counts one encode per distinct t_max against
    the designs encoded for; untraced, it counts nothing."""
    x, y = _stream(n=12, length=10, seed=3)
    space = dse.DesignSpace(q=(2, 3), t_max=(8, 32),
                            threshold_scale=(0.9, 1.1))
    names = (simulator.ENCODE_DESIGNS, simulator.ENCODE_RUNS)
    assert set(names) <= set(simulator.SWEEP_COUNTERS)
    obs.reset()
    dse.explore(x, y, space, epochs=1, seed=4)
    assert not set(names) & set(obs.snapshot().counters)

    with jax.profiler.trace(str(tmp_path)):
        dse.explore(x, y, space, epochs=1, seed=4)
    counters = obs.snapshot().counters
    obs.reset()
    assert counters[simulator.ENCODE_DESIGNS] == space.size() == 8
    assert counters[simulator.ENCODE_RUNS] == 2


# ---------------------------------------------------------- initial draws
def _explore_module():
    # ``repro.dse.explore`` the attribute is the function; the module
    # holds the draw and the counters
    import importlib

    return importlib.import_module("repro.dse.explore")


def _space(mode, **axes):
    from repro.core.types import STDPConfig

    axes = {"q": (2, 3), "t_max": (8, 16), "threshold_scale": (0.9, 1.1),
            **axes}
    return dse.DesignSpace(**axes, stdp=STDPConfig(mode=mode))


@pytest.mark.parametrize("mode", ["expected", "stochastic"])
def test_batched_init_draws_match_the_per_candidate_draws(mode):
    """The batched draw gives every candidate of a grid over two q (two
    shape groups), in any order, the weights of ``init_params`` of
    ``fold_in(init_key, i)`` and, under stochastic STDP, the stream key of
    ``stream_key(fold_in(stream_root, i))``, bit for bit."""
    from repro.core import column, stdp

    space = _space(mode)
    cfgs = [dse.candidate_config(c, 10, space.stdp) for c in space.grid()]
    idxs = [5, 0, 3, 7, 2, 6]
    stream_root, init_key = jax.random.split(jax.random.key(9))
    w, keys = _explore_module()._draw_inits(
        init_key, stream_root, idxs, [cfgs[i] for i in idxs]
    )
    assert len(w) == len(idxs)
    for j, i in enumerate(idxs):
        want = column.init_params(jax.random.fold_in(init_key, i), cfgs[i])
        assert w[j].dtype == want["w"].dtype
        np.testing.assert_array_equal(w[j], np.asarray(want["w"]))
    if mode == "expected":
        assert keys is None
        return
    assert len(keys) == len(idxs)
    for j, i in enumerate(idxs):
        np.testing.assert_array_equal(
            keys[j], np.asarray(stdp.stream_key(
                jax.random.fold_in(stream_root, i)
            ))
        )


def test_traced_explore_counts_the_init_draws(tmp_path):
    """Traced, an exploration counts the candidates drawn and one draw
    program per candidate shape; untraced, it counts nothing."""
    mod = _explore_module()
    x, y = _stream(n=12, length=10, seed=3)
    space = _space("expected", t_max=(8, 32))
    names = (mod.INIT_DESIGNS, mod.INIT_RUNS)
    assert names == mod.DSE_COUNTERS
    obs.reset()
    dse.explore(x, y, space, epochs=1, seed=4)
    assert not set(names) & set(obs.snapshot().counters)

    with jax.profiler.trace(str(tmp_path)):
        dse.explore(x, y, space, epochs=1, seed=4)
    counters = obs.snapshot().counters
    obs.reset()
    assert counters[mod.INIT_DESIGNS] == space.size() == 8
    assert counters[mod.INIT_RUNS] == len(space.q) == 2


@pytest.mark.parametrize("mode", ["expected", "stochastic"])
def test_resumed_partial_explore_matches_the_full_run(mode, tmp_path):
    """A journaled exploration of the grid's first three candidates, then
    resumed over the whole grid, draws the rest in two shape groups and
    ends bit-identical to an uninterrupted run."""
    x, y = _stream(n=12, length=10, seed=14)
    space = _space(mode)
    full = dse.explore(x, y, space, epochs=1, seed=6)
    path = str(tmp_path / "dse.jsonl")
    dse.explore(x, y, space, epochs=1, seed=6, budget=3, journal=path)
    again = dse.explore(
        x, y, space, epochs=1, seed=6, journal=path, resume=True
    )
    assert again.meta["resumed"] == 3
    assert [p.index for p in again.points] == [p.index for p in full.points]
    for a, b in zip(full.points, again.points):
        assert a.rand_index == b.rand_index or (
            np.isnan(a.rand_index) and np.isnan(b.rand_index)
        )
        np.testing.assert_array_equal(
            np.asarray(a.params["w"]), np.asarray(b.params["w"])
        )
