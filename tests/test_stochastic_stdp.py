"""Stochastic (Bernoulli-gated) STDP on the fused path.

The stream (``repro.core.stdp``): Threefry-2x32 of a design's stream key
on the counter pair (global volley index, (i << 16) | j) decides each
synapse's unit update.  Every path draws the same bits for the same
synapse and volley, so from integer initial counters the fused fit (its
jnp reference body and the Pallas kernel under the interpreter), the
``cycle`` solver and the benchmark's plain reference
(``bench/reference_stochastic.py``, which draws through JAX's own
``threefry2x32_p``) agree bit for bit — alone, bucketed with a larger
design, sharded, and through ``dse.explore`` with a journal kill and
resume.  The streaming service serves such designs, each re-fit resuming
its design's stream where the last one stopped, and agrees bit for bit
with ``bench/reference_stochastic_serve.py``, through a kill and
``recover`` too.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.random import threefry2x32_p

from repro import dse
from repro.core import backend, column, simulator, stdp
from repro.core.types import ColumnConfig, NeuronConfig, STDPConfig, TIME_DTYPE
from repro.kernels import fused_column
from repro.serve import durability

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))
import reference  # noqa: E402
import reference_stochastic  # noqa: E402

STOCH = STDPConfig(mode="stochastic")
N, EPOCHS, T_MAX = 16, 2, 16


def _cfg(p, q, threshold=None):
    c = ColumnConfig(p=p, q=q, t_max=T_MAX, stdp=STOCH)
    return c.with_threshold(
        threshold if threshold is not None else simulator.suggest_threshold(c)
    )


def _volleys(p, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, T_MAX + 2, (N, p)), TIME_DTYPE)


def _statics(cfg):
    s = cfg.stdp
    return (cfg.neuron.w_max, cfg.wta.k, s.mu_capture, s.mu_backoff,
            s.mu_search, s.stabilizer == "half")


def _reference_fit(cfg, w0, xs, key, dtype=jnp.float32, epochs=EPOCHS):
    return np.asarray(reference_stochastic.fit(
        jnp.asarray(w0), xs, jnp.float32(cfg.neuron.threshold),
        jax.random.key_data(key).astype(jnp.uint32), t_max=cfg.t_max,
        epochs=epochs, statics=_statics(cfg), dtype=dtype,
    ), np.float32)


# ------------------------------------------------------------ the stream
@pytest.mark.parametrize("seed", [0, 1, 2**31 + 7])
def test_threefry_rounds_match_the_jax_primitive(seed):
    rng = np.random.default_rng(seed % 2**32)
    k = rng.integers(0, 2**32, (2,), dtype=np.uint32)
    x0 = rng.integers(0, 2**32, (257,), dtype=np.uint32)
    x1 = rng.integers(0, 2**32, (257,), dtype=np.uint32)
    want = threefry2x32_p.bind(
        jnp.uint32(k[0]), jnp.uint32(k[1]), jnp.asarray(x0), jnp.asarray(x1)
    )[0]
    ki = k.view(np.int32)
    got = jax.jit(stdp.threefry2x32)(
        jnp.int32(ki[0]), jnp.int32(ki[1]),
        jnp.asarray(x0.view(np.int32)), jnp.asarray(x1.view(np.int32)),
    )
    np.testing.assert_array_equal(np.asarray(want).view(np.int32), np.asarray(got))


def test_stream_bits_change_with_key_and_volley():
    """The control of the draws: the same synapses under another key or
    at another volley index draw different bits."""
    ctr = stdp.synapse_counter((40, 5))
    key = stdp.stream_key(jax.random.key(3))
    u = np.asarray(stdp.stream_uniform(key, jnp.int32(9), ctr))
    u_key = np.asarray(stdp.stream_uniform(
        stdp.stream_key(jax.random.key(4)), jnp.int32(9), ctr))
    u_vol = np.asarray(stdp.stream_uniform(key, jnp.int32(10), ctr))
    assert (u != u_key).mean() > 0.99 and (u != u_vol).mean() > 0.99
    assert np.all((u >= 0) & (u < 1))
    # the reference's uniforms, drawn through the primitive, are the same
    ref = reference_stochastic.uniforms(
        jax.random.key_data(jax.random.key(3)).astype(jnp.uint32), 9, 40, 5,
        jnp.float32,
    )
    np.testing.assert_array_equal(u, np.asarray(ref))


def test_init_counters_are_integers():
    w = np.asarray(column.init_params(jax.random.key(2), _cfg(30, 4))["w"])
    assert np.array_equal(w, np.round(w)) and w.min() >= 0 and w.max() <= 7
    assert len(np.unique(w)) == 8
    w_exp = np.asarray(column.init_params(
        jax.random.key(2), ColumnConfig(p=30, q=4))["w"])
    assert not np.array_equal(w_exp, np.round(w_exp))


# ------------------------------------------------ fused == cycle == reference
@pytest.mark.parametrize("p,q,seed", [(24, 3, 0), (32, 4, 1), (40, 5, 2)])
def test_fused_fit_equals_cycle_solver_and_reference(p, q, seed):
    cfg = _cfg(p, q)
    xs = _volleys(p, seed)
    w0 = column.init_params(jax.random.key(100 + seed), cfg)
    key = jax.random.key(200 + seed)
    cyc = np.asarray(column.fit(w0, xs, cfg, EPOCHS, mode="cycle", rng=key)["w"])
    assert np.abs(cyc - np.asarray(w0["w"])).sum() > 0, "training must move"
    assert np.array_equal(cyc, np.round(cyc)), "counters stay integers"
    np.testing.assert_array_equal(_reference_fit(cfg, w0["w"], xs, key), cyc)
    for low in ("reference", "interpret"):
        got, _ = fused_column.fit_fused(w0, xs, cfg, EPOCHS, lowering=low, rng=key)
        np.testing.assert_array_equal(np.asarray(got["w"]), cyc, err_msg=low)
    k = stdp.stream_key(key)[None]
    for low in ("reference", "interpret"):
        for v_blk in (1, 5):
            got = fused_column.fit_scan_padded(
                jnp.array(w0["w"])[None], xs[:, None, :],
                jnp.asarray([cfg.neuron.threshold], jnp.float32),
                jnp.asarray([T_MAX], TIME_DTYPE), jnp.asarray([q], TIME_DTYPE),
                t_window=T_MAX, w_max=7, wta_k=1, mu_capture=0.5,
                mu_backoff=0.5, mu_search=2.0 ** -10, stabilize=True,
                response="rnl", epochs=EPOCHS, lowering=low, v_blk=v_blk,
                stochastic=True, keys=k,
            )
            np.testing.assert_array_equal(
                np.asarray(got[0]), cyc, err_msg=f"{low} v_blk={v_blk}"
            )


def test_weights_do_not_depend_on_bucket_mates():
    """A design alone, and padded into an envelope with a larger design
    (one bucket), trains to the same counters."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(N, 28))
    small, large = _cfg(28, 3), _cfg(28, 5)
    assert backend.envelope_buckets([(28, 3, 16), (28, 5, 16)]) == [
        ((28, 5, 16), [1, 0])
    ]
    both = simulator.cluster_time_series_many(x, None, [small, large], EPOCHS, seed=4)
    # design 0's own init and stream, as the two-design sweep derives them
    root, init_key = jax.random.split(jax.random.key(4))
    w0 = column.init_params(jax.random.split(init_key, 2)[0], small)["w"]
    alone = simulator.cluster_time_series_many(
        x, None, [small], EPOCHS, w_init=[np.asarray(w0)],
        stream_keys=[stdp.stream_key(jax.random.fold_in(root, 0))],
    )
    np.testing.assert_array_equal(
        np.asarray(alone[0].params["w"]), np.asarray(both[0].params["w"])
    )
    np.testing.assert_array_equal(alone[0].assignments, both[0].assignments)


def test_sharded_stochastic_sweep_is_bit_identical_subprocess():
    """4 forced host devices: the stream keys shard with their designs and
    every design trains as it does unsharded."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np, jax
        from repro.core import simulator, backend
        from repro.core.types import ColumnConfig, STDPConfig

        def cfg(q, s):
            c = ColumnConfig(p=24, q=q, t_max=16,
                             stdp=STDPConfig(mode="stochastic"))
            return c.with_threshold(s * simulator.suggest_threshold(c))

        rng = np.random.default_rng(0)
        x = rng.normal(size=(16, 24)); y = rng.integers(0, 3, 16)
        cfgs = [cfg(3, 0.8), cfg(4, 1.0), cfg(3, 1.2), cfg(5, 0.9)]
        from repro.testing import count_compiles

        with count_compiles() as compiled:
            res_s = simulator.cluster_time_series_many(x, y, cfgs, epochs=2)
        assert [r.shards for r in res_s] == [4] * 4, res_s[0].shards
        # the sharded programs carry the fit's and the assign's names
        assert {'"jit_fit_scan_padded"', '"jit_assign_padded"'} <= set(
            compiled.names), compiled.names
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
        cwd=ROOT, env=dict(os.environ, PYTHONPATH="src"), timeout=600,
    )
    assert "SHARD_OK" in r.stdout, r.stderr[-3000:]


# ------------------------------------------------------------ policy
def test_policy_admits_stochastic_designs():
    cfg = _cfg(24, 3)
    fused_column.check_fusable(cfg, "mosaic")
    assert backend.resolve("auto", cfg, training=True) == "pallas"
    w_int = column.init_params(jax.random.key(0), cfg)["w"]
    assert backend.cycle_exact(cfg, w_int)
    assert not backend.cycle_exact(cfg, w_int + 0.5)
    ladder = backend.lowering_ladder("mosaic", cycle_exact=True)
    assert ladder[-1] == "cycle"
    # on a kernel host, integer counters take the Mosaic assign kernel
    assert backend.assign_lowering("rnl", w_int) == backend.padded_lowering("rnl")


def test_cycle_rung_reproduces_the_fused_sweep(monkeypatch):
    """Every fused rung failing, an isolated sweep degrades to the
    'cycle' solver rung, which is bit-identical for stochastic designs
    and counted in ``sim.solver_designs``."""
    from repro import obs

    rng = np.random.default_rng(9)
    x = rng.normal(size=(N, 24)); y = rng.integers(0, 3, N)
    cfgs = [_cfg(24, 3), _cfg(24, 4)]
    fused = simulator.cluster_time_series_many(x, y, cfgs, EPOCHS, seed=2)

    def broken(*a, **k):
        raise RuntimeError("rung down")

    monkeypatch.setattr(fused_column, "fit_scan_padded", broken)
    counted = []
    monkeypatch.setattr(obs, "count", lambda name, n=1: counted.append((name, n)))
    with pytest.warns(RuntimeWarning):
        solo = simulator.cluster_time_series_many(
            x, y, cfgs, EPOCHS, seed=2, on_error="isolate"
        )
    for a, b in zip(fused, solo):
        assert b.lowering == "cycle"
        np.testing.assert_array_equal(np.asarray(a.params["w"]), np.asarray(b.params["w"]))
        np.testing.assert_array_equal(a.assignments, b.assignments)
    assert counted.count((simulator.SOLVER_DESIGNS, 1)) == 2


# ------------------------------------------------------------ serving
SERVE_KW = dict(batch_size=4, refit_every=8, refit_window=6, seed=5)


def _fleet():
    """Three stochastic designs in two envelope buckets."""
    return {"a": _cfg(12, 3), "b": _cfg(12, 4), "c": _cfg(40, 5)}


def _counters(designs, seed=0):
    rng = np.random.default_rng(seed)
    return {n: rng.integers(0, 8, (c.p, c.q)).astype(np.float32)
            for n, c in designs.items()}


def _traffic(designs, n, seed=1):
    """``n`` requests (design, series) and a flush after every fifth."""
    rng = np.random.default_rng(seed)
    streams = {d: rng.normal(size=(24, c.p)) for d, c in designs.items()}
    names = list(designs)
    events = []
    for k in range(n):
        d = names[int(rng.integers(len(names)))]
        events.append((d, int(rng.integers(24))))
        if k % 5 == 4:
            events.append(None)
    return streams, events


def _base(svc, name):
    """The next volley base of the bucket that serves ``name``."""
    return next(b["stream_base"] for b in svc.buckets() if name in b["designs"])


def _serve(svc, streams, events):
    handles = []
    for ev in events:
        if ev is None:
            svc.flush()
        else:
            handles.append(svc.submit(streams[ev[0]][ev[1]], ev[0]))
    svc.flush()
    return [h.result().cluster for h in handles]


def test_service_equals_the_reference_replay_over_refits_and_buckets():
    """Answers equal and counters bit-identical to the plain reference of
    the service (``bench/reference_stochastic_serve.py``) over several
    re-fits of two buckets, each re-fit resuming its design's stream."""
    import reference_stochastic_serve
    from repro.serve import ClusteringService

    designs = _fleet()
    w0 = _counters(designs)
    streams, events = _traffic(designs, 60)
    svc = ClusteringService(designs, weights=w0, **SERVE_KW)
    assert len(svc.buckets()) == 2
    svc.warmup()
    assert all(_base(svc, n) == 0 for n in designs)  # warm-up draws nothing
    got = _serve(svc, streams, events)
    assert svc.stats().refits >= 4

    encodes = {n: reference.encode(streams[n], c.t_max) for n, c in designs.items()}
    sim = reference_stochastic_serve.ServiceReplay(
        {n: (c.p, c.q, c.t_max, c.neuron.threshold) for n, c in designs.items()},
        w0, encodes, seed=SERVE_KW["seed"], batch_size=4, refit_every=8,
        refit_window=6, refit_epochs=1, statics=_statics(designs["a"]),
    )
    for ev in events:
        sim.flush() if ev is None else sim.submit(*ev)
    sim.flush()
    want = []
    for name, series, version in sim.requests:
        c = designs[name]
        want.append(int(reference.assign(
            sim.versions[name][version], encodes[name][series][None],
            jnp.float32(c.neuron.threshold), t_max=c.t_max, dtype=jnp.float32)[0]))
    assert got == want
    for n in designs:
        assert len(sim.versions[n]) > 2, "every design re-fits more than once"
        np.testing.assert_array_equal(svc.weights(n), np.asarray(sim.versions[n][-1]))
        assert _base(svc, n) == sim.bases[n]


@pytest.mark.parametrize("lowering", ["reference", "interpret"])
@pytest.mark.parametrize("v0", [0, 37, 2**31 - 64])
def test_fit_padded_from_a_stream_index_equals_the_solver_and_reference(lowering, v0):
    """``fit_padded(v0=k)`` draws volley n of epoch e at index k + e N + n,
    as the 'cycle' solver and the reference do; two chained fits equal
    one over the joined volleys."""
    import reference_stochastic_serve

    cfg = _cfg(24, 3)
    xs = _volleys(24, 4)
    w0 = np.asarray(column.init_params(jax.random.key(6), cfg)["w"])
    key = stdp.stream_key(jax.random.key(7))
    ops = (jnp.asarray([cfg.neuron.threshold], jnp.float32),
           jnp.asarray([T_MAX], TIME_DTYPE), jnp.asarray([3], TIME_DTYPE))
    kw = dict(t_window=T_MAX, w_max=7, wta_k=1, mu_capture=0.5, mu_backoff=0.5,
              mu_search=2.0 ** -10, stabilize=True, response="rnl", lowering=lowering)

    def fused(w, x, v, epochs):
        return np.asarray(backend.fit_padded(
            jnp.array(w)[None], x[:, None, :], *ops, epochs=epochs, keys=key[None],
            v0=v, **kw)[0])

    got = fused(w0, xs, v0, EPOCHS)
    cyc = np.asarray(backend.solver_fit_stream(
        w0, xs, cfg, epochs=EPOCHS, stream_key=key, v0=v0))
    ref = np.asarray(reference_stochastic_serve.fit(
        jnp.asarray(w0), xs, jnp.float32(cfg.neuron.threshold),
        jnp.asarray(key).view(jnp.uint32), jnp.uint32(v0), t_max=T_MAX, epochs=EPOCHS,
        statics=_statics(cfg), dtype=jnp.float32))
    assert np.abs(got - w0).sum() > 0, "training must move"
    np.testing.assert_array_equal(got, cyc)
    np.testing.assert_array_equal(got, ref)
    # another start draws other bits
    assert not np.array_equal(fused(w0, xs, v0 + 1, EPOCHS), got)
    # two re-fits, the second resuming where the first stopped
    half = N // 2
    chained = fused(fused(w0, xs[:half], v0, 1), xs[half:], v0 + half, 1)
    np.testing.assert_array_equal(chained, fused(w0, xs, v0, 1))


def _durable(designs, w0, path, **kw):
    from repro.serve import ClusteringService

    return ClusteringService(designs, weights=w0, durable_dir=str(path),
                             snapshot_every=2, **dict(SERVE_KW, **kw))


def test_recover_restores_counters_and_stream_bases_bit_identical(tmp_path):
    """Killed after two snapshots and a WAL tail, the recovered service
    holds the live service's counters and bases, and goes on serving the
    bucket that had just re-fit as the live service does."""
    from repro.serve import ClusteringService

    designs = _fleet()
    w0 = _counters(designs)
    streams, events = _traffic(designs, 90)
    live = _durable(designs, w0, tmp_path / "live")
    live.warmup()
    _serve(live, streams, events)
    # serve bucket {a, b} on until its re-fit is the WAL's newest record:
    # it then holds no served-but-unrefit volleys, which a kill would lose
    base = _base(live, "a")
    for k in range(80):
        if live.stats().wal_records and _base(live, "a") != base:
            break
        base = _base(live, "a")
        live.submit(streams["a"][k % 24], "a")
    st = live.stats()
    assert st.snapshots >= 2 and st.wal_records >= 1, st
    recs = durability.VolleyWAL(str(tmp_path / "live" / "wal.jsonl")).validate(
        live._store.fingerprint)
    assert all({"keys", "v_base"} <= set(r) for r in recs)
    # the process dies here: nothing is drained or flushed
    back = ClusteringService.recover(str(tmp_path / "live"))
    assert back.stats().replayed == st.wal_records
    for n in designs:
        np.testing.assert_array_equal(back.weights(n), live.weights(n))
        assert _base(back, n) == _base(live, n) > 0
    back.warmup()
    more_streams, more = _traffic({n: designs[n] for n in "ab"}, 30, seed=2)
    assert _serve(back, more_streams, more) == _serve(live, more_streams, more)
    for n in designs:
        np.testing.assert_array_equal(back.weights(n), live.weights(n))
        assert _base(back, n) == _base(live, n)


def test_a_degraded_refit_leaves_the_stream_base_unchanged(monkeypatch):
    """Every rung down: the bucket serves from its last counters and its
    base stays.  Fused rungs down alone: the 'cycle' rung commits the
    counters and base the unfaulted service reaches."""
    from repro.serve import ClusteringService
    from repro.testing import faults

    designs = {"a": _cfg(12, 3), "b": _cfg(12, 4)}
    w0 = _counters(designs)
    streams, events = _traffic(designs, 12)
    clean = ClusteringService(designs, weights=w0, **SERVE_KW)
    want = _serve(clean, streams, events)
    assert clean.stats().refits == 1

    monkeypatch.setattr(fused_column, "fit_scan_padded",
                        faults.fail_always(fused_column.fit_scan_padded))
    fused_down = ClusteringService(designs, weights=w0, **SERVE_KW)
    assert fused_down.buckets()[0]["fit_lowering"] == "reference"
    with pytest.warns(RuntimeWarning):
        assert _serve(fused_down, streams, events) == want
    assert fused_down.buckets()[0]["last_fit_lowering"] == "cycle"
    for n in designs:
        np.testing.assert_array_equal(fused_down.weights(n), clean.weights(n))
        assert _base(fused_down, n) == _base(clean, n) == 6

    monkeypatch.setattr(backend, "solver_fit_stream",
                        faults.fail_always(backend.solver_fit_stream))
    down = ClusteringService(designs, weights=w0, **SERVE_KW)
    with pytest.warns(RuntimeWarning):
        _serve(down, streams, events)
    assert down.stats().refit_failures == 1 and down.stats().degraded == 1
    for n in designs:
        np.testing.assert_array_equal(down.weights(n), w0[n])
        assert _base(down, n) == 0


def test_expected_mode_keeps_its_aot_key_and_results():
    """An expected-mode fit keeps the key it had before the stream offset
    (no stochastic operands), its result is the jit path's, a stream
    offset without stream keys is refused, and the service keeps no
    stream state for it."""
    from repro.serve import ClusteringService

    cfg = ColumnConfig(p=24, q=3, t_max=T_MAX)
    cfg = cfg.with_threshold(simulator.suggest_threshold(cfg))
    xs = _volleys(24, 5)
    w0 = np.asarray(column.init_params(jax.random.key(8), cfg)["w"])
    ops = (jnp.asarray([cfg.neuron.threshold], jnp.float32),
           jnp.asarray([T_MAX], TIME_DTYPE), jnp.asarray([3], TIME_DTYPE))
    kw = dict(t_window=T_MAX, w_max=7, wta_k=1, mu_capture=0.5, mu_backoff=0.5,
              mu_search=2.0 ** -10, stabilize=True, response="rnl", epochs=EPOCHS,
              lowering="reference")
    got = backend.fit_padded(jnp.array(w0)[None], xs[:, None, :], *ops, **kw)
    v_blk, t_blk = backend._plan_blocks("fit", "reference", 1, 24, 3, T_MAX, N, EPOCHS,
                                        7, "rnl", None, None)
    key = ("fit", (1, 24, 3), (N, 1, 24), T_MAX, 7, 1, True, "rnl", EPOCHS,
           "reference", t_blk, v_blk)
    assert backend._fit_key((1, 24, 3), (N, 1, 24), T_MAX, 7, 1, True, "rnl", EPOCHS,
                            "reference", t_blk, v_blk) == key
    assert key in backend._AOT_CACHE
    jit = fused_column.fit_scan_padded(jnp.array(w0)[None], xs[:, None, :], *ops, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(jit))
    with pytest.raises(ValueError, match="stochastic STDP only"):
        backend.fit_padded(jnp.array(w0)[None], xs[:, None, :], *ops, v0=3, **kw)
    svc = ClusteringService({"e": cfg}, batch_size=4, refit_every=8, refit_window=6)
    assert svc.buckets()[0]["stream_base"] is None


def test_service_refuses_fractional_counters_for_stochastic_designs():
    from repro.serve import ClusteringService

    cfg = _cfg(24, 3)
    with pytest.raises(ValueError, match="integral"):
        ClusteringService({"a": cfg}, weights={"a": np.full((24, 3), 2.5, np.float32)})


# ------------------------------------------------------------ exploration
def _explore_stream():
    rng = np.random.default_rng(21)
    return rng.normal(size=(N, 24)), rng.integers(0, 3, N)


SPACE = dse.DesignSpace(q=(3, 4), t_max=(16,), threshold_scale=(0.8, 1.1),
                        stdp=STOCH)


def test_explore_equals_per_design_solver_runs():
    x, y = _explore_stream()
    res = dse.explore(x, y, SPACE, epochs=EPOCHS, seed=13)
    assert len(res.points) == SPACE.size()
    root, init_key = jax.random.split(jax.random.key(13))
    for pt in res.points:
        assert pt.cfg.stdp == STOCH
        w0 = column.init_params(jax.random.fold_in(init_key, pt.index), pt.cfg)
        xs = simulator._encode(jnp.asarray(x), pt.cfg, "latency")
        w = column.fit(w0, xs, pt.cfg, EPOCHS, mode="cycle",
                       rng=jax.random.fold_in(root, pt.index))["w"]
        np.testing.assert_array_equal(np.asarray(w), np.asarray(pt.params["w"]))


def test_explore_journal_kill_and_resume_is_bit_identical(tmp_path):
    x, y = _explore_stream()
    path = tmp_path / "stoch.jsonl"

    class Killed(Exception):
        pass

    class KillingJournal(dse.Journal):
        def append(self, records):
            super().append(records)
            raise Killed  # the run dies after its first published bucket

    with pytest.raises(Killed):
        dse.explore(x, y, SPACE, epochs=EPOCHS, seed=13, max_bucket=1,
                    journal=KillingJournal(str(path)))
    resumed = dse.explore(x, y, SPACE, epochs=EPOCHS, seed=13, max_bucket=1,
                          journal=str(path), resume=True)
    assert resumed.meta["resumed"] == 1
    full = dse.explore(x, y, SPACE, epochs=EPOCHS, seed=13)
    for a, b in zip(full.points, resumed.points):
        assert a.index == b.index and a.rand_index == b.rand_index
        np.testing.assert_array_equal(np.asarray(a.params["w"]), np.asarray(b.params["w"]))
    # the header records the rule: the journal will not resume another
    expected = dse.DesignSpace(q=(3, 4), t_max=(16,), threshold_scale=(0.8, 1.1))
    with pytest.raises(ValueError, match="stdp"):
        dse.explore(x, y, expected, epochs=EPOCHS, seed=13, journal=str(path),
                    resume=True)


# ------------------------------------------------------------ the control
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_reference_in_bfloat16_fails_the_weight_limit(seed):
    """The cell's ``weight_gap`` limit (1e-3 of w_max) fails when the
    reference runs in bfloat16 in float32's place (4 epochs: at this
    size 2 can pass without a single draw decided otherwise)."""
    import json

    with open(os.path.join(ROOT, "bench", "traffic", "ws-sweep-stochastic.json")) as f:
        limit = json.load(f)["limits"]["weight_gap"]
    cfg = _cfg(40, 5)
    xs = _volleys(40, 8 + seed)
    w0 = column.init_params(jax.random.key(8 + seed), cfg)["w"]
    key = jax.random.key(9 + seed)
    f32 = _reference_fit(cfg, w0, xs, key, epochs=4)
    bf16 = _reference_fit(cfg, w0, xs, key, dtype=jnp.bfloat16, epochs=4)
    assert np.max(np.abs(f32 - bf16)) / 7 > limit
    # the assign of integer counters fires alike on the float and the grid path
    thr = jnp.float32(cfg.neuron.threshold)
    ids = reference.assign(jnp.asarray(f32), xs, thr, t_max=T_MAX, dtype=jnp.float32)
    asg = fused_column.assign_padded(
        jnp.asarray(f32)[None], xs[:, None, :], jnp.asarray([cfg.neuron.threshold]),
        jnp.asarray([T_MAX], TIME_DTYPE), jnp.asarray([5], TIME_DTYPE),
        t_window=T_MAX, wta_k=1, response="rnl", lowering="interpret", w_max=7,
    )
    np.testing.assert_array_equal(np.asarray(asg[0]), np.asarray(ids))
