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
resume.
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


def test_service_still_refuses_stochastic_designs():
    from repro.serve import ClusteringService

    with pytest.raises(ValueError, match="expected-mode STDP only"):
        ClusteringService({"a": _cfg(24, 3)})


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
