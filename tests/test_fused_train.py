"""Fused training path: cross-backend equivalence + compile-once regression.

The contract under test (ISSUE 1 acceptance):
  * 'event', 'cycle' and the fused 'pallas' path produce BIT-IDENTICAL
    online firing times on integer weights (integer mus, no stabilizer keep
    the weights on the integer grid for the whole run, so the fused path's
    integer-grid fire is exact);
  * weights agree within float tolerance;
  * the Pallas kernel lowering (interpreter) matches the jnp reference
    lowering of the same fused step;
  * a whole fit — every epoch, every volley — triggers exactly ONE
    compilation;
  * train_step's default is the true-online rule; the legacy batch-stale
    fold survives as update='batch' and is genuinely different.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend, column, simulator
from repro.core.types import ColumnConfig, NeuronConfig, STDPConfig
from repro.kernels import fused_column, ref
from repro.kernels.rnl_response import rnl_fire_pallas


def int_cfg(p=19, q=4, t_max=24, threshold=7.0, w_max=7, k=1):
    """Config whose expected-STDP updates keep weights integer-valued."""
    return ColumnConfig(
        p=p, q=q, t_max=t_max,
        neuron=NeuronConfig(threshold=threshold, w_max=w_max),
        stdp=STDPConfig(
            mu_capture=1.0, mu_backoff=1.0, mu_search=1.0, stabilizer="none"
        ),
    )


def int_data(cfg, n=12, seed=0):
    rng = np.random.default_rng(seed)
    params = {
        "w": jnp.asarray(
            rng.integers(0, cfg.neuron.w_max + 1, (cfg.p, cfg.q)), jnp.float32
        )
    }
    x = jnp.asarray(rng.integers(0, cfg.t_max + 6, (n, cfg.p)), jnp.int32)
    return params, x


def test_backends_bit_identical_firing_times_on_integer_weights():
    cfg = int_cfg()
    params, x = int_data(cfg)
    outs = {}
    for name in ("event", "cycle", "pallas"):
        p2, ys = backend.get(name).fit(params, x, cfg, name, 3, None, True, None)
        outs[name] = (np.asarray(p2["w"]), np.asarray(ys))
    for name in ("cycle", "pallas"):
        np.testing.assert_array_equal(
            outs["event"][1], outs[name][1],
            err_msg=f"firing times diverge: event vs {name}",
        )
        np.testing.assert_allclose(
            outs["event"][0], outs[name][0], rtol=1e-6, atol=1e-6,
            err_msg=f"weights diverge: event vs {name}",
        )


def test_fused_interpret_kernel_matches_reference_lowering():
    """The actual Pallas kernel (interpreter) == jnp lowering, full fit."""
    cfg = ColumnConfig(p=13, q=3, t_max=16, neuron=NeuronConfig(threshold=5.0))
    params, x = int_data(cfg, n=6, seed=1)
    p_ref, y_ref = fused_column.fit_fused(
        params, x, cfg, epochs=2, lowering="reference", trace=True
    )
    p_int, y_int = fused_column.fit_fused(
        params, x, cfg, epochs=2, lowering="interpret", trace=True
    )
    np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_int))
    np.testing.assert_allclose(
        np.asarray(p_ref["w"]), np.asarray(p_int["w"]), rtol=1e-6, atol=1e-6
    )


def test_fused_matches_cycle_mode_firing_times():
    """Acceptance: fused firing times bit-identical to mode='cycle'."""
    cfg = int_cfg(p=31, q=5, t_max=40, threshold=11.0)
    params, x = int_data(cfg, n=10, seed=2)
    _, ys_fused = fused_column.fit_fused(
        params, x, cfg, epochs=2, lowering="reference", trace=True
    )
    _, ys_cycle = backend.get("cycle").fit(
        params, x, cfg, "cycle", 2, None, True, None
    )
    np.testing.assert_array_equal(np.asarray(ys_fused), np.asarray(ys_cycle))


def test_fit_compiles_exactly_once_across_epochs():
    cfg = int_cfg(p=17, q=3, t_max=20)  # unique geometry -> fresh cache key
    params, x = int_data(cfg, n=8, seed=3)
    assert backend.resolve("auto", cfg, training=True) == "pallas"
    fn = fused_column._fused_fit_scan
    before = fn._cache_size()
    column.fit(params, x, cfg, epochs=6)
    after_first = fn._cache_size()
    assert after_first == before + 1, "fit must compile exactly once"
    column.fit(params, x, cfg, epochs=6)
    assert fn._cache_size() == after_first, "refit must not recompile"


def test_train_step_online_default_differs_from_batch_stale():
    """Batch mode computes every winner from stale pre-batch weights; the
    online default must fold each volley before the next one fires."""
    cfg = ColumnConfig(
        p=4, q=2, t_max=16,
        neuron=NeuronConfig(threshold=6.0, w_max=7),
        stdp=STDPConfig(
            mu_capture=1.0, mu_backoff=1.0, mu_search=2.0, stabilizer="none"
        ),
    )
    # neuron 0 starts dead (w=0) and never fires from stale weights; online,
    # mu_search pumps it up each volley until it ties neuron 1 and steals
    # the win via the index tie-break — impossible under the stale fold.
    params = {
        "w": jnp.asarray([[0.0, 2.0]] * 4, jnp.float32)  # [p=4, q=2]
    }
    x = jnp.zeros((4, 4), jnp.int32)  # the same volley, 4 times
    p_on, y_on = column.train_step(params, x, cfg, update="online")
    p_ba, y_ba = column.train_step(params, x, cfg, update="batch")
    assert np.asarray(y_ba).std(axis=0).max() == 0  # stale: identical rows
    assert np.asarray(y_on).std(axis=0).max() > 0  # online: winner flips
    diff = np.abs(np.asarray(p_on["w"]) - np.asarray(p_ba["w"])).max()
    assert diff > 0, "online and batch folds should diverge on repeated input"


def test_train_step_online_equals_sequential_single_steps():
    cfg = int_cfg(p=11, q=3, t_max=16, threshold=5.0)
    params, x = int_data(cfg, n=5, seed=5)
    p_scan, ys = column.train_step(params, x, cfg)
    p_seq = params
    for i in range(x.shape[0]):
        p_seq, yi = column.train_step(p_seq, x[i : i + 1], cfg)
        np.testing.assert_array_equal(np.asarray(ys[i]), np.asarray(yi[0]))
    np.testing.assert_allclose(
        np.asarray(p_scan["w"]), np.asarray(p_seq["w"]), rtol=1e-6, atol=1e-6
    )


def test_kernel_interpret_default_is_central():
    """rnl_fire_pallas with interpret unset must follow the central policy
    (interpreter off-TPU) and still match the oracle."""
    rng = np.random.default_rng(6)
    t_in = jnp.asarray(rng.integers(0, 40, (4, 21)), jnp.int32)
    w = jnp.asarray(rng.integers(0, 8, (21, 3)), jnp.float32)
    got = rnl_fire_pallas(t_in, w, 9.0, 32, 7)  # no interpret kwarg
    want = ref.rnl_fire_ref(t_in, w, 9.0, 32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert backend.pallas_interpret() == (jax.default_backend() != "tpu")


def test_design_sweep_matches_single_design_fit():
    """The padded multi-design vmap must reproduce the single-design fused
    fit exactly for each member design (incl. the non-envelope one)."""
    # designs share the stream (p fixed by the encoder) but differ in q,
    # t_max and threshold — the non-envelope design exercises the masking
    small = ColumnConfig(p=14, q=2, t_max=12).with_threshold(4.0)
    big = ColumnConfig(p=14, q=3, t_max=20).with_threshold(6.0)
    cfgs = [small, big]
    rng = np.random.default_rng(7)
    series = rng.normal(size=(10, 14))
    labels = rng.integers(0, 2, 10)

    sweep = simulator.cluster_time_series_many(series, labels, cfgs, epochs=2, seed=3)

    # replicate the sweep's per-design init-key derivation
    rng_key = jax.random.key(3)
    _, init_key = jax.random.split(rng_key)
    keys = jax.random.split(init_key, len(cfgs))
    from repro.core import encoding

    for i, cfg in enumerate(cfgs):
        params0 = column.init_params(keys[i], cfg)
        volleys = encoding.latency_encode(jnp.asarray(series), cfg.t_max)
        p_fit, _ = fused_column.fit_fused(
            params0, volleys, cfg, epochs=2, lowering="reference"
        )
        np.testing.assert_allclose(
            np.asarray(sweep[i].params["w"]), np.asarray(p_fit["w"]),
            rtol=1e-5, atol=1e-5,
            err_msg=f"sweep weights diverge for design {i}",
        )
        asg = column.cluster_assignments(p_fit, volleys, cfg, "auto")
        np.testing.assert_array_equal(sweep[i].assignments, np.asarray(asg))


def test_fused_rejects_unsupported_configs():
    lif = ColumnConfig(p=8, q=2, t_max=16, neuron=NeuronConfig(response="lif"))
    with pytest.raises(ValueError):
        fused_column.check_fusable(lif, "reference")
    assert backend.resolve("auto", lif, training=True) == "cycle"
    # stochastic STDP is inside the fused contract: it trains fused
    stoch = ColumnConfig(p=8, q=2, t_max=16, stdp=STDPConfig(mode="stochastic"))
    fused_column.check_fusable(stoch, "mosaic")
    assert backend.resolve("auto", stoch, training=True) == "pallas"
    # forcing the pallas forward on LIF must raise, not silently run RNL/SNL
    params = {"w": jnp.ones((8, 2), jnp.float32)}
    x = jnp.zeros((3, 8), jnp.int32)
    with pytest.raises(ValueError, match="pallas forward"):
        column.apply(params, x, lif, "pallas")
    # a single-design sweep must validate its (only) config too: LIF is
    # refused, a stochastic design is swept
    rng = np.random.default_rng(8)
    series = rng.normal(size=(6, 8))
    with pytest.raises(ValueError):
        simulator.cluster_time_series_many(series, None, [lif], epochs=1)
    (res,) = simulator.cluster_time_series_many(
        series, None, [stoch], epochs=1
    )
    assert res.lowering == backend.padded_lowering("rnl")
