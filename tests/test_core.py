"""TNN core behaviour: solver equivalence, WTA, STDP, encodings, networks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import column, encoding, network, neuron, stdp, wta
from repro.core.types import (
    ColumnConfig, LayerConfig, NetworkConfig, NeuronConfig, STDPConfig,
    WTAConfig,
)


# ---------------------------------------------------------------- neurons
@settings(max_examples=30, deadline=None)
@given(
    p=st.integers(2, 24),
    q=st.integers(1, 5),
    t_max=st.integers(4, 48),
    thr=st.floats(0.5, 40.0),
    seed=st.integers(0, 2**31 - 1),
    resp=st.sampled_from(["rnl", "snl"]),
)
def test_event_equals_cycle(p, q, t_max, thr, seed, resp):
    """The paper's event-driven fast path must be bit-identical to the
    cycle-accurate hardware-semantics path for RNL and SNL."""
    rng = np.random.default_rng(seed)
    t_in = jnp.asarray(rng.integers(0, t_max + 4, (3, p)), jnp.int32)
    w = jnp.asarray(rng.uniform(0, 7, (p, q)), jnp.float32)
    cfg = NeuronConfig(response=resp, threshold=thr)
    ev = neuron.fire_times(t_in, w, cfg, t_max, "event")
    cy = neuron.fire_times(t_in, w, cfg, t_max, "cycle")
    np.testing.assert_array_equal(np.asarray(ev), np.asarray(cy))


@settings(max_examples=20, deadline=None)
@given(
    p=st.integers(2, 16),
    t_max=st.integers(8, 32),
    seed=st.integers(0, 2**31 - 1),
)
def test_firing_time_monotone_in_threshold(p, t_max, seed):
    """V is nondecreasing => a higher threshold can never fire earlier."""
    rng = np.random.default_rng(seed)
    t_in = jnp.asarray(rng.integers(0, t_max, (2, p)), jnp.int32)
    w = jnp.asarray(rng.uniform(0, 7, (p, 3)), jnp.float32)
    lo = neuron.fire_times(t_in, w, NeuronConfig(threshold=2.0), t_max, "event")
    hi = neuron.fire_times(t_in, w, NeuronConfig(threshold=9.0), t_max, "event")
    assert np.all(np.asarray(hi) >= np.asarray(lo))


def test_no_input_no_spike():
    t_in = jnp.full((1, 5), 99, jnp.int32)  # all silent (t_max=32)
    w = jnp.ones((5, 2), jnp.float32) * 7
    out = neuron.fire_times(t_in, w, NeuronConfig(threshold=1.0), 32, "event")
    assert np.all(np.asarray(out) == 32)


def test_lif_leak_delays_or_prevents_firing():
    t_in = jnp.asarray([[0, 4, 8]], jnp.int32)
    w = jnp.ones((3, 1), jnp.float32) * 2
    no_leak = neuron.fire_times(t_in, w, NeuronConfig(response="lif", threshold=5.0, leak=0.0), 32, "cycle")
    leak = neuron.fire_times(t_in, w, NeuronConfig(response="lif", threshold=5.0, leak=1.0), 32, "cycle")
    assert np.asarray(leak)[0, 0] >= np.asarray(no_leak)[0, 0]


# ---------------------------------------------------------------- WTA
@settings(max_examples=25, deadline=None)
@given(
    q=st.integers(2, 8),
    k=st.integers(1, 3),
    seed=st.integers(0, 2**31 - 1),
)
def test_wta_winner_count(q, k, seed):
    k = min(k, q)
    rng = np.random.default_rng(seed)
    t = jnp.asarray(rng.integers(0, 17, (4, q)), jnp.int32)  # 16 == no spike
    out, win = wta.wta(t, WTAConfig(k=k, tie_break="index"), 16)
    win = np.asarray(win)
    spikes = np.asarray(t) < 16
    assert np.all(win.sum(-1) <= np.minimum(k, spikes.sum(-1)))
    # winners must be the earliest spikes
    out = np.asarray(out)
    for b in range(win.shape[0]):
        if win[b].any():
            assert out[b][win[b]].max() <= np.where(~win[b], np.asarray(t)[b], 0).max() or win[b].all()


def test_wta_tie_break_index_picks_lowest():
    t = jnp.asarray([[5, 5, 9]], jnp.int32)
    out, win = wta.wta(t, WTAConfig(k=1, tie_break="index"), 16)
    assert np.asarray(win).tolist() == [[True, False, False]]


def test_wta_tie_break_all_shares():
    t = jnp.asarray([[5, 5, 9]], jnp.int32)
    out, win = wta.wta(t, WTAConfig(k=1, tie_break="all"), 16)
    assert np.asarray(win).tolist() == [[True, True, False]]


# ---------------------------------------------------------------- STDP
@settings(max_examples=25, deadline=None)
@given(
    p=st.integers(2, 12),
    q=st.integers(1, 4),
    seed=st.integers(0, 2**31 - 1),
    mode=st.sampled_from(["expected", "stochastic"]),
)
def test_stdp_weights_stay_bounded(p, q, seed, mode):
    """Weights stay in [0, w_max]; under the stochastic stream, integer
    counters stay integers and move at most one LSB per volley."""
    rng = np.random.default_rng(seed)
    if mode == "stochastic":
        w = jnp.asarray(rng.integers(0, 8, (p, q)), jnp.float32)
    else:
        w = jnp.asarray(rng.uniform(0, 7, (p, q)), jnp.float32)
    x = jnp.asarray(rng.integers(0, 20, (p,)), jnp.int32)
    y = jnp.asarray(rng.integers(0, 20, (q,)), jnp.int32)
    cfg = STDPConfig(mode=mode)
    w2 = stdp.stdp_update(
        w, x, y, cfg, 7, 16, rng=jax.random.key(seed), volley=seed % 4096
    )
    w2 = np.asarray(w2)
    assert np.all(w2 >= 0) and np.all(w2 <= 7)
    if mode == "stochastic":
        assert np.array_equal(w2, np.round(w2))
        assert np.max(np.abs(w2 - np.asarray(w))) <= 1


def test_stdp_capture_increases_weight():
    w = jnp.full((1, 1), 3.0)
    x = jnp.asarray([2], jnp.int32)
    y = jnp.asarray([5], jnp.int32)  # x before y -> capture
    w2 = stdp.stdp_update(w, x, y, STDPConfig(), 7, 16)
    assert float(w2[0, 0]) > 3.0


def test_stdp_backoff_decreases_weight():
    w = jnp.full((1, 1), 3.0)
    x = jnp.asarray([9], jnp.int32)
    y = jnp.asarray([5], jnp.int32)  # y before x -> backoff
    w2 = stdp.stdp_update(w, x, y, STDPConfig(), 7, 16)
    assert float(w2[0, 0]) < 3.0


def test_stdp_neither_spike_no_change():
    w = jnp.full((2, 2), 3.0)
    x = jnp.asarray([16, 16], jnp.int32)
    y = jnp.asarray([16, 16], jnp.int32)
    w2 = stdp.stdp_update(w, x, y, STDPConfig(), 7, 16)
    np.testing.assert_allclose(np.asarray(w2), 3.0)


# ---------------------------------------------------------------- encoding
def test_latency_encode_order():
    x = jnp.asarray([[0.1, 0.9, 0.5]])
    t = np.asarray(encoding.latency_encode(x, 32))
    assert t[0, 1] < t[0, 2] < t[0, 0]  # larger value -> earlier spike


def test_onoff_encode_channels():
    x = jnp.asarray([[1.0, -1.0, 0.0, 2.0]])
    t = np.asarray(encoding.onoff_encode(x, 32))
    assert t.shape == (1, 8)
    on, off = t[0, :4], t[0, 4:]
    assert on[1] == 32 and off[1] < 32  # negative dev -> off channel spikes


# ---------------------------------------------------------------- column/network
def test_column_train_changes_weights_and_clusters():
    cfg = ColumnConfig(p=16, q=3, t_max=32)
    cfg = cfg.with_threshold(8.0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 32, (12, 16)), jnp.int32)
    params = column.init_params(jax.random.key(0), cfg)
    p2, y = column.train_step(params, x, cfg)
    assert float(jnp.abs(p2["w"] - params["w"]).sum()) > 0
    a = column.cluster_assignments(p2, x, cfg)
    assert np.asarray(a).shape == (12,)
    assert np.all((np.asarray(a) >= 0) & (np.asarray(a) <= 3))


def test_multilayer_network_shapes():
    col1 = ColumnConfig(p=8, q=4, t_max=16).with_threshold(4.0)
    col2 = ColumnConfig(p=8, q=2, t_max=16).with_threshold(4.0)
    net = NetworkConfig(layers=(
        LayerConfig(columns=2, column=col1, connectivity="full"),
        LayerConfig(columns=1, column=col2, connectivity="full"),
    ))
    params = network.init_params(jax.random.key(0), net, in_width=8)
    x = jnp.asarray(np.random.default_rng(0).integers(0, 16, (5, 8)), jnp.int32)
    out = network.apply(params, x, net)
    assert out.shape == (5, 2)
    trained = network.fit_greedy(params, x, net, epochs=2)
    out2 = network.apply(trained, x, net)
    assert out2.shape == (5, 2)


def test_network_validate_rejects_bad_widths():
    col = ColumnConfig(p=9, q=2, t_max=16)
    net = NetworkConfig(layers=(LayerConfig(columns=1, column=col),))
    with pytest.raises(ValueError):
        network.validate(net, in_width=8)
