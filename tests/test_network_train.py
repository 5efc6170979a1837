"""Multi-layer fused training path (ISSUE 2 acceptance).

The contract under test:
  * ``network.fit_greedy`` resolves the backend per layer through
    ``backend.resolve`` — same knob semantics as columns;
  * on integer weights, 'pallas', 'cycle', 'event' and 'auto' produce
    BIT-IDENTICAL network outputs and matching weights for a 2-layer net;
  * the fused layer scan compiles once per distinct layer shape (layers
    sharing a padded-envelope shape share one trace) and refits recompile
    nothing;
  * non-fusable layers (LIF, stochastic STDP) train on the solver scan
    under 'auto', and forcing mode='pallas' on them raises;
  * ``simulator.cluster_time_series_network`` plugs networks into the same
    encode -> fit -> assign -> rand-index loop as columns.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend, network, simulator
from repro.core.types import (
    ColumnConfig, LayerConfig, NetworkConfig, NeuronConfig, STDPConfig,
)
from repro.kernels import fused_column


def int_col(p, q, t_max, threshold):
    """Column whose expected-STDP updates keep weights on the integer grid."""
    return ColumnConfig(
        p=p, q=q, t_max=t_max,
        neuron=NeuronConfig(threshold=threshold, w_max=7),
        stdp=STDPConfig(
            mu_capture=1.0, mu_backoff=1.0, mu_search=1.0, stabilizer="none"
        ),
    )


def two_layer_net(t_max=16):
    return NetworkConfig(layers=(
        LayerConfig(columns=2, column=int_col(8, 4, t_max, 5.0)),
        LayerConfig(columns=1, column=int_col(8, 2, t_max, 4.0)),
    ))


def int_net_data(net, in_width, n=10, seed=0):
    rng = np.random.default_rng(seed)
    params = [
        {
            "w": jnp.asarray(
                rng.integers(
                    0, l.column.neuron.w_max + 1,
                    (l.columns, l.column.p, l.column.q),
                ),
                jnp.float32,
            )
        }
        for l in net.layers
    ]
    x = jnp.asarray(rng.integers(0, 20, (n, in_width)), jnp.int32)
    return params, x


def test_network_backends_bit_identical_on_integer_weights():
    """Acceptance: fit_greedy firing times bit-identical across backends."""
    net = two_layer_net()
    params, x = int_net_data(net, in_width=8)
    outs = {}
    for mode in ("pallas", "cycle", "event", "auto"):
        trained = network.fit_greedy(params, x, net, epochs=3, mode=mode)
        # compare on a fixed forward so only training differs between modes
        y = network.apply(trained, x, net, "cycle")
        outs[mode] = (np.asarray(y), [np.asarray(p["w"]) for p in trained])
    for mode in ("cycle", "event", "auto"):
        np.testing.assert_array_equal(
            outs["pallas"][0], outs[mode][0],
            err_msg=f"network firing times diverge: pallas vs {mode}",
        )
        for li, (a, b) in enumerate(zip(outs["pallas"][1], outs[mode][1])):
            np.testing.assert_allclose(
                a, b, rtol=1e-6, atol=1e-6,
                err_msg=f"layer {li} weights diverge: pallas vs {mode}",
            )


def test_network_fit_compiles_once_per_layer_shape(compile_counter):
    """Layers padded to the same envelope shape share ONE compiled scan;
    refitting the same network recompiles nothing.

    Counted at the ``backend_compile`` seam (``compile_counter``), not via
    ``_cache_size()``: the network routes through ``backend.fit_padded``'s
    AOT executable cache, which never touches the jit trace cache.
    """
    # unique geometry (t_max=18) so this test owns its envelope keys:
    # layers 0 and 1 both vmap 2 columns in the (p=10, q=3, 18) envelope
    # -> one shared executable; layer 2 (1 column) -> a second one.
    net = NetworkConfig(layers=(
        LayerConfig(columns=2, column=int_col(10, 3, 18, 5.0)),
        LayerConfig(columns=2, column=int_col(6, 3, 18, 4.0)),
        LayerConfig(columns=1, column=int_col(6, 2, 18, 4.0)),
    ))
    params, x = int_net_data(net, in_width=10, n=9, seed=1)
    for layer in net.layers:
        assert backend.resolve("auto", layer.column, training=True) == "pallas"
    backend.aot_cache_clear()
    trained = network.fit_greedy(params, x, net, epochs=4, mode="auto")
    after_first = compile_counter.named("fit_scan_padded")
    assert after_first == 2, (
        "3 layers / 2 distinct padded shapes must compile exactly 2 scans"
    )
    network.fit_greedy(params, x, net, epochs=4, mode="auto")
    assert compile_counter.named("fit_scan_padded") == after_first, (
        "refit must not recompile"
    )
    assert trained[0]["w"].shape == (2, 10, 3)
    assert trained[2]["w"].shape == (1, 6, 2)


def test_validate_rejects_growing_t_max():
    """A larger downstream window would read the upstream no-spike sentinel
    as a live spike; validate must refuse loudly."""
    net = NetworkConfig(layers=(
        LayerConfig(columns=2, column=int_col(8, 4, 16, 5.0)),
        LayerConfig(columns=1, column=int_col(8, 2, 32, 4.0)),
    ))
    with pytest.raises(ValueError, match="alias"):
        network.validate(net, in_width=8)
    params, x = int_net_data(two_layer_net(), in_width=8)
    with pytest.raises(ValueError, match="alias"):
        network.fit_greedy(params, x, net, epochs=1)
    with pytest.raises(ValueError, match="alias"):  # inference guards too
        network.cluster_assignments(params, x, net)
    # shrinking windows are legal (late spikes fall outside the window)
    shrink = NetworkConfig(layers=(
        LayerConfig(columns=2, column=int_col(8, 4, 32, 5.0)),
        LayerConfig(columns=1, column=int_col(8, 2, 16, 4.0)),
    ))
    network.validate(shrink, in_width=8)


def test_envelope_waste_cap_splits_mismatched_layers():
    """A tiny layer must not ride a huge layer's padding envelope: sharing
    saves one compile, padded FLOPs recur every volley."""
    big = LayerConfig(columns=1, column=int_col(64, 4, 24, 9.0))
    small = LayerConfig(columns=1, column=int_col(4, 2, 24, 3.0))
    envs = network._fused_envelopes([big, small])
    assert envs[0] == (64, 4, 24)
    assert envs[1] == (4, 2, 24), "mismatched layer must keep its own shape"
    # close sizes DO share (the compile-once test's premise)
    near = LayerConfig(columns=1, column=int_col(48, 4, 24, 8.0))
    envs2 = network._fused_envelopes([big, near])
    assert envs2[0] == envs2[1] == (64, 4, 24)


def test_network_resolves_per_layer_and_rejects_bad_pallas():
    """'auto' routes each layer by its own config; forcing 'pallas' on a
    non-fusable layer raises instead of silently switching semantics."""
    lif_col = ColumnConfig(
        p=8, q=2, t_max=16,
        neuron=NeuronConfig(response="lif", threshold=5.0),
    )
    mixed = NetworkConfig(layers=(
        LayerConfig(columns=2, column=int_col(8, 4, 16, 5.0)),
        LayerConfig(columns=1, column=lif_col),
    ))
    assert backend.resolve("auto", mixed.layers[0].column, training=True) == "pallas"
    assert backend.resolve("auto", mixed.layers[1].column, training=True) == "cycle"
    params, x = int_net_data(mixed, in_width=8, n=6, seed=2)
    trained = network.fit_greedy(params, x, mixed, epochs=2, mode="auto")
    moved = sum(
        float(jnp.abs(t["w"] - p["w"]).sum())
        for t, p in zip(trained, params)
    )
    assert moved > 0, "mixed fused/solver network must still learn"
    with pytest.raises(ValueError):
        network.fit_greedy(params, x, mixed, epochs=2, mode="pallas")


def test_network_solver_layer_handles_stochastic_stdp():
    """A stochastic-STDP layer trains on the fused path, each column on
    its own stream from the layer's key — bit-identical to the solver
    layer scan, which draws the same streams."""
    col = ColumnConfig(
        p=6, q=3, t_max=16,
        neuron=NeuronConfig(threshold=4.0),
        stdp=STDPConfig(mode="stochastic"),
    )
    net = NetworkConfig(layers=(LayerConfig(columns=2, column=col),))
    assert backend.resolve("auto", col, training=True) == "pallas"
    params, x = int_net_data(net, in_width=6, n=5, seed=3)
    t1 = network.fit_greedy(params, x, net, epochs=2, rng=jax.random.key(7))
    t2 = network.fit_greedy(params, x, net, epochs=2, rng=jax.random.key(7))
    np.testing.assert_array_equal(
        np.asarray(t1[0]["w"]), np.asarray(t2[0]["w"]),
        err_msg="same PRNG key must reproduce stochastic training exactly",
    )
    solver = network.fit_greedy(
        params, x, net, epochs=2, mode="cycle", rng=jax.random.key(7)
    )
    np.testing.assert_array_equal(
        np.asarray(t1[0]["w"]), np.asarray(solver[0]["w"]),
        err_msg="fused and solver layers must draw the same streams",
    )
    # no key may not be silently replaced by a fixed one (column parity)
    with pytest.raises(ValueError, match="PRNG key"):
        network.fit_greedy(params, x, net, epochs=1)


def test_network_cluster_assignments_unclustered_bucket():
    net = two_layer_net()
    params, x = int_net_data(net, in_width=8, n=4, seed=4)
    a = np.asarray(network.cluster_assignments(params, x, net))
    assert a.shape == (4,)
    assert np.all((a >= 0) & (a <= network.out_width(net)))
    # silence the net: zero weights never cross threshold -> all unclustered
    dead = [{"w": jnp.zeros_like(p["w"])} for p in params]
    a0 = np.asarray(network.cluster_assignments(dead, x, net))
    np.testing.assert_array_equal(
        a0, np.full(4, network.out_width(net))
    )


def test_cluster_time_series_network_end_to_end():
    """Networks plug into the same clustering/rand-index loop as columns,
    and the run is seed-reproducible."""
    net = NetworkConfig(layers=(
        LayerConfig(columns=2, column=int_col(14, 3, 20, 5.0)),
        LayerConfig(columns=1, column=int_col(6, 2, 20, 4.0)),
    ))
    rng = np.random.default_rng(5)
    series = rng.normal(size=(12, 14))
    labels = rng.integers(0, 2, 12)
    res = simulator.cluster_time_series_network(
        series, labels, net, epochs=2, seed=3
    )
    assert res.assignments.shape == (12,)
    assert 0.0 <= res.rand_index <= 1.0
    res2 = simulator.cluster_time_series_network(
        series, labels, net, epochs=2, seed=3
    )
    np.testing.assert_array_equal(res.assignments, res2.assignments)
    # wrong encoder geometry is a loud error, as for columns
    with pytest.raises(ValueError, match="encoded width"):
        simulator.cluster_time_series_network(
            series[:, :10], labels, net, epochs=1
        )
