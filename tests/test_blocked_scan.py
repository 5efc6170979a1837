"""Volley-blocked fused scan (ISSUE 4 acceptance).

The contract under test:
  * blocking is a throughput knob, NEVER a semantic one: the blocked scan
    is BIT-IDENTICAL to the per-volley scan (``v_blk=1``) for every block
    size, including blocks that do not divide the volley count (the tail
    is silent-padded and a silent volley is an exact weight no-op);
  * the volley-blocked kernel (interpreter standing in for Mosaic
    off-TPU) — one kernel invocation per block, in-kernel sequential
    ``fori_loop``, VMEM-resident weights — matches the reference blocked
    body exactly on heterogeneous padded design batches;
  * a padded D=1 blocked fit stays bit-identical to ``mode='cycle'`` on
    integer weights (the fused contract, end to end through blocking),
    also at each of the paper's seven Table II designs;
  * the batched assignment pass (``assign_padded``) equals per-design,
    per-volley assignment — blocked reference body on float weights,
    grid-batched kernel on integer-grid weights;
  * the central block-size policy (``backend.volley_block``) and the
    weight-grid-aware assignment lowering (``backend.assign_lowering``)
    pick sane, clamped values.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import tnn_columns
from repro.core import backend, column
from repro.core.types import ColumnConfig, NeuronConfig, STDPConfig, TIME_DTYPE
from repro.kernels import fused_column


def padded_batch(seed=0, d=3, p_pad=20, q_pad=5, t_window=24, n=7):
    """Heterogeneous integer-grid designs sharing one padding envelope.

    ``n=7`` volleys on purpose: no default block size divides it, so every
    blocked run exercises the silent-padded tail.
    """
    rng = np.random.default_rng(seed)
    thresholds = jnp.asarray([7.0, 4.0, 5.0][:d], jnp.float32)
    t_maxes = jnp.asarray([24, 12, 20][:d], TIME_DTYPE)
    q_actives = jnp.asarray([5, 2, 3][:d], TIME_DTYPE)
    w = jnp.asarray(rng.integers(0, 8, (d, p_pad, q_pad)), jnp.float32)
    xs = jnp.asarray(rng.integers(0, 28, (n, d, p_pad)), TIME_DTYPE)
    return w, xs, thresholds, t_maxes, q_actives, t_window


def run_padded(lowering, v_blk, seed=0, n=7, **kw):
    w, xs, th, tm, qa, t_window = padded_batch(seed=seed, n=n)
    args = dict(
        t_window=t_window, w_max=7, wta_k=1, mu_capture=1.0,
        mu_backoff=1.0, mu_search=1.0, stabilize=False, response="rnl",
        epochs=2, lowering=lowering, v_blk=v_blk,
    )
    args.update(kw)
    return fused_column.fit_scan_padded(w, xs, th, tm, qa, **args)


def test_blocked_reference_bit_identical_across_block_sizes():
    """Acceptance: every v_blk — dividing or not, larger than N or not —
    reproduces the per-volley (v_blk=1) scan bit for bit."""
    w_1 = np.asarray(run_padded("reference", v_blk=1))
    for v_blk in (2, 3, 5, 7, 8, 16):
        w_b = np.asarray(run_padded("reference", v_blk=v_blk))
        np.testing.assert_array_equal(
            w_1, w_b, err_msg=f"v_blk={v_blk} diverges from per-volley scan"
        )
    # stabilizer path (off-grid weights): still identical across blocking,
    # because blocking never changes the arithmetic, only the batching
    w_1s = np.asarray(run_padded("reference", v_blk=1, stabilize=True))
    w_3s = np.asarray(run_padded("reference", v_blk=3, stabilize=True))
    np.testing.assert_array_equal(w_1s, w_3s)


def test_blocked_tail_is_masked_even_for_degenerate_thresholds():
    """threshold <= 0 makes a fully-silent volley fire every neuron at
    t=0, so the sentinel alone would NOT make tail volleys no-ops — the
    per-block valid count must mask them.  Regression: v_blk must not
    change results even for such degenerate designs."""
    rng = np.random.default_rng(11)
    w = jnp.asarray(rng.integers(1, 8, (1, 8, 3)), jnp.float32)
    xs = jnp.asarray(rng.integers(0, 10, (3, 1, 8)), TIME_DTYPE)
    th = jnp.asarray([0.0], jnp.float32)  # degenerate: silence still fires
    tm = jnp.asarray([10], TIME_DTYPE)
    qa = jnp.asarray([3], TIME_DTYPE)
    args = dict(
        t_window=10, w_max=7, wta_k=1, mu_capture=1.0, mu_backoff=1.0,
        mu_search=1.0, stabilize=False, response="rnl", epochs=1,
    )
    outs = {
        (low, vb): np.asarray(fused_column.fit_scan_padded(
            jnp.array(w, copy=True), xs, th, tm, qa,
            lowering=low, v_blk=vb, **args,
        ))
        for low, vb in (
            ("reference", 1), ("reference", 2), ("interpret", 2),
        )
    }
    np.testing.assert_array_equal(
        outs[("reference", 1)], outs[("reference", 2)],
        err_msg="tail volleys leaked into the weight fold (reference)",
    )
    np.testing.assert_array_equal(
        outs[("reference", 1)], outs[("interpret", 2)],
        err_msg="tail volleys leaked into the weight fold (kernel)",
    )


def test_blocked_kernel_bit_identical_to_reference():
    """The volley-blocked kernel (one invocation per block, in-kernel
    sequential loop) == blocked reference body, heterogeneous designs,
    non-dividing block, both k-WTA branches."""
    for kw in (dict(), dict(wta_k=2, seed=1)):
        w_ref = np.asarray(run_padded("reference", v_blk=3, **kw))
        w_int = np.asarray(run_padded("interpret", v_blk=3, **kw))
        np.testing.assert_array_equal(w_ref, w_int)
    # default (policy-chosen) block sizes differ per lowering; results
    # must not
    w_ref = np.asarray(run_padded("reference", v_blk=None))
    w_int = np.asarray(run_padded("interpret", v_blk=None))
    np.testing.assert_array_equal(w_ref, w_int)


def test_blocked_scan_matches_cycle_on_integer_weights():
    """D=1 blocked padded fit == mode='cycle' column fit on the integer
    grid — the fused contract survives blocking end to end."""
    cfg = ColumnConfig(
        p=11, q=3, t_max=18,
        neuron=NeuronConfig(threshold=6.0, w_max=7),
        stdp=STDPConfig(
            mu_capture=1.0, mu_backoff=1.0, mu_search=1.0, stabilizer="none"
        ),
    )
    rng = np.random.default_rng(3)
    w0 = jnp.asarray(rng.integers(0, 8, (cfg.p, cfg.q)), jnp.float32)
    x = jnp.asarray(rng.integers(0, cfg.t_max + 4, (10, cfg.p)), jnp.int32)

    p_cyc, _ = backend.get("cycle").fit(
        {"w": w0}, x, cfg, "cycle", 2, None, False, None
    )
    for v_blk in (1, 4):
        w_blk = fused_column.fit_scan_padded(
            w0[None], x[:, None, :].astype(TIME_DTYPE),
            jnp.asarray([cfg.neuron.threshold], jnp.float32),
            jnp.asarray([cfg.t_max], TIME_DTYPE),
            jnp.asarray([cfg.q], TIME_DTYPE),
            t_window=cfg.t_max, w_max=cfg.neuron.w_max, wta_k=cfg.wta.k,
            mu_capture=1.0, mu_backoff=1.0, mu_search=1.0, stabilize=False,
            response="rnl", epochs=2, lowering="reference", v_blk=v_blk,
        )
        np.testing.assert_array_equal(
            np.asarray(p_cyc["w"]), np.asarray(w_blk[0]),
            err_msg=f"v_blk={v_blk} diverges from mode='cycle'",
        )


@pytest.mark.parametrize("name", tnn_columns.all_benchmarks())
def test_table2_design_fit_matches_cycle(name):
    """The padded fused fit == mode='cycle' at each Table II design's
    published p x q and t_max, on the integer grid."""
    cfg = dataclasses.replace(
        tnn_columns.column_config(name),
        stdp=STDPConfig(
            mu_capture=1.0, mu_backoff=1.0, mu_search=1.0, stabilizer="none"
        ),
    )
    rng = np.random.default_rng(17)
    w0 = jnp.asarray(
        rng.integers(0, cfg.neuron.w_max + 1, (cfg.p, cfg.q)), jnp.float32
    )
    x = jnp.asarray(rng.integers(0, cfg.t_max + 4, (6, cfg.p)), jnp.int32)

    p_cyc, _ = backend.get("cycle").fit(
        {"w": w0}, x, cfg, "cycle", 1, None, False, None
    )
    w_fused = fused_column.fit_scan_padded(
        w0[None], x[:, None, :].astype(TIME_DTYPE),
        jnp.asarray([cfg.neuron.threshold], jnp.float32),
        jnp.asarray([cfg.t_max], TIME_DTYPE),
        jnp.asarray([cfg.q], TIME_DTYPE),
        t_window=cfg.t_max, w_max=cfg.neuron.w_max, wta_k=cfg.wta.k,
        mu_capture=1.0, mu_backoff=1.0, mu_search=1.0, stabilize=False,
        response="rnl", epochs=1, lowering="reference",
    )
    w_cyc = np.asarray(p_cyc["w"])
    assert (w_cyc != np.asarray(w0)).any(), "STDP left the weights unchanged"
    np.testing.assert_array_equal(w_cyc, np.asarray(w_fused[0]))


def _assign_single_volley(w, xs, th, tm, qa, t_window, n):
    """Per-design, per-volley assignment spec (the pre-blocking loop)."""
    d = w.shape[0]
    out = np.zeros((d, n), np.int64)
    for di in range(d):
        for vi in range(n):
            t = fused_column.fire_dense_ref(
                w[di], xs[vi, di], th[di], t_window, t_max=tm[di],
                response="rnl",
            )
            t = np.asarray(
                jnp.where(
                    jnp.arange(w.shape[2]) < qa[di], t, tm[di]
                )
            )
            out[di, vi] = (
                int(t.argmin()) if (t < int(tm[di])).any() else int(qa[di])
            )
    return out


def test_assign_padded_identity_vs_single_volley_assignment():
    """Acceptance: the batched assignment pass == per-design single-volley
    assignment, for float weights (reference, blocked) and integer-grid
    weights (kernel, volleys batched into the grid)."""
    rng = np.random.default_rng(5)
    w_int, xs, th, tm, qa, t_window = padded_batch(seed=5, n=9)
    spec = _assign_single_volley(
        np.asarray(w_int), np.asarray(xs), np.asarray(th), np.asarray(tm),
        np.asarray(qa), t_window, 9,
    )
    for v_blk in (1, 4, None):
        got = fused_column.assign_padded(
            w_int, xs, th, tm, qa, t_window=t_window, wta_k=1,
            response="rnl", lowering="reference", v_blk=v_blk,
        )
        np.testing.assert_array_equal(spec, np.asarray(got))
    # the kernel lowering (grid-batched, integer-grid fire) agrees on
    # integer weights
    got_k = fused_column.assign_padded(
        w_int, xs, th, tm, qa, t_window=t_window, wta_k=1,
        response="rnl", lowering="interpret", w_max=7,
    )
    np.testing.assert_array_equal(spec, np.asarray(got_k))
    # float weights: the reference body keeps the established float fire
    w_f = w_int + jnp.asarray(
        rng.uniform(-0.45, 0.45, w_int.shape), jnp.float32
    )
    spec_f = _assign_single_volley(
        np.asarray(w_f), np.asarray(xs), np.asarray(th), np.asarray(tm),
        np.asarray(qa), t_window, 9,
    )
    got_f = fused_column.assign_padded(
        w_f, xs, th, tm, qa, t_window=t_window, wta_k=1,
        response="rnl", lowering="reference",
    )
    np.testing.assert_array_equal(spec_f, np.asarray(got_f))
    # the kernel lowering refuses to run without the grid parameter
    with pytest.raises(ValueError, match="w_max"):
        fused_column.assign_padded(
            w_int, xs, th, tm, qa, t_window=t_window, wta_k=1,
            response="rnl", lowering="interpret",
        )


def test_volley_block_policy_and_assign_lowering(monkeypatch):
    """The central heuristics: small unrolled blocks for the reference
    lowering, larger in-kernel blocks for the kernels, clamped to the
    stream; the assignment kernel only ever picked for on-grid weights."""
    assert backend.volley_block("reference", 100) == 8
    assert backend.volley_block("mosaic", 100) == 32
    assert backend.volley_block("interpret", 100) == 32
    assert backend.volley_block("reference", 3) == 3
    assert backend.volley_block("mosaic", 1) == 1
    # envelope-aware unroll cap: a known small design axis slims the
    # unrolled reference block (cheap traces), never below 2, never above
    # the D-free default, and never affects the in-kernel lowerings
    assert backend.volley_block("reference", 100, d=1) == 2
    assert backend.volley_block("reference", 100, d=2) == 4
    assert backend.volley_block("reference", 100, d=3) == 6
    assert backend.volley_block("reference", 100, d=4) == 8
    assert backend.volley_block("reference", 100, d=64) == 8
    assert backend.volley_block("reference", 3, d=4) == 3  # stream clamp
    assert backend.volley_block("mosaic", 100, d=1) == 32
    assert backend.volley_block("interpret", 100, d=2) == 32
    w_grid = jnp.asarray([[2.0, 3.0]])
    w_off = jnp.asarray([[2.0, 3.5]])
    # off-TPU: reference everywhere
    assert backend.assign_lowering("rnl", w_grid) == backend.pallas_lowering()
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    assert backend.assign_lowering("rnl", w_grid) == "mosaic"
    assert backend.assign_lowering("rnl", w_off) == "reference"
    assert backend.assign_lowering("snl", w_grid) == "reference"


def test_blocked_scan_still_one_trace_per_envelope(compile_counter):
    """Changing every runtime operand on the blocked scan retraces
    nothing; changing v_blk (a static envelope knob) is a new trace."""
    fn = fused_column.fit_scan_padded
    w, xs, th, tm, qa, _ = padded_batch(seed=2, t_window=23, n=7)
    args = dict(
        t_window=23, w_max=7, wta_k=1, mu_capture=1.0, mu_backoff=1.0,
        mu_search=1.0, stabilize=False, response="rnl", epochs=2,
        lowering="reference", v_blk=4,
    )
    with compile_counter.expect_traces(fn, 1):
        fn(w, xs, th, tm, qa, **args)
    w2, xs2, *_ = padded_batch(seed=3, t_window=23, n=7)
    # per-design scalars are runtime operands of the blocked scan;
    # changing them must not recompile
    with compile_counter.expect_traces(fn, 0):
        fn(
            w2, xs2,
            jnp.asarray([3.0, 9.0, 6.0], jnp.float32),
            jnp.asarray([16, 23, 8], TIME_DTYPE),
            jnp.asarray([1, 4, 2], TIME_DTYPE),
            **args,
        )
    w3, xs3, th3, tm3, qa3, _ = padded_batch(seed=2, t_window=23, n=7)
    with compile_counter.expect_traces(fn, 1):  # v_blk is envelope
        fn(w3, xs3, th3, tm3, qa3, **{**args, "v_blk": 7})
