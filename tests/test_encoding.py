"""Property-based round-trip tests for the latency encoder (ISSUE 8).

The encoder is the admission boundary of every front-end (simulator
sweeps, the streaming service): these properties pin the degenerate
inputs real traffic produces — constant series, single-sample series,
extreme gamma windows — plus the two invariants everything downstream
assumes: spike times live on the ``[0, t_max)`` integer grid in
``TIME_DTYPE``, and larger samples spike earlier (order preservation
per feature, which is what makes latency-coded clustering meaningful).

Runs on the vendored hypothesis shim in ``conftest.py`` (deterministic,
dependency-free) or the real library when installed.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import encoding
from repro.core.types import TIME_DTYPE


def _enc(x, t_max, **kw):
    return np.asarray(encoding.latency_encode(jnp.asarray(x), t_max, **kw))


@settings(max_examples=25, deadline=None)
@given(
    t_max=st.integers(2, 512),
    length=st.integers(1, 32),
    value=st.floats(-1e6, 1e6),
)
def test_constant_series_encodes_to_latest_spike(t_max, length, value):
    """A constant series (zero dynamic range — silence, a stuck sensor)
    normalizes to 0 everywhere and must encode to the LAST grid slot for
    every feature, never to out-of-range or mid-window times.  Covers the
    single-sample series at length 1."""
    t = _enc(np.full(length, value), t_max)
    assert t.dtype == TIME_DTYPE
    assert (t == t_max - 1).all()


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10**6), length=st.integers(2, 48))
def test_times_live_on_the_spike_grid(seed, length):
    x = np.random.default_rng(seed).normal(scale=100.0, size=length)
    for t_max in (2, 3, 257):
        t = _enc(x, t_max)
        assert t.dtype == TIME_DTYPE
        assert ((0 <= t) & (t < t_max)).all()
        # the dynamic range is used end to end: the max sample spikes at
        # 0, the min sample at the last slot
        assert t[np.argmax(x)] == 0
        assert t[np.argmin(x)] == t_max - 1


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10**6))
def test_degenerate_gamma_window(seed):
    """Extreme gamma: a one-slot window (t_max=1) collapses every sample
    to time 0 — degenerate but well-defined, never negative/NaN."""
    x = np.random.default_rng(seed).normal(size=16)
    t = _enc(x, 1)
    assert (t == 0).all() and t.dtype == TIME_DTYPE


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 10**6),
    t_max=st.sampled_from([2, 8, 32, 256]),
    length=st.integers(2, 48),
)
def test_monotone_order_preserving_per_feature(seed, t_max, length):
    """Larger sample => earlier (or equal) spike time, feature by
    feature: sorting the samples ascending must sort the times
    descending (ties allowed — the grid quantizes)."""
    x = np.random.default_rng(seed).normal(size=length)
    t = _enc(x, t_max)
    by_value = np.argsort(x, kind="stable")
    assert (np.diff(t[by_value].astype(np.int64)) <= 0).all()


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10**6), t_max=st.sampled_from([2, 8, 32, 256]))
def test_round_trip_through_decode(seed, t_max):
    """Grid times decode to intensities (v = 1 - t/(t_max-1)) that
    re-encode to the SAME times (normalize=False: the decoded values are
    already in [0, 1]) — the encoder loses only sub-grid precision, once."""
    x = np.random.default_rng(seed).normal(size=24)
    t = _enc(x, t_max)
    v = 1.0 - t.astype(np.float64) / (t_max - 1)
    t2 = _enc(v, t_max, normalize=False)
    assert np.array_equal(t, t2)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10**6), t_max=st.sampled_from([4, 32]))
def test_onoff_round_trip_width_and_silence(seed, t_max):
    """On/off coding doubles the width and keeps exactly one of the two
    channels silent per sample (the sentinel ``t_max``), so downstream
    synapse counts stay ``encoded_width`` exact."""
    x = np.random.default_rng(seed).normal(size=9)
    t = np.asarray(encoding.onoff_encode(jnp.asarray(x), t_max))
    assert t.shape == (18,)
    on, off = t[:9], t[9:]  # concatenated channel halves
    assert ((on == t_max) != (off == t_max)).all()  # exactly one silent
    assert ((0 <= t) & (t <= t_max)).all()


def test_encode_dispatch_matches_width_contract():
    x = jnp.asarray(np.linspace(-1, 1, 10))
    for encoder in encoding.ENCODERS:
        out = np.asarray(encoding.encode(x, 16, encoder))
        assert out.shape == (encoding.encoded_width(10, encoder),)
    assert encoding.encoded_width(10, "latency") == 10
    assert encoding.encoded_width(10, "onoff") == 20
    with pytest.raises(ValueError, match="unknown encoder"):
        encoding.encoded_width(10, "morse")
    with pytest.raises(ValueError, match="unknown encoder"):
        encoding.encode(x, 16, "morse")


# The seven Table II designs' (series length, gamma window) pairs.
TABLE2_SHAPES = [(65, 64), (96, 64), (152, 64), (343, 64), (637, 64),
                 (470, 64), (270, 64)]
SERIES_KINDS = {
    "random": lambda n: np.random.default_rng(n).normal(2.0, 3.0, size=n),
    "constant": lambda n: np.full(n, 0.75),  # hi == lo: the eps guard
    "ramp": lambda n: np.linspace(-1.0, 1.0, n),
}


@pytest.mark.parametrize("kind", sorted(SERIES_KINDS))
@pytest.mark.parametrize("length,t_max", TABLE2_SHAPES)
@pytest.mark.parametrize("encoder", encoding.ENCODERS)
def test_jitted_encode_is_bit_identical_to_eager(encoder, length, t_max, kind):
    """The service's jitted encode, fed the float64 host array admission
    makes, returns the op-by-op ``encode``'s spike times bit for bit."""
    x = SERIES_KINDS[kind](length)
    eager = np.asarray(encoding.encode(jnp.asarray(x), t_max, encoder))
    jitted = np.asarray(encoding.encode_jit(x, t_max, encoder))
    assert jitted.dtype == eager.dtype == TIME_DTYPE
    assert jitted.shape == (encoding.encoded_width(length, encoder),)
    np.testing.assert_array_equal(jitted, eager)
