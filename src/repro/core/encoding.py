"""Temporal (spike-time) encodings of real-valued signals.

Following Chaudhari et al. (ICASSP'21), a time series of length L feeds a
single column with p = L synapses; each sample's amplitude is converted to a
spike *latency* within the gamma window: larger amplitude -> earlier spike.
An optional on/off-center pair doubles the synapse count and encodes signed
deviations, mirroring DoG receptive fields in sensory pathways.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import TIME_DTYPE


ENCODERS = ("latency", "onoff")


def minmax_normalize(x: jnp.ndarray, axis: int = -1, eps: float = 1e-9) -> jnp.ndarray:
    lo = x.min(axis=axis, keepdims=True)
    hi = x.max(axis=axis, keepdims=True)
    return (x - lo) / (hi - lo + eps)


def encoded_width(length: int, encoder: str) -> int:
    """Synapse count a series of ``length`` samples encodes to.

    The admission contract of every front-end (simulator sweeps, the
    streaming service): a design with ``p`` synapses accepts exactly the
    series lengths for which ``encoded_width(L, encoder) == p``.
    """
    if encoder == "latency":
        return length
    if encoder == "onoff":
        return 2 * length
    raise ValueError(f"unknown encoder: {encoder!r} (have {ENCODERS})")


def encode(x: jnp.ndarray, t_max: int, encoder: str = "latency") -> jnp.ndarray:
    """Dispatch on the encoder name: [..., L] -> [..., encoded_width(L)]."""
    if encoder == "latency":
        return latency_encode(x, t_max)
    if encoder == "onoff":
        return onoff_encode(x, t_max)
    raise ValueError(f"unknown encoder: {encoder!r} (have {ENCODERS})")


def latency_encode(
    x: jnp.ndarray, t_max: int, normalize: bool = True
) -> jnp.ndarray:
    """Intensity-to-latency coding: v in [0,1] -> t = round((1-v)*(t_max-1)).

    Args:
      x: [..., L] real signal.
      t_max: gamma window length in cycles.

    Returns:
      [..., L] int32 spike times in [0, t_max).
    """
    v = minmax_normalize(x) if normalize else jnp.clip(x, 0.0, 1.0)
    t = jnp.round((1.0 - v) * (t_max - 1))
    return jnp.clip(t, 0, t_max - 1).astype(TIME_DTYPE)


def onoff_encode(x: jnp.ndarray, t_max: int) -> jnp.ndarray:
    """On/off-center pair coding: [..., L] -> [..., 2L] spike times.

    The on channel spikes early for positive deviations from the series mean,
    the off channel for negative deviations; the silent channel of each pair
    emits no spike (t_max).
    """
    return _onoff_around(x, x.mean(axis=-1, keepdims=True), t_max)


def _onoff_around(x: jnp.ndarray, mu: jnp.ndarray, t_max: int) -> jnp.ndarray:
    """``onoff_encode`` of ``x`` given its mean ``mu`` [..., 1]."""
    dev = x - mu
    mag = minmax_normalize(jnp.abs(dev))
    t = jnp.round((1.0 - mag) * (t_max - 1)).astype(TIME_DTYPE)
    no = jnp.asarray(t_max, TIME_DTYPE)
    on = jnp.where(dev >= 0, t, no)
    off = jnp.where(dev < 0, t, no)
    return jnp.concatenate([on, off], axis=-1)


_latency_jit = jax.jit(latency_encode, static_argnames=("t_max",))
_mean_jit = jax.jit(lambda x: x.mean(axis=-1, keepdims=True))
_onoff_around_jit = jax.jit(_onoff_around, static_argnames=("t_max",))


def encode_jit(x, t_max: int, encoder: str = "latency") -> jax.Array:
    """``encode`` as compiled programs, one per (series shape, t_max,
    encoder): the same jnp ops in f32, bit-identical to the op-by-op call.

    The on/off mean is a program of its own.  Fused with the deviation
    ``x - mean``, XLA:CPU contracts the mean's multiply by 1/L into an FMA
    in some fusions and not in others, so a near-constant series would
    normalise against a range its own deviations do not share.
    """
    if encoder == "latency":
        return _latency_jit(x, t_max)
    if encoder == "onoff":
        x = jnp.asarray(x)
        return _onoff_around_jit(x, _mean_jit(x), t_max)
    raise ValueError(f"unknown encoder: {encoder!r} (have {ENCODERS})")
