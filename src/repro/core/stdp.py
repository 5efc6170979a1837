"""Spike-timing-dependent plasticity for TNN columns.

Implements the classic TNN STDP rule (Smith 2020, arXiv:2011.13844; used by
Chaudhari et al. ICASSP'21 for time-series clustering).  For synapse (i, j)
with input spike time x_i and post-WTA output spike time y_j (t_max == none):

  case                         update
  x and y spike, x <= y        w += mu_capture * s_plus(w)    (capture)
  x and y spike, x >  y        w -= mu_backoff * s_minus(w)   (backoff)
  x spikes, y silent           w += mu_search                 (search)
  x silent, y spikes           w -= mu_backoff * s_minus(w)   (backoff)
  neither spikes               no change

With the 'half' (bimodal) stabilizer, s_plus(w) = 1 - w/w_max + eps and
s_minus(w) = w/w_max + eps, which drives converged weights toward the rails
{0, w_max} — the behaviour the TNN7 unary weight counters implement with
LFSR-gated increments.  'none' sets both to 1.

Two execution modes:
  'expected'   — deterministic, applies the expected update (float weights).
  'stochastic' — Bernoulli(mu * s) unit-magnitude updates on integer
                 counters, matching the LFSR-gated LSB increments of the
                 hardware.  The draws come from one counter-based stream
                 (``stream_bits``): Threefry-2x32 of the design's stream key
                 on the counter pair (volley index, (i << 16) | j), so
                 synapse (i, j) of volley v draws the same bits on every
                 path — the event/cycle solvers here and the fused kernels
                 in ``repro.kernels.fused_column`` — whatever padding,
                 bucket or shard it rides in.  ``stochastic_update`` is the
                 one rule all of them apply.

Supervised mode simply substitutes the label-derived target spike volley for
y (the caller picks y; the rule itself is unchanged), as in the paper's
"supervised and unsupervised modes".
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import STDPConfig

# ------------------------------------------------- the stochastic stream
# Threefry-2x32 with 20 rounds, as ``jax.extend.random.threefry2x32_p``
# computes it, written in int32 adds, xors and logical shifts so the same
# function traces into a Mosaic kernel body and into XLA alike (int32 adds
# wrap exactly like the uint32 ones of the reference).
THREEFRY_PARITY = 0x1BD11BDA
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
U_SCALE = 2.0 ** -23  # u = (bits >> 9) * 2**-23: 23 exact bits in [0, 1)


def _srl(x, r: int):
    return jax.lax.shift_right_logical(x, jnp.full(jnp.shape(x), r, jnp.int32))


def _rotl(x, r: int):
    return jax.lax.shift_left(x, jnp.full(jnp.shape(x), r, jnp.int32)) | _srl(
        x, 32 - r
    )


def threefry2x32(k0, k1, x0, x1):
    """Word 0 of Threefry-2x32 (20 rounds) of counter (x0, x1) under key
    (k0, k1); every operand int32 (bit patterns of the uint32 words),
    broadcast against each other."""
    ks = (k0, k1, k0 ^ k1 ^ THREEFRY_PARITY)
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for g in range(5):
        for r in _ROTATIONS[g % 2]:
            x0 = x0 + x1
            x1 = x0 ^ _rotl(x1, r)
        x0 = x0 + ks[(g + 1) % 3]
        if g < 4:  # the last injection into word 1 reaches no output
            x1 = x1 + ks[(g + 2) % 3] + (g + 1)
    return x0


def synapse_counter(shape) -> jnp.ndarray:
    """Counter word 1 of every synapse of a [p, q] block: (i << 16) | j."""
    i = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    j = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return jax.lax.shift_left(i, jnp.full(shape, 16, jnp.int32)) | j


def stream_uniform(key, volley, counter) -> jnp.ndarray:
    """u in [0, 1) of each synapse for one volley: ``key`` is the design's
    (k0, k1) int32 pair, ``volley`` the global volley index, ``counter``
    from ``synapse_counter``.  u = float32(bits >> 9) * 2**-23, exact."""
    bits = threefry2x32(key[0], key[1], volley, counter)
    return _srl(bits, 9).astype(jnp.float32) * U_SCALE


def stream_key(rng) -> jnp.ndarray:
    """A design's stream key, int32 [2], from a PRNG key (typed or raw
    uint32): its first two key words."""
    if jnp.issubdtype(getattr(rng, "dtype", None), jax.dtypes.prng_key):
        rng = jax.random.key_data(rng)
    data = jnp.asarray(rng).reshape(-1)[:2]
    return jax.lax.bitcast_convert_type(data.astype(jnp.uint32), jnp.int32)


def stochastic_update(
    w, x, y, t_max, mu_capture, mu_backoff, mu_search, u, *, w_max: int,
    stabilize: bool,
):
    """One volley of stochastic STDP on integer counters ``w`` [p, q].

    ``x`` [p, 1] input and ``y`` [1, q] winner times (any real or integer
    dtype), ``u`` [p, q] the stream's uniforms.  Each synapse moves one
    LSB in the sign of its expected-mode delta when u < |delta|, then
    clamps to [0, w_max].  |delta| is the expected rule's magnitude
    written as ONE f32 expression every path shares: with integer w,
    mu * ((1 - w / w_max) + 1 / (2 w_max)) == ((w_max + 1/2) - w) *
    (mu * f32(1 / w_max)), whose half-integer factor is exact and whose
    two products round once each — no add follows a product, so no
    compiler can fuse the chain differently on another path.
    """
    inv = float(np.float32(1.0 / w_max))
    xs = x < t_max
    ys = y < t_max
    capture = xs & ys & (x <= y)
    backoff = (xs & ys & (x > y)) | ((~xs) & ys)
    search = xs & (~ys)
    mu_c = jnp.asarray(mu_capture, jnp.float32)
    mu_b = jnp.asarray(mu_backoff, jnp.float32)
    if stabilize:
        p_up = ((w_max + 0.5) - w) * (mu_c * inv)
        p_down = (w + 0.5) * (mu_b * inv)
    else:
        p_up, p_down = mu_c, mu_b
    up = (capture & (u < p_up)) | (search & (u < jnp.asarray(mu_search, jnp.float32)))
    down = backoff & (u < p_down)
    step = up.astype(jnp.float32) - down.astype(jnp.float32)
    return jnp.clip(w + step, 0.0, float(w_max))


def _stabilizers(w: jnp.ndarray, w_max: int, cfg: STDPConfig):
    if cfg.stabilizer == "none":
        one = jnp.ones_like(w)
        return one, one
    frac = jnp.clip(w / w_max, 0.0, 1.0)
    eps = 1.0 / (2 * w_max)
    return (1.0 - frac) + eps, frac + eps


def stdp_delta(
    w: jnp.ndarray,
    x_times: jnp.ndarray,
    y_times: jnp.ndarray,
    cfg: STDPConfig,
    w_max: int,
    t_max: int,
) -> jnp.ndarray:
    """Expected STDP update for one volley.

    Args:
      w: [p, q] weights.
      x_times: [p] input spike times.
      y_times: [q] post-WTA output spike times.
      cfg: STDP config.
      w_max: weight ceiling.
      t_max: window length (>= t_max means no spike).

    Returns:
      [p, q] weight delta (expected value).
    """
    x = x_times[:, None]  # [p, 1]
    y = y_times[None, :]  # [1, q]
    xs = x < t_max
    ys = y < t_max
    s_plus, s_minus = _stabilizers(w, w_max, cfg)

    capture = xs & ys & (x <= y)
    backoff = (xs & ys & (x > y)) | (~xs & ys)
    search = xs & ~ys

    delta = jnp.zeros_like(w)
    delta = jnp.where(capture, cfg.mu_capture * s_plus, delta)
    delta = jnp.where(backoff, -cfg.mu_backoff * s_minus, delta)
    delta = jnp.where(search, cfg.mu_search * jnp.ones_like(w), delta)
    return delta


def stdp_update(
    w: jnp.ndarray,
    x_times: jnp.ndarray,
    y_times: jnp.ndarray,
    cfg: STDPConfig,
    w_max: int,
    t_max: int,
    rng: jax.Array | None = None,
    volley=0,
) -> jnp.ndarray:
    """Apply one STDP step and clamp to [0, w_max].

    In 'stochastic' mode the magnitudes of the expected delta are
    per-synapse Bernoulli probabilities of a +/-1 LSB update (hardware
    semantics), drawn from the stream of ``rng`` (``stream_key``) at
    volley index ``volley``; 'expected' applies the float expectation
    directly.
    """
    if cfg.mode == "stochastic":
        if rng is None:
            raise ValueError("stochastic STDP requires a PRNG key")
        u = stream_uniform(
            stream_key(rng), jnp.asarray(volley, jnp.int32),
            synapse_counter(w.shape),
        )
        return stochastic_update(
            w, x_times[:, None], y_times[None, :], t_max, cfg.mu_capture,
            cfg.mu_backoff, cfg.mu_search, u, w_max=w_max,
            stabilize=cfg.stabilizer == "half",
        )
    delta = stdp_delta(w, x_times, y_times, cfg, w_max, t_max)
    return jnp.clip(w + delta, 0.0, float(w_max))


def stdp_update_batch(
    w: jnp.ndarray,
    x_times: jnp.ndarray,
    y_times: jnp.ndarray,
    cfg: STDPConfig,
    w_max: int,
    t_max: int,
    rng: jax.Array | None = None,
) -> jnp.ndarray:
    """Sequentially fold a batch of volleys into the weights (online rule).

    x_times: [B, p]; y_times: [B, q].  Hardware processes volleys one gamma
    window at a time; lax.scan preserves that online semantics exactly.
    Stochastic mode draws volley b of the batch at stream index b.
    """
    B = x_times.shape[0]
    if cfg.mode == "stochastic" and rng is None:
        raise ValueError("stochastic STDP requires a PRNG key")

    def step(wc, inp):
        xt, yt, v = inp
        return stdp_update(wc, xt, yt, cfg, w_max, t_max, rng=rng, volley=v), None

    w_new, _ = jax.lax.scan(
        step, w, (x_times, y_times, jnp.arange(B, dtype=jnp.int32))
    )
    return w_new
