"""Plain reference of stochastic (Bernoulli-gated) STDP, for the check of
``correct`` in a cell whose configuration states ``stdp.mode`` stochastic.

Written from the documented rule (``docs/kernels.md``, "The stochastic
stream"), in straightforward ``jax.numpy``, one design and one volley at
a time.  It imports nothing of the program: the fire, the 1-WTA, the
encode and the assign are ``reference.py``'s, the draws come from JAX's
own ``threefry2x32_p`` primitive (not the program's hand-written rounds),
and the initial counters and stream keys are derived here from the seed.

* Init: candidate ``i`` of an exploration seeded ``s`` starts from
  integer counters ``randint(fold_in(k1, i), (p, q), 0, w_max + 1)`` and
  draws under the stream key ``key_data(fold_in(k0, i))``, where
  ``(k0, k1) = split(key(s))``.
* Per volley ``v = epoch * N + n``: fire on the counters, 1-WTA with
  index tie-break, then for synapse (i, j) the Threefry-2x32 word 0 of
  the counter pair ``(v, (i << 16) | j)`` gives
  ``u = (bits >> 9) * 2**-23``; the counter moves one LSB up under a
  capture (probability ``((w_max + 1/2) - w) * (mu_capture *
  f32(1 / w_max))`` with the 'half' stabiliser) or a search
  (``mu_search``), one down under a backoff (``(w + 1/2) * (mu_backoff *
  f32(1 / w_max))``) when ``u`` is below its probability, and clamps to
  ``[0, w_max]``.

``dtype`` selects the precision of the weights, the uniforms and the
arithmetic: float32 as the configuration states, bfloat16 for the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

import reference


def explore_init(seed: int, index: int, p: int, q: int, w_max: int):
    """Initial integer counters of candidate ``index`` of an exploration
    seeded ``seed``."""
    _, init_key = jax.random.split(jax.random.key(seed))
    return jax.random.randint(
        jax.random.fold_in(init_key, index), (p, q), 0, w_max + 1
    ).astype(jnp.float32)


def explore_stream_key(seed: int, index: int):
    """Stream key (two uint32 words) of candidate ``index``."""
    root, _ = jax.random.split(jax.random.key(seed))
    return jax.random.key_data(jax.random.fold_in(root, index)).astype(jnp.uint32)


def uniforms(key, volley, p: int, q: int, dtype):
    """u of every synapse [p, q] for one volley."""
    i = jnp.arange(p, dtype=jnp.uint32)[:, None]
    j = jnp.arange(q, dtype=jnp.uint32)[None, :]
    ctr = (i << 16) | j
    v = jnp.full((p, q), volley, jnp.uint32)
    bits = threefry2x32_p.bind(key[0], key[1], v, ctr)[0]
    return (bits >> 9).astype(dtype) * jnp.asarray(2.0 ** -23, dtype)


def stdp(w, x, y, u, mu_capture, mu_backoff, mu_search, w_max: int, t_max: int,
         stabilize: bool):
    """Stochastic STDP of one volley on counters ``w`` in their dtype."""
    dt = w.dtype
    xs = (x < t_max)[:, None]
    ys = (y < t_max)[None, :]
    xc = x[:, None]
    yc = y[None, :]
    capture = xs & ys & (xc <= yc)
    backoff = (xs & ys & (xc > yc)) | (~xs & ys)
    search = xs & ~ys
    inv = jnp.asarray(np.float32(1.0 / w_max), dt)
    mu_c = jnp.asarray(mu_capture, dt)
    mu_b = jnp.asarray(mu_backoff, dt)
    if stabilize:
        p_up = (jnp.asarray(w_max + 0.5, dt) - w) * (mu_c * inv)
        p_down = (w + jnp.asarray(0.5, dt)) * (mu_b * inv)
    else:
        p_up, p_down = mu_c, mu_b
    up = (capture & (u < p_up)) | (search & (u < jnp.asarray(mu_search, dt)))
    down = backoff & (u < p_down)
    return jnp.clip(w + up.astype(dt) - down.astype(dt), 0, w_max).astype(dt)


@functools.partial(jax.jit, static_argnames=("t_max", "epochs", "statics", "dtype"))
def fit(w, xs, threshold, key, *, t_max: int, epochs: int, statics: tuple, dtype):
    """Online stochastic STDP over volleys ``xs`` [N, p] for ``epochs``
    passes, one volley at a time, drawing under stream ``key``.
    ``statics`` is ``reference.statics`` of the configuration."""
    w_max, k, mu_c, mu_b, mu_s, stab = statics
    n, p = xs.shape
    q = w.shape[1]
    w = w.astype(dtype)

    def volley(wc, inp):
        x, v = inp
        w_fire = jnp.round(jnp.clip(wc, 0, w_max))
        y = reference.winners(
            reference.fire_times(w_fire, x, threshold, t_max, dtype), k, t_max
        )
        u = uniforms(key, v, p, q, dtype)
        return stdp(wc, x, y, u, mu_c, mu_b, mu_s, w_max, t_max, stab), None

    def epoch(wc, e):
        vs = (e * n + jnp.arange(n, dtype=jnp.int32)).astype(jnp.uint32)
        return jax.lax.scan(volley, wc, (xs, vs))[0], None

    return jax.lax.scan(epoch, w, jnp.arange(epochs, dtype=jnp.int32))[0]
