"""Plain reference of the clustering service under stochastic STDP, for
the check of ``correct`` in a serving cell whose configuration states
``stdp.mode`` stochastic.

Written from the service's documented semantics (``docs/serving.md``,
"Stochastic STDP"), in straightforward ``jax.numpy``, one design and one
volley at a time.  It imports nothing of the program: the queues, batches
and re-fit schedule are ``reference.ServiceReplay``'s, the fire, 1-WTA,
uniforms and unit update are ``reference.py``'s and
``reference_stochastic.py``'s, and the stream keys are derived here from
the seed.

* Stream keys: design ``i`` (its index in the configuration's order) of a
  service seeded ``s`` draws under ``key_data(fold_in(k0, i))``, where
  ``(k0, _) = split(key(s))``.
* Volley base: every design starts at 0.  A bucket's re-fit trains each
  design with buffered volleys, for ``refit_epochs`` passes, on its
  window of ``refit_window`` (W) slots — the buffered volleys in order,
  then silent slots — drawing slot n of epoch e at stream index ``base +
  e * W + n``; then every design of the bucket moves its base on by
  ``refit_epochs * W`` (the bucket re-fits as one window, silent lanes
  included).

``dtype`` selects the precision of the counters, the uniforms and the
arithmetic: float32 as the configuration states, bfloat16 for the
control.  The fits run under ``jax.default_matmul_precision("highest")``,
so no reduced-precision matrix unit can stand in for the dtype asked for.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference
import reference_stochastic


def stream_key(seed: int, index: int):
    """Stream key (two uint32 words) of design ``index`` of a service
    seeded ``seed``."""
    return reference_stochastic.explore_stream_key(seed, index)


@functools.partial(jax.jit, static_argnames=("t_max", "epochs", "statics", "dtype"))
def fit(w, xs, threshold, key, base, *, t_max: int, epochs: int, statics: tuple,
        dtype):
    """Stochastic STDP over window ``xs`` [W, p] for ``epochs`` passes,
    slot n of epoch e drawing at stream index ``base + e * W + n``."""
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
        u = reference_stochastic.uniforms(key, v, p, q, dtype)
        return reference_stochastic.stdp(wc, x, y, u, mu_c, mu_b, mu_s, w_max,
                                         t_max, stab), None

    def epoch(wc, e):
        vs = (base + e * n + jnp.arange(n, dtype=jnp.uint32)).astype(jnp.uint32)
        return jax.lax.scan(volley, wc, (xs, vs))[0], None

    return jax.lax.scan(epoch, w, jnp.arange(epochs, dtype=jnp.uint32))[0]


class ServiceReplay(reference.ServiceReplay):
    """``reference.ServiceReplay`` whose re-fits learn by stochastic STDP,
    each design on its own stream from its bucket's volley base."""

    def __init__(self, designs: dict, weights: dict, encodes: dict, *, seed: int,
                 **kw):
        super().__init__(designs, weights, encodes, **kw)
        self.keys = {n: stream_key(seed, i) for i, n in enumerate(designs)}
        self.bases = {n: 0 for n in designs}

    def _run(self, b: dict) -> None:
        super()._run(b)
        # a batch always serves a request, so a served count back at 0
        # means the bucket just re-fit: all its designs move on together
        if b["served"] == 0:
            for n in b["names"]:
                self.bases[n] += self.refit_epochs * self.refit_window

    def _refit(self, design: str) -> None:
        p, q, t_max, threshold = self.designs[design]
        xs = np.full((self.refit_window, p), t_max, np.int32)
        buf = self.buffers[design]
        xs[: len(buf)] = np.asarray(self.encodes[design])[buf]
        self.trained[design] += len(buf)
        with jax.default_matmul_precision("highest"):
            w = fit(
                self.versions[design][-1], jnp.asarray(xs), jnp.float32(threshold),
                self.keys[design], jnp.uint32(self.bases[design]), t_max=t_max,
                epochs=self.refit_epochs, statics=self.statics, dtype=self.dtype,
            )
        self.versions[design].append(w)
