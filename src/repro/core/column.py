"""Single-column TNN: the paper's NSPU building block.

A column is p synapses x q neurons + WTA inhibition + STDP.  Inference for
one input volley:

  volley [p] --(response fn + threshold)--> spikes [q] --(WTA)--> winners [q]

Training is online: each volley's (input, winner) pair drives one STDP step.
Weights, being the only state, live in a plain dict pytree.

Execution is dispatched through the backend registry
(``repro.core.backend``): ``mode`` accepts 'auto' | 'event' | 'cycle' |
'pallas'.  ``fit`` runs the whole training loop as ONE jitted, donated
``lax.scan`` over epochs x volleys (a single compilation per config); on the
'pallas' backend the scan body is the fused column step of
``repro.kernels.fused_column`` (fire + WTA + STDP in one kernel).

Grids of columns with inter-layer connectivity are ``repro.core.network``;
the same ``mode`` knob resolves there layer by layer, so a column trains
identically standalone or as a network layer.  The full backend contract is
documented in ``docs/backends.md``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import backend as backend_lib
from repro.core import stdp
from repro.core.types import ColumnConfig, TIME_DTYPE, WEIGHT_DTYPE


def init_params(rng: jax.Array, cfg: ColumnConfig) -> dict:
    """Initialize weights uniformly over [0, w_max] (hardware reset state
    randomizes the unary counters).

    Stochastic-STDP designs start from *integer* counters, uniform on
    {0..w_max}, as the hardware's reset does: their unit updates then keep
    every weight on the integer grid, where the fused integer-grid fire
    and the float-weight solvers agree exactly.  Expected mode draws
    floats, as before.
    """
    if cfg.stdp.mode == "stochastic":
        w = jax.random.randint(
            rng, (cfg.p, cfg.q), 0, cfg.neuron.w_max + 1
        ).astype(WEIGHT_DTYPE)
        return {"w": w}
    w = jax.random.uniform(
        rng, (cfg.p, cfg.q), WEIGHT_DTYPE, 0.0, float(cfg.neuron.w_max)
    )
    return {"w": w}


@functools.partial(jax.jit, static_argnames=("cfg", "mode"))
def apply(
    params: dict,
    x_times: jnp.ndarray,
    cfg: ColumnConfig,
    mode: str = "auto",
    rng: Optional[jax.Array] = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Forward one or a batch of volleys.

    Args:
      params: {'w': [p, q]}.
      x_times: [..., p] input spike times.
      cfg: column config.
      mode: 'auto' | 'event' | 'cycle' | 'pallas' simulation backend.
      rng: only needed for random WTA tie-break.

    Returns:
      (post-WTA spike times [..., q], winner mask [..., q]).
    """
    be = backend_lib.get(backend_lib.resolve(mode, cfg))
    return be.fire(params, x_times, cfg, rng=rng)


def train_step(
    params: dict,
    x_times: jnp.ndarray,
    cfg: ColumnConfig,
    mode: str = "auto",
    rng: Optional[jax.Array] = None,
    y_target: Optional[jnp.ndarray] = None,
    update: str = "online",
) -> tuple[dict, jnp.ndarray]:
    """One training pass over a batch of volleys.

    ``update`` selects the fold semantics:

      'online' (default) — true online rule, matching the hardware: each
        volley's winners are computed from the weights as updated by every
        preceding volley (one fused forward+STDP step per volley).
      'batch' — legacy semantics: ALL winners are computed from the stale
        pre-batch weights, then the STDP updates fold sequentially.  Kept as
        an explicit option because it approximates minibatch training, but
        it diverges from the generated RTL.

    Unsupervised: the WTA winners are the STDP teacher (paper default).
    Supervised: ``y_target`` [..., q] spike times override the winners.

    Returns (new params, winner spike times [..., q]).
    """
    if update == "batch":
        y, _ = apply(params, x_times, cfg, mode, rng)
        teacher = y if y_target is None else y_target
        xb = x_times.reshape((-1, cfg.p))
        yb = teacher.reshape((-1, cfg.q))
        w = stdp.stdp_update_batch(
            params["w"], xb, yb, cfg.stdp, cfg.neuron.w_max, cfg.t_max,
            rng=rng,
        )
        return {"w": w}, y
    if update != "online":
        raise ValueError(f"unknown update: {update!r}")

    batch_shape = x_times.shape[:-1]
    xb = x_times.reshape((-1, cfg.p))
    yt = None if y_target is None else y_target.reshape((-1, cfg.q))
    name = backend_lib.resolve(mode, cfg, training=True)
    new_params, ys = backend_lib.get(name).fit(
        params, xb, cfg, mode, 1, rng, True, yt
    )
    y = ys[0].reshape(batch_shape + (cfg.q,)).astype(TIME_DTYPE)
    return new_params, y


def fit(
    params: dict,
    x_times: jnp.ndarray,
    cfg: ColumnConfig,
    epochs: int = 8,
    mode: str = "auto",
    rng: Optional[jax.Array] = None,
) -> dict:
    """Run unsupervised online STDP for several passes over the data [N, p].

    The whole run — every epoch, every volley — is one compiled scan with a
    donated weight buffer; nothing is re-traced or re-padded per volley.
    Stochastic STDP draws from the stream of ``rng`` (required then;
    ``stdp.stream_key``), volley n of epoch e at index e * N + n, on every
    backend alike.
    """
    name = backend_lib.resolve(mode, cfg, training=True)
    new_params, _ = backend_lib.get(name).fit(
        params, x_times, cfg, mode, epochs, rng, False, None
    )
    return new_params


def cluster_assignments(
    params: dict, x_times: jnp.ndarray, cfg: ColumnConfig, mode: str = "auto"
) -> jnp.ndarray:
    """Winner neuron index per volley = cluster id (paper's clustering use).

    Volleys where no neuron spikes are assigned cluster q (an 'unclustered'
    bucket), matching the simulator's rand-index accounting.  Assignment is
    batched, never scanned: the solver backends forward the whole stream in
    one call, and the 'pallas' forward fires volley *blocks*
    (``backend.volley_block``) off-TPU / the kernel grid on TPU.
    """
    y, win = apply(params, x_times, cfg, mode)
    any_spike = win.any(axis=-1)
    idx = jnp.argmin(y, axis=-1)
    return jnp.where(any_spike, idx, cfg.q).astype(TIME_DTYPE)
