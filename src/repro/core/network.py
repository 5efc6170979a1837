"""Multi-layer TNNs: grids of columns with configurable connectivity.

Paper §II-A: "large multi-layer TNNs with an arbitrary number of layers and
columns per layer with configurable inter-layer connectivity".  Layer l holds
``columns`` parallel columns; their post-WTA spike volleys concatenate into
the next layer's input volley.  Training is greedy layer-wise unsupervised
STDP (the standard TNN recipe — each layer converges on the spike statistics
of the layer below).

Execution is dispatched through the backend registry (``repro.core.backend``)
exactly as for single columns: ``mode`` accepts 'auto' | 'event' | 'cycle' |
'pallas' and is resolved *per layer* against that layer's column config, so
the knob means the same thing for networks as for columns ('auto' routes
each layer's training to the fused path whenever its config fits the fused
contract, and falls back to the hybrid solvers otherwise).

``fit_greedy`` runs each layer's whole epochs x volleys loop as ONE jitted,
donated ``lax.scan``:

* layers that resolve to 'pallas' share the padded-envelope fused scan of
  ``repro.kernels.fused_column.fit_scan_padded`` — fused layers that can
  share a compiled step (same column count and static hyper-parameters,
  sizes within ``backend.ENVELOPE_WASTE_CAP`` of each other) are padded into one
  (p, q, t_max) envelope and the fused column step runs over the layer's
  columns axis, so heterogeneous layers reuse one compiled step when close
  enough in size that padding compute stays bounded (at most one
  compilation per distinct layer shape).  The scan is volley-blocked
  (``backend.volley_block`` volleys folded per step, bit-identical to the
  per-volley fold) and lowers through ``backend.padded_lowering``: the
  Mosaic kernel on TPU (per-layer threshold / window / live-q / STDP mus
  are runtime SMEM operands of one static envelope), the jnp reference
  body of the same algebra elsewhere — bit-identical on integer weight
  grids either way;
* layers that resolve to 'event' / 'cycle' (LIF, random tie-break,
  supervised, ...) run the same solver volley body as ``column.fit``
  (``backend.solver_volley_step``) scanned over epochs x volleys and
  ``vmap``-ed over columns — one compilation per layer *config* (the
  solver scan specializes on the full column config, threshold included).

An explicit ``mode='pallas'`` validates layers against the fused contract
exactly like single-column ``fit``: RNL trains on the kernel wherever one
exists; SNL layers are legal too and take the reference body of the same
fused algebra on every host (``backend.padded_lowering`` picks the
lowering, never the semantics).

The greedy handoff (``apply`` of the frozen stack below) is jitted per
layer as well; no Python-level per-epoch dispatch survives anywhere in
network training.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import backend as backend_lib
from repro.core import column as column_lib
from repro.core import stdp
from repro.core.types import (
    ColumnConfig,
    LayerConfig,
    NetworkConfig,
    TIME_DTYPE,
)
from repro.kernels import fused_column


def _layer_input_width(layer: LayerConfig, in_width: int) -> int:
    if layer.connectivity == "full":
        return in_width
    if in_width % layer.columns != 0:
        raise ValueError(
            f"tiled connectivity needs in_width % columns == 0, got "
            f"{in_width} % {layer.columns}"
        )
    return in_width // layer.columns


def validate(cfg: NetworkConfig, in_width: int) -> None:
    """Check that declared column widths match the connectivity plan, and
    that temporal windows never grow across layers.

    A layer's no-spike sentinel IS its ``t_max`` (``types.no_spike``), so a
    downstream layer with a *larger* window would read upstream silence as
    a live late spike — silently corrupting every backend identically.
    Nonincreasing ``t_max`` keeps the sentinel silent everywhere; shrinking
    windows are fine (late spikes fall outside the next window).
    """
    width = in_width
    for li, layer in enumerate(cfg.layers):
        need = _layer_input_width(layer, width)
        if layer.column.p != need:
            raise ValueError(
                f"layer {li}: column.p={layer.column.p} but connectivity "
                f"provides {need} inputs"
            )
        if li > 0 and layer.column.t_max > cfg.layers[li - 1].column.t_max:
            raise ValueError(
                f"layer {li}: t_max={layer.column.t_max} exceeds layer "
                f"{li - 1}'s t_max={cfg.layers[li - 1].column.t_max}; the "
                "upstream no-spike sentinel would alias into a live spike"
            )
        width = layer.columns * layer.column.q


def in_width(cfg: NetworkConfig) -> int:
    """Input volley width layer 0's connectivity plan expects.

    The inverse of ``_layer_input_width`` for the first layer — front-ends
    (e.g. the simulator's encoder) size their volleys from this instead of
    re-deriving connectivity semantics.
    """
    layer0 = cfg.layers[0]
    if layer0.connectivity == "full":
        return layer0.column.p
    return layer0.columns * layer0.column.p


def out_width(cfg: NetworkConfig) -> int:
    """Width of the final layer's concatenated post-WTA volley."""
    last = cfg.layers[-1]
    return last.columns * last.column.q


def init_params(rng: jax.Array, cfg: NetworkConfig, in_width: int) -> list:
    """Per-layer params: list of {'w': [columns, p, q]} stacked over columns."""
    validate(cfg, in_width)
    params = []
    for layer in cfg.layers:
        rng, sub = jax.random.split(rng)
        keys = jax.random.split(sub, layer.columns)
        w = jax.vmap(lambda k: column_lib.init_params(k, layer.column)["w"])(keys)
        params.append({"w": w})
    return params


def _split_columns(x: jnp.ndarray, layer: LayerConfig) -> jnp.ndarray:
    """Distribute a volley over a layer's columns: [..., in_w] -> [..., c, p]."""
    c = layer.columns
    if layer.connectivity == "full":
        return jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (c, x.shape[-1]))
    return x.reshape(x.shape[:-1] + (c, layer.column.p))


@functools.partial(jax.jit, static_argnames=("layer", "mode"))
def _apply_layer(
    lp: dict, x: jnp.ndarray, layer: LayerConfig, mode: str
) -> jnp.ndarray:
    """x: [..., in_width] -> [..., columns * q] post-WTA spike times.

    Jitted per (layer, mode): the greedy handoff between layers is one
    compiled call, not a Python loop over columns.
    """
    xc = _split_columns(x, layer)

    def one(w, xi):  # w: [p, q]; xi: [..., p]
        y, _ = column_lib.apply({"w": w}, xi, layer.column, mode)
        return y

    y = jax.vmap(one, in_axes=(0, -2), out_axes=-2)(lp["w"], xc)
    return y.reshape(y.shape[:-2] + (layer.columns * layer.column.q,))


def apply(
    params: list, x_times: jnp.ndarray, cfg: NetworkConfig, mode: str = "auto"
) -> jnp.ndarray:
    """Forward a volley through all layers; returns final spike volley.

    ``mode`` resolves per layer through ``backend.resolve`` (inside
    ``column.apply``), so the hybrid 'auto' forward — event where exact,
    cycle for LIF — applies layer by layer.
    """
    validate(cfg, x_times.shape[-1])
    h = x_times
    for lp, layer in zip(params, cfg.layers):
        h = _apply_layer(lp, h, layer, mode)
    return h


def cluster_assignments(
    params: list, x_times: jnp.ndarray, cfg: NetworkConfig, mode: str = "auto"
) -> jnp.ndarray:
    """Winner index in the final concatenated volley = cluster id.

    Volleys where no output neuron spikes map to ``out_width(cfg)`` (the
    'unclustered' bucket), mirroring ``column.cluster_assignments``.
    """
    y = apply(params, x_times, cfg, mode)
    t_max = cfg.layers[-1].column.t_max
    any_spike = (y < t_max).any(axis=-1)
    idx = jnp.argmin(y, axis=-1)
    return jnp.where(any_spike, idx, out_width(cfg)).astype(TIME_DTYPE)


# ------------------------------------------------------------ layer training
def _fused_group_key(layer: LayerConfig):
    """Layers can share one compiled padded scan iff they vmap the same
    column count with the same static hyper-parameters; only then is a
    shared padding envelope worth paying for."""
    c = layer.column
    return (layer.columns, c.neuron.w_max, c.neuron.response, c.wta.k, c.stdp)


def _fused_envelopes(
    layers: list[LayerConfig],
    n_volleys: Optional[int] = None,
    epochs: int = 1,
) -> list[tuple[int, int, int]]:
    """Per-layer (p, q, t_window) padding envelope, in input order.

    Layers group by ``_fused_group_key``; within a group, members pack
    into shared envelopes via the central bucket policy
    (``backend.envelope_buckets``, greedy largest-first under the plan's
    waste cap — ``backend.ENVELOPE_WASTE_CAP`` unless a device
    calibration plus the stream-length hint derive a break-even cap) —
    size-compatible heterogeneous layers share one compiled step, badly
    mismatched ones get their own envelope.  The same policy buckets
    heterogeneous design sweeps in
    ``simulator.cluster_time_series_many``.
    """
    by_key: dict[tuple, list[int]] = {}
    for i, l in enumerate(layers):
        by_key.setdefault(_fused_group_key(l), []).append(i)
    envs: list = [None] * len(layers)
    for idxs in by_key.values():
        shapes = [
            (layers[i].column.p, layers[i].column.q, layers[i].column.t_max)
            for i in idxs
        ]
        for env, members in backend_lib.envelope_buckets(
            shapes, n_volleys=n_volleys, epochs=epochs
        ):
            for m in members:
                envs[idxs[m]] = env
    return envs


def _fit_layer_fused(
    w: jnp.ndarray,
    hc: jnp.ndarray,
    cfg: ColumnConfig,
    envelope: tuple[int, int, int],
    epochs: int,
    plan_sink: Optional[list] = None,
    rng: Optional[jax.Array] = None,
) -> jnp.ndarray:
    """Train one layer's columns on the fused path.  [c,p,q],[N,c,p] -> [c,p,q].

    Pads weights and volleys into the layer group's shared envelope and
    drives ``backend.fit_padded`` — the envelope-keyed AOT executable
    cache over ``fused_column.fit_scan_padded`` — with the layer's columns
    as the design axis: shape-compatible layers (and equal-envelope design
    sweeps in the same process) share ONE compiled executable, and a
    persistent cache (``backend.compile_cache``) extends that across
    processes.  The
    lowering comes from ``backend.padded_lowering``: the Mosaic kernel on
    TPU (the layer's threshold / window / live-q / mus ride along as
    runtime operands), the jnp reference body elsewhere — and fusability is
    checked against that lowering.  A stochastic layer draws column k's
    stream under ``_column_stream_keys(rng, c)[k]``, as its solver twin
    does.
    """
    lowering = backend_lib.padded_lowering(cfg.neuron.response)
    fused_column.check_fusable(cfg, lowering)
    c = w.shape[0]
    p_env, q_env, t_window = envelope
    w_pad = (
        jnp.zeros((c, p_env, q_env), jnp.float32)
        .at[:, : cfg.p, : cfg.q]
        .set(w.astype(jnp.float32))
    )
    # padding synapses are silent: any time >= the traced t_max never fires
    xs = jnp.full(hc.shape[:-1] + (p_env,), t_window, TIME_DTYPE)
    xs = xs.at[..., : cfg.p].set(hc.astype(TIME_DTYPE))
    thresholds = jnp.full((c,), cfg.neuron.threshold, jnp.float32)
    t_maxes = jnp.full((c,), cfg.t_max, TIME_DTYPE)
    q_actives = jnp.full((c,), cfg.q, TIME_DTYPE)
    # one ExecutionPlan per (layer, envelope): blocking comes from the
    # roofline cost model when a calibration is active, the hand-tuned
    # constants otherwise — fit_padded would resolve the same plan from the
    # same inputs, so pinning v_blk/t_blk here changes nothing but lets the
    # choice be recorded alongside the trained weights.
    plan = backend_lib.execution_plan(
        "fit", lowering, c, p_env, q_env, t_window, hc.shape[0], epochs,
        w_max=cfg.neuron.w_max, response=cfg.neuron.response,
    )
    keys = None
    if cfg.stdp.mode == "stochastic":
        keys = _column_stream_keys(rng, c)
    w_new = backend_lib.fit_padded(
        w_pad, xs, thresholds, t_maxes, q_actives, keys=keys,
        t_window=t_window, w_max=cfg.neuron.w_max, wta_k=cfg.wta.k,
        mu_capture=cfg.stdp.mu_capture, mu_backoff=cfg.stdp.mu_backoff,
        mu_search=cfg.stdp.mu_search,
        stabilize=cfg.stdp.stabilizer == "half",
        response=cfg.neuron.response, epochs=epochs, lowering=lowering,
        v_blk=plan.v_blk, t_blk=plan.t_blk,
    )
    if plan_sink is not None:
        plan_sink.append(plan.meta())
    return w_new[:, : cfg.p, : cfg.q]


def _column_stream_keys(rng: jax.Array, columns: int) -> jnp.ndarray:
    """[columns, 2] i32 stochastic-STDP stream keys of a layer's columns,
    from the layer's training key: ``stdp.stream_key`` of its split."""
    return jax.vmap(stdp.stream_key)(jax.random.split(rng, columns))


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "solver_mode", "epochs"),
    donate_argnums=(0,),
)
def _layer_solver_fit_scan(
    w: jnp.ndarray,
    xs: jnp.ndarray,
    rng: jax.Array,
    cfg: ColumnConfig,
    solver_mode: str,
    epochs: int,
) -> jnp.ndarray:
    """One layer's epochs x volleys on the event/cycle solvers, one program.

    ``w``: [c, p, q] (donated), ``xs``: [N, c, p].  The scan body is the
    shared ``backend.solver_volley_step`` vmapped over the columns axis, so
    the full config surface (LIF, stochastic STDP, random tie-break) trains
    with a single compilation per (layer config, shape) — ``cfg`` is a
    static argument here, so unlike the fused path a threshold change does
    retrace.
    """
    n = xs.shape[0]
    c = w.shape[0]
    s_keys = _column_stream_keys(rng, c)

    def volley(carry, inp):
        wc, key = carry
        xt, i, v = inp  # xt: [c, p]
        kv = jax.random.fold_in(key, i)
        keys = jax.random.split(kv, c)
        w2, _ = jax.vmap(
            lambda wi, xi, ki, si: backend_lib.solver_volley_step(
                wi, xi, ki, cfg, solver_mode, stream=(si, v)
            )
        )(wc, xt, keys, s_keys)
        return (w2, key), None

    def epoch(carry, e):
        wc, key = carry
        ke = jax.random.fold_in(key, e)
        idx = jnp.arange(n, dtype=jnp.int32)
        (w2, _), _ = jax.lax.scan(
            volley, (wc, ke), (xs, idx, e * n + idx)
        )
        return (w2, key), None

    (w, _), _ = jax.lax.scan(epoch, (w, rng), jnp.arange(epochs))
    return w


def fit_greedy(
    params: list,
    x_times: jnp.ndarray,
    cfg: NetworkConfig,
    epochs: int = 8,
    mode: str = "auto",
    rng: Optional[jax.Array] = None,
    plan_sink: Optional[list] = None,
) -> list:
    """Greedy layer-wise unsupervised STDP training.

    Each layer is trained to convergence on the (frozen) output of the stack
    below it, then frozen in turn — the online-learning recipe the hardware
    implements with per-column local learning only.

    Per layer, the entire epochs x volleys loop is ONE jitted, donated
    ``lax.scan`` on the backend ``mode`` resolves to for that layer's column
    config, and the handoff forward of the frozen layer is one jitted call.
    Layers sharing a shape compile once; refitting recompiles nothing.

    Args:
      mode: 'auto' | 'event' | 'cycle' | 'pallas', resolved *per layer*
        through ``backend.resolve`` — 'auto' routes each layer to the fused
        padded scan whenever its config fits the fused contract (RNL,
        expected or stochastic STDP, index tie-break) and to the event/cycle
        solvers
        otherwise; explicit names force that backend for every layer and
        raise on layers outside its contract.  Under 'pallas' the padded
        scan lowers via ``backend.padded_lowering`` (Mosaic kernel on TPU,
        reference body elsewhere).
      rng: PRNG key.  Required whenever any layer's config is stochastic —
        ``wta.tie_break == 'random'`` or ``stdp.mode == 'stochastic'`` —
        and never silently defaulted for those (a loud ValueError instead);
        deterministic configs may omit it.  A stochastic layer draws its
        columns' streams from its split of ``rng`` on the fused and the
        solver path alike.
      plan_sink: optional list; each fused layer appends its
        ``ExecutionPlan.meta()`` dict (in layer order) so callers can
        record which blocking policy trained the weights without changing
        the returned params contract.  Solver layers append nothing.
    """
    if rng is None:
        # mirror the single-column guards: never silently substitute a
        # fixed key where training is meant to be randomized
        for li, layer in enumerate(cfg.layers):
            if layer.column.wta.tie_break == "random":
                raise ValueError(
                    f"layer {li}: tie_break='random' requires a PRNG key"
                )
            if layer.column.stdp.mode == "stochastic":
                raise ValueError(
                    f"layer {li}: stochastic STDP requires a PRNG key"
                )
        rng = jax.random.key(0)
    validate(cfg, x_times.shape[-1])
    h = x_times.reshape((-1, x_times.shape[-1]))

    names = [
        backend_lib.resolve(mode, layer.column, training=True)
        for layer in cfg.layers
    ]
    fused_idx = [i for i, nm in enumerate(names) if nm == "pallas"]
    env_by_layer = dict(zip(
        fused_idx,
        _fused_envelopes(
            [cfg.layers[i] for i in fused_idx],
            n_volleys=h.shape[0], epochs=epochs,
        ),
    ))

    new_params = []
    for li, (lp, layer, name) in enumerate(zip(params, cfg.layers, names)):
        rng, sub = jax.random.split(rng)
        hc = _split_columns(h, layer)  # [N, c, p]
        if name == "pallas":
            w = _fit_layer_fused(
                lp["w"], hc, layer.column, env_by_layer[li], epochs,
                plan_sink=plan_sink, rng=sub,
            )
        else:
            # copy: the scan donates its weight buffer; the caller keeps params
            w0 = jnp.array(lp["w"], jnp.float32, copy=True)
            w = _layer_solver_fit_scan(w0, hc, sub, layer.column, name, epochs)
        new_params.append({"w": w})
        if li < len(cfg.layers) - 1:  # the last handoff has no consumer
            h = _apply_layer({"w": w}, h, layer, mode)
    return new_params
