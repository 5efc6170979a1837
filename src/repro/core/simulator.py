"""TNNGen functional simulator front-end (paper §II-A).

Ties encoding + column/network inference + online STDP + clustering metrics
into the "rapid application exploration" loop the paper describes.  The
``mode`` knob selects a backend from the unified registry
(``repro.core.backend``) and means the same thing for single columns and
multi-layer networks:

  'auto'   — hybrid: event-driven closed form where exact (RNL/SNL),
             cycle-accurate scan where required (LIF); training routes to
             the fused column step whenever the config fits its contract.
  'event'  — force the closed form.
  'cycle'  — force cycle-accurate lax.scan (bit-identical to generated RTL).
  'pallas' — force the fused kernel path (Mosaic on TPU; the jnp reference
             lowering of the same fused step elsewhere).

Three clustering front-ends share the loop:

* ``cluster_time_series`` — one column design, one stream.
* ``cluster_time_series_many`` — a whole *design sweep*, envelope-bucketed:
  designs partition into shared (p, q, t_max) padding envelopes under the
  central waste cap (``backend.envelope_buckets``), each bucket runs as ONE
  compiled program with the fused training step over the design axis
  (threshold / window / live-neuron count become traced per-design
  scalars), advancing ``backend.volley_block`` volleys per scan step, the
  design axis sharded across local devices where ``backend.design_mesh``
  finds one; assignment batches the whole stream instead of scanning it.
  The padded scans live in ``repro.kernels.fused_column``.  This is the
  engine ``repro.dse.explore`` drives for design-space exploration.
* ``cluster_time_series_network`` — a multi-layer ``NetworkConfig`` design
  through the same encode -> fit -> assign -> rand-index loop, trained
  greedily layer-by-layer via ``network.fit_greedy`` (each layer one jitted
  donated scan on the resolved backend).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import backend as backend_lib
from repro.core import column as column_lib
from repro.core import encoding
from repro.core import stdp as stdp_lib
from repro.core.types import ColumnConfig, NetworkConfig, TIME_DTYPE
from repro.kernels import fused_column


@dataclasses.dataclass
class ClusteringResult:
    assignments: np.ndarray  # [N] cluster ids (q == unclustered)
    rand_index: float
    # Trained parameters, one dict shape across every front-end so
    # downstream consumers (forecaster features, examples, DSE) can rely
    # on it: single-column front-ends (``cluster_time_series`` and each
    # sweep member of ``cluster_time_series_many``) return ``{'w': [p, q]}``
    # cropped to the design's true size; the network front-end returns
    # ``{'layers': [{'w': [columns, p, q]}, ...]}``.
    params: dict
    train_seconds: float
    mode: str
    # Lowering the fused training path actually ran on this host
    # ('mosaic' | 'interpret' | 'reference'), '' when training resolved to
    # the event/cycle solvers only, comma-joined when a network's fused
    # layers mixed lowerings (e.g. 'mosaic,reference' for RNL + SNL layers
    # on TPU).
    lowering: str = ""
    # Sweep metadata (``cluster_time_series_many``): how many envelope
    # buckets the sweep split into, and how many devices this design's
    # bucket sharded its design axis across (1 = single-device fallback).
    buckets: int = 1
    shards: int = 1
    # Degradation count (``on_error='isolate'`` sweeps only): how many
    # ladder rungs failed before the one recorded in ``lowering`` ran.
    # 0 = first-choice lowering succeeded.
    retries: int = 0
    # ExecutionPlan metadata of the fused fit that produced these params
    # (``ExecutionPlan.meta()``: v_blk/t_blk/shards/waste_cap/predicted
    # step time + whether the cost model or the constants chose them);
    # None when training took a solver path with no plan.  Observability
    # only — a plan changes blocking, never the result recorded here.
    plan: Optional[dict] = None


@dataclasses.dataclass
class EvalFailure:
    """A quarantined design evaluation — the structured no-crash outcome.

    Fault-isolated sweeps (``cluster_time_series_many(on_error='isolate')``
    and ``dse.explore``) convert per-design failures into these records
    instead of aborting the run: the design is quarantined, every other
    design's result is untouched (bit-identical to a failure-free sweep —
    bucketing and the degradation ladder never change surviving results).

    Attributes:
      index: the design's position in the sweep's input order.
      stage: where it failed — 'fit' (every ladder rung raised), 'assign'
        (training succeeded, assignment raised), 'weights' (non-finite
        weights after training), or 'silent' (no output spikes on any
        volley, so the Rand index is undefined).
      error: the final exception repr, or a diagnostic for the
        weights/silent guards.
      lowerings: the ladder rungs attempted, in order.
      retries: failed attempts before giving up (== len(lowerings) for
        'fit' failures; the rung that *ran* for post-check failures is
        last in ``lowerings``).
    """

    index: int
    stage: str
    error: str
    lowerings: tuple = ()
    retries: int = 0

    @property
    def rand_index(self) -> float:
        """NaN — a quarantined design carries no quality information
        (lets failure records ride result lists without isinstance
        checks at every consumer)."""
        return float("nan")


SweepOutcome = Union[ClusteringResult, EvalFailure]


def suggest_threshold(cfg: ColumnConfig) -> float:
    """Default firing threshold scaling used by the simulator.

    Expected saturated potential is p * w_max / 2 for uniform weights; firing
    around a quarter of that keeps spike times mid-window, the operating
    point the TNN microarchitecture calibrates for.
    """
    return max(1.0, 0.25 * cfg.p * cfg.neuron.w_max / 2.0)


def _check_width(volleys: jnp.ndarray, width: int) -> jnp.ndarray:
    if volleys.shape[-1] != width:
        raise ValueError(
            f"encoded width {volleys.shape[-1]} != design input width {width}"
        )
    return volleys


def _encode_width(
    x: jnp.ndarray, t_max: int, width: int, encoder: str
) -> jnp.ndarray:
    return _check_width(encoding.encode(x, t_max, encoder), width)


def _encode(x: jnp.ndarray, cfg: ColumnConfig, encoder: str) -> jnp.ndarray:
    return _encode_width(x, cfg.t_max, cfg.p, encoder)


def cluster_time_series(
    series: np.ndarray,
    labels: Optional[np.ndarray],
    cfg: ColumnConfig,
    epochs: int = 8,
    mode: str = "auto",
    seed: int = 0,
    encoder: str = "latency",
) -> ClusteringResult:
    """End-to-end: encode -> online STDP -> assign clusters -> rand index.

    Args:
      series: [N, L] real-valued time series (L == cfg.p for 'latency',
        2L == cfg.p for 'onoff').
      labels: [N] integer class labels, or None (rand_index = nan).
      cfg: column config (p x q).
      epochs: STDP passes over the data.
      mode: simulation backend, resolved by ``backend.resolve`` (see module
        docstring); forcing 'pallas' on a config outside the fused contract
        raises rather than silently switching semantics.
      seed: PRNG seed — the one source of randomness (weight init, plus the
        per-volley keys stochastic/random configs consume); equal seeds
        reproduce the run exactly on every host.
      encoder: 'latency' or 'onoff'.

    The returned ``ClusteringResult.lowering`` records which lowering of the
    fused algebra training actually ran ('mosaic' on TPU, 'reference'
    elsewhere), or '' when it trained on the event/cycle solvers.
    """
    from repro.clustering.metrics import rand_index as rand_index_fn

    volleys = _encode(jnp.asarray(series), cfg, encoder)
    rng = jax.random.key(seed)
    rng, init_key = jax.random.split(rng)
    params = column_lib.init_params(init_key, cfg)

    t0 = time.perf_counter()
    params = column_lib.fit(params, volleys, cfg, epochs=epochs, mode=mode, rng=rng)
    assignments = np.asarray(
        column_lib.cluster_assignments(params, volleys, cfg, mode)
    )
    train_seconds = time.perf_counter() - t0

    ri = float("nan")
    if labels is not None:
        ri = float(rand_index_fn(np.asarray(labels), assignments))
    resolved = backend_lib.resolve(mode, cfg, training=True)
    lowering = (
        backend_lib.padded_lowering(cfg.neuron.response)
        if resolved == "pallas"
        else ""
    )
    return ClusteringResult(
        assignments, ri, params, train_seconds, mode, lowering
    )


def assign_time_series(
    series: np.ndarray,
    cfg: ColumnConfig,
    params: dict,
    encoder: str = "latency",
) -> np.ndarray:
    """Assignment-only entry: cluster ids from frozen trained weights.

    The inference half of ``cluster_time_series`` on its own — encode one
    series ``[L]`` (returns a scalar id) or a micro-batch ``[N, L]``
    (returns ``[N]`` ids) and fire it against ``params['w']`` with no
    training pass.  Configs inside the fused fire contract route through
    ``backend.assign_padded``, the envelope-keyed AOT executable cache, so
    repeated calls at the same batch shape dispatch ONE cached executable
    (the streaming service batches requests into exactly this path);
    everything else (LIF) falls back to the solver-backed
    ``column.cluster_assignments``.  Ids follow the assignment contract:
    earliest-firing neuron index, ``cfg.q`` for a silent (unclustered)
    volley.
    """
    x = jnp.asarray(series)
    single = x.ndim == 1
    if single:
        x = x[None]
    volleys = _encode(x, cfg, encoder)
    try:
        fused_column.check_fusable(cfg, "reference")
    except ValueError:
        ids = np.asarray(
            column_lib.cluster_assignments(params, volleys, cfg, "auto")
        )
        return ids[0] if single else ids
    w = jnp.asarray(params["w"], jnp.float32)[None]  # [1, p, q]
    asg = np.asarray(
        backend_lib.assign_padded(
            w,
            volleys[:, None, :],  # [N, 1, p]
            jnp.asarray([cfg.neuron.threshold], jnp.float32),
            jnp.asarray([cfg.t_max], TIME_DTYPE),
            jnp.asarray([cfg.q], TIME_DTYPE),
            t_window=cfg.t_max,
            wta_k=cfg.wta.k,
            response=cfg.neuron.response,
            lowering=backend_lib.assign_lowering(cfg.neuron.response, w[0]),
            w_max=cfg.neuron.w_max,
        )[0]
    )
    return asg[0] if single else asg


# --------------------------------------------------- batched design sweep
# Counters the sweep records (``repro.obs.count``, while a profiler runs):
# designs whose batched assign took the Mosaic kernel / the reference
# body, counted per bucket where the lowering is chosen, and designs the
# degradation ladder evaluated on the 'cycle' solver rung; per sweep, the
# designs the stream was encoded for and the encodes computed (one per
# distinct t_max).
ASSIGN_MOSAIC = "sim.assign_mosaic"
ASSIGN_REFERENCE = "sim.assign_reference"
SOLVER_DESIGNS = "sim.solver_designs"
ENCODE_DESIGNS = "sim.encode_designs"
ENCODE_RUNS = "sim.encode_runs"
SWEEP_COUNTERS = (
    ASSIGN_MOSAIC, ASSIGN_REFERENCE, SOLVER_DESIGNS, ENCODE_DESIGNS,
    ENCODE_RUNS,
)


def _fit_sharded(w, xs, thresholds, t_maxes, q_actives, keys, **statics):
    """``fused_column.fit_scan_padded`` with the stream keys positional
    (None for expected-mode STDP), so ``backend.shard_designs`` shards
    them with their designs (a module-level function: the shard wrapper
    memoizes on it)."""
    return fused_column.fit_scan_padded(
        w, xs, thresholds, t_maxes, q_actives, keys=keys,
        stochastic=keys is not None, **statics,
    )


# the sharded fit's program is named like the unsharded one's in profiles
_fit_sharded.__name__ = _fit_sharded.__qualname__ = "fit_scan_padded"


def _sweep_bucket(
    cfgs: Sequence[ColumnConfig],
    idxs: Sequence[int],
    envelope: tuple[int, int, int],
    enc: Sequence[jnp.ndarray],
    w_init: Sequence[np.ndarray],
    epochs: int,
    lowering: str,
    stream_keys: Optional[Sequence] = None,
) -> tuple[np.ndarray, list[jnp.ndarray], int, dict]:
    """Train + assign one envelope bucket of a design sweep.

    Pads the bucket's members into its shared (p_env, q_env, t_window)
    envelope, shards the design axis across local devices when the central
    policy finds a mesh (``backend.design_mesh``; None = single-device
    fallback, arrays stay put), and drives one volley-blocked
    ``fit_scan_padded`` plus one batched ``assign_padded``.  On a single
    device the calls route through ``backend.fit_padded`` /
    ``backend.assign_padded`` — the envelope-keyed AOT executable cache —
    so buckets with equal envelope shapes and member counts share ONE
    compiled executable across sweep calls in this process, and across
    processes once ``backend.compile_cache`` is enabled; sharded buckets
    run the jitted scans per device on their own designs
    (``backend.shard_designs``).

    Blocking and sharding come from the bucket's ``ExecutionPlan``
    (``backend.execution_plan``; the documented constants when no device
    calibration is active) — observability rides along in the returned
    plan metadata.

    ``stream_keys`` (stochastic STDP: one [2] i32 stream key per design
    of the sweep, None otherwise) ride the design axis like ``w_init``.

    Returns (assignments [Db, N], cropped per-design weights, shard
    count, plan metadata dict).
    """
    c0 = cfgs[idxs[0]]
    p_env, q_env, t_window = envelope
    db = len(idxs)
    n = enc[idxs[0]].shape[0]
    with obs.span("sim.bucket", envelope=envelope, designs=db, volleys=n,
                  lowering=lowering):
        with obs.span("sim.pad"):
            # Stack padded volleys [Db, N, p_env] in ONE shot: the members'
            # encodes are stacked and the whole [Db, N, p] block lands in the
            # silent-padded buffer with a single set — no per-design
            # ``.at[i].set`` dispatch chain, O(1) graph nodes however many
            # designs ride the bucket.  (Designs currently share p — the
            # encoder pins it — so the stack is uniform; the single set keeps
            # the p < p_env envelope case working should a future per-design
            # front-end relax that.)
            encb = jnp.stack([enc[i] for i in idxs])  # [Db, N, p]
            xs = jnp.full((db, n, p_env), t_window, TIME_DTYPE)
            xs = xs.at[:, :, : encb.shape[-1]].set(encb)
            xs = jnp.swapaxes(xs, 0, 1)  # scan axis first: [N, Db, p_env]

            # Per-design init draws stay per-(key, shape) — seed semantics —
            # but the padded stack is assembled host-side and shipped as ONE
            # buffer instead of a D-deep ``.at[i].set`` graph.
            w0_np = np.zeros((db, p_env, q_env), np.float32)
            for j, i in enumerate(idxs):
                c = cfgs[i]
                w0_np[j, : c.p, : c.q] = w_init[i]
            w0 = jnp.asarray(w0_np)
            thresholds = jnp.asarray(
                [cfgs[i].neuron.threshold for i in idxs], jnp.float32
            )
            t_maxes = jnp.asarray(
                [cfgs[i].t_max for i in idxs], TIME_DTYPE
            )
            q_actives = jnp.asarray([cfgs[i].q for i in idxs], TIME_DTYPE)
            keys = None
            if stream_keys is not None:
                keys = jnp.asarray(
                    np.stack([np.asarray(stream_keys[i]) for i in idxs])
                )

            # the bucket's execution plan: blocking + sharding for this
            # envelope (cost model when calibrated, the documented constants
            # otherwise); returned as metadata so ClusteringResult/DSE
            # journals record WHY
            fit_plan = backend_lib.execution_plan(
                "fit", lowering, db, p_env, q_env, t_window, n, epochs,
                w_max=c0.neuron.w_max, response=c0.neuron.response,
            )

            # shard the design axis across local devices: per-design work is
            # independent, so GSPMD splits the jitted scans with no
            # collectives; mesh=None (single device / indivisible Db) leaves
            # every array put.  The mesh is built from the plan's shard count
            # — ONE policy output, so the recorded plan and the actual
            # placement cannot disagree.  The legacy call shape is kept
            # whenever the plan agrees with the default divisor policy
            # (always, uncalibrated) so tests stubbing ``design_mesh`` to
            # force the unsharded path keep working.
            if fit_plan.shards == backend_lib.design_shards(db):
                mesh = backend_lib.design_mesh(db)
            else:
                mesh = backend_lib.design_mesh(db, shards=fit_plan.shards)
            shards = fit_plan.shards if mesh is not None else 1
            w0 = backend_lib.shard_design_axis(mesh, w0, axis=0)
            xs = backend_lib.shard_design_axis(mesh, xs, axis=1)
            thresholds = backend_lib.shard_design_axis(mesh, thresholds)
            t_maxes = backend_lib.shard_design_axis(mesh, t_maxes)
            q_actives = backend_lib.shard_design_axis(mesh, q_actives)
            if keys is not None:
                keys = backend_lib.shard_design_axis(mesh, keys)

        fit_kw = dict(
            t_window=t_window, w_max=c0.neuron.w_max, wta_k=c0.wta.k,
            mu_capture=c0.stdp.mu_capture, mu_backoff=c0.stdp.mu_backoff,
            mu_search=c0.stdp.mu_search,
            stabilize=c0.stdp.stabilizer == "half",
            response=c0.neuron.response, epochs=epochs, lowering=lowering,
            # v_blk defaults to the central backend.volley_block policy
        )
        with obs.span("sim.fit"):
            if mesh is None:
                # single-device: go through the envelope-keyed AOT
                # executable cache, so equal-envelope buckets share ONE
                # executable across sweep calls (and across processes under
                # backend.compile_cache)
                w = backend_lib.fit_padded(
                    w0, xs, thresholds, t_maxes, q_actives, keys=keys,
                    **fit_kw
                )
            else:
                # sharded operands: each device runs the jitted scan on its
                # own designs (shard_map — Mosaic kernels cannot be
                # auto-partitioned); the plan rides along as a hashable static
                w = backend_lib.shard_designs(
                    mesh, _fit_sharded, (0, 1, 0, 0, 0, 0),
                    plan=fit_plan, **fit_kw,
                )(w0, xs, thresholds, t_maxes, q_actives, keys)
        # assignment batches volleys (kernel grid / vmapped blocks); the
        # kernel fires on the integer weight grid, so it is only
        # auto-selected when the trained weights concretely sit on that grid
        # (pure lowering choice) — float weights keep the reference fire on
        # every host.  On a kernel host this is the first wait on the fit.
        with obs.span("sim.lowering"):
            asg_lowering = backend_lib.assign_lowering(
                c0.neuron.response, w
            )
        obs.count(
            ASSIGN_REFERENCE if asg_lowering == "reference" else ASSIGN_MOSAIC,
            db,
        )
        asg_kw = dict(
            t_window=t_window, wta_k=c0.wta.k,
            response=c0.neuron.response, lowering=asg_lowering,
            w_max=c0.neuron.w_max,
        )
        with obs.span("sim.assign"):
            if mesh is None:
                asg = np.asarray(
                    backend_lib.assign_padded(
                        w, xs, thresholds, t_maxes, q_actives, **asg_kw
                    )
                )
            else:
                asg = np.asarray(
                    backend_lib.shard_designs(
                        mesh, fused_column.assign_padded, (0, 1, 0, 0, 0),
                        **asg_kw,
                    )(w, xs, thresholds, t_maxes, q_actives)
                )
        w_out = [
            jnp.asarray(w[j, : cfgs[i].p, : cfgs[i].q])
            for j, i in enumerate(idxs)
        ]
        return asg, w_out, shards, fit_plan.meta()


def _eval_design_solver(
    cfg: ColumnConfig, volleys: jnp.ndarray, w0: np.ndarray, epochs: int,
    stream_key=None,
) -> tuple[np.ndarray, jnp.ndarray]:
    """Bottom-rung ('cycle') evaluation of ONE design on the solver scan.

    Only reached when ``backend.cycle_exact`` holds for the design, i.e.
    the solver is bit-identical to the fused path (integer init weights
    and integer steps: stochastic STDP on the design's stream key, or
    integer-mu expected STDP with no stabilizer) — the ladder never trades
    semantics for availability.
    """
    rng = None
    if stream_key is not None:
        # a raw key whose words are the stream key: the solver draws from
        # stdp.stream_key(rng), the very key the fused rungs were given
        rng = jax.lax.bitcast_convert_type(
            jnp.asarray(stream_key, jnp.int32), jnp.uint32
        )
    params = column_lib.fit(
        {"w": jnp.asarray(w0)}, volleys, cfg, epochs=epochs, mode="cycle",
        rng=rng,
    )
    asg = np.asarray(
        column_lib.cluster_assignments(params, volleys, cfg, "cycle")
    )
    return asg, jnp.asarray(params["w"])


def _design_guard(
    cfg: ColumnConfig, asg_i: np.ndarray, w_i
) -> Optional[tuple[str, str]]:
    """Post-training degeneracy checks for one design (guarded sweeps).

    Returns (stage, diagnostic) for a quarantinable outcome, None for a
    healthy design: non-finite trained weights (a NaN/inf anywhere makes
    the design's assignments meaningless), or a fully silent design (no
    volley produced an output spike, so every assignment is the
    'unclustered' bucket and the Rand index carries no information).
    """
    w_np = np.asarray(w_i)
    if not np.all(np.isfinite(w_np)):
        return (
            "weights",
            f"non-finite weights after training "
            f"(nan={int(np.isnan(w_np).sum())}, "
            f"inf={int(np.isinf(w_np).sum())})",
        )
    if np.all(np.asarray(asg_i) == cfg.q):
        return (
            "silent",
            "silent design: no output spikes on any volley, "
            "Rand index undefined",
        )
    return None


def _eval_bucket_guarded(
    cfgs: Sequence[ColumnConfig],
    idxs: Sequence[int],
    envelope: tuple[int, int, int],
    enc: Sequence[jnp.ndarray],
    w_init: Sequence[np.ndarray],
    epochs: int,
    lowering: str,
    stream_keys: Optional[Sequence] = None,
) -> list:
    """Fault-isolated evaluation of one envelope bucket.

    Walks the central degradation ladder (``backend.lowering_ladder``)
    bucket-wise first — a rung failure (Mosaic lowering error, OOM) is
    usually envelope-wide, and one retry at the next rung fixes every
    member with one compilation.  Only when *every* fused rung fails
    bucket-wise does it isolate per design: each member re-runs alone
    (its own envelope — bit-identical by the padding contract) down the
    same ladder, then the 'cycle' solver rung where that is provably
    exact, so one degenerate design quarantines itself and never its
    bucket-mates.

    Returns one outcome per member, aligned with ``idxs``: either a
    tuple ``('ok', asg, w, shards, lowering_ran, retries, plan_meta)``
    or an ``EvalFailure``.
    """
    ladder = backend_lib.lowering_ladder(lowering)
    attempts: list[tuple[str, str]] = []
    for low in ladder:
        try:
            asg_b, w_b, shards, plan_meta = _sweep_bucket(
                cfgs, idxs, envelope, enc, w_init, epochs, low, stream_keys
            )
            return [
                ("ok", asg_b[j], w_b[j], shards, low, len(attempts),
                 plan_meta)
                for j in range(len(idxs))
            ]
        except Exception as e:  # noqa: BLE001 — the guard IS the feature
            attempts.append((low, repr(e)))
            backend_lib.warn_step_down("sweep bucket", low, repr(e))
    out = []
    for i in idxs:
        c = cfgs[i]
        d_attempts = list(attempts)
        done = None
        solo_ladder = backend_lib.lowering_ladder(
            lowering, cycle_exact=backend_lib.cycle_exact(
                c, jnp.asarray(w_init[i])
            ),
        )[: backend_lib.MAX_EVAL_RETRIES]
        for low in solo_ladder:
            try:
                if low == "cycle":
                    asg_i, w_i = _eval_design_solver(
                        c, enc[i], w_init[i], epochs,
                        None if stream_keys is None else stream_keys[i],
                    )
                    obs.count(SOLVER_DESIGNS)
                    plan_i = None
                else:
                    asg_1, w_1, _, plan_i = _sweep_bucket(
                        cfgs, [i], (c.p, c.q, c.t_max), enc, w_init,
                        epochs, low, stream_keys,
                    )
                    asg_i, w_i = asg_1[0], w_1[0]
                done = ("ok", asg_i, w_i, 1, low, len(d_attempts), plan_i)
                break
            except Exception as e:  # noqa: BLE001
                d_attempts.append((low, repr(e)))
                backend_lib.warn_step_down(f"design {i}", low, repr(e))
        if done is None:
            out.append(
                EvalFailure(
                    index=i,
                    stage="fit",
                    error=d_attempts[-1][1],
                    lowerings=tuple(l for l, _ in d_attempts),
                    retries=len(d_attempts),
                )
            )
        else:
            out.append(done)
    return out


def cluster_time_series_many(
    series: np.ndarray,
    labels: Optional[np.ndarray],
    cfgs: Sequence[ColumnConfig],
    epochs: int = 8,
    seed: int = 0,
    encoder: str = "latency",
    waste_cap: Optional[float] = None,
    max_bucket: Optional[int] = None,
    on_error: str = "raise",
    w_init: Optional[Sequence[np.ndarray]] = None,
    bucket_callback: Optional[Callable] = None,
    monitor=None,
    stream_keys: Optional[Sequence] = None,
) -> list[SweepOutcome]:
    """Sweep several column designs over one stream, envelope-bucketed.

    Designs are partitioned into **envelope buckets** by the central
    policy ``backend.envelope_buckets``: members pack into a shared
    (p, q, t_max) padding envelope while padding keeps every member's
    per-volley fire volume within ``waste_cap`` (default
    ``backend.ENVELOPE_WASTE_CAP``) of its true volume — so a 5-neuron
    design never pays a 96-neuron design's padding on every volley.  Each
    bucket runs as ONE compiled program: per-design threshold / window /
    live-neuron count become traced scalars — runtime SMEM operands of the
    Mosaic kernel on TPU, ``vmap``-ed operands of the reference body
    elsewhere (``backend.padded_lowering`` picks) — driving a single
    jitted volley-blocked scan (``backend.volley_block`` volleys folded
    per step) plus one batched assignment pass.  Compilation cost is one
    trace per distinct bucket (envelope shape, member count) pair:
    buckets agreeing on both — e.g. same-shape designs split into full
    ``max_bucket`` groups — share one trace, and bucketing never changes
    results: every design trains bit-identically under any envelope that
    contains it, including the old single-global-envelope sweep
    (``waste_cap=float('inf')`` reproduces that exactly).

    Each bucket's design axis is **sharded across local devices** when the
    central shard policy finds a usable mesh (``backend.design_mesh``;
    per-design work is embarrassingly parallel, so GSPMD splits the scans
    with no collectives).  Single-device hosts fall back to the unsharded
    path with identical results; the shard count rides on
    ``ClusteringResult.shards``.

    This front-end always trains on the fused path (there is no ``mode``
    knob): every design must fit the fused contract — expected- or
    stochastic-mode STDP, index tie-break WTA, and a response the selected
    lowering supports — or the sweep raises up front.  ``seed`` feeds
    weight initialization and, under stochastic STDP, each design's
    stream key — both split per design BEFORE bucketing, so equal seeds
    reproduce the sweep bit-for-bit on every host under every
    bucketing/sharding.  An empty stream (N=0) raises a
    ValueError up front; ``epochs=0`` is well-defined and returns the
    designs' init weights with assignments from those weights.

    Designs must share the response function, STDP rule, WTA config and
    w_max (they are compile-time constants of the fused step); q, t_max and
    threshold may vary freely.  p is pinned by the encoder — every design
    sees the same stream, encoded once per distinct t_max within the call,
    so ``cfg.p`` must equal the encoded width for all of them.
    ``train_seconds`` on every result is the wall time of the whole sweep
    (all buckets), not a per-design share; ``lowering`` records the
    lowering that ran, ``buckets``/``shards`` the bucket count and the
    design's bucket shard count.

    **Fault isolation** (``on_error``): the default ``'raise'`` propagates
    any evaluation failure — one degenerate design aborts the sweep, the
    right behavior for interactive runs and tests.  ``'isolate'`` instead
    converts per-design failures into structured ``EvalFailure`` records
    in the result list and keeps sweeping: a failing bucket retries down
    the central lowering-degradation ladder
    (``backend.lowering_ladder``; a fallback changes the lowering, never
    the semantics), a bucket failing every rung is re-run design-by-design
    so one bad design never quarantines its bucket-mates, and trained
    designs with non-finite weights or no output spikes at all are
    quarantined post-hoc (``EvalFailure.stage`` 'weights' / 'silent').
    Surviving designs are bit-identical to a failure-free sweep.

    ``w_init`` overrides the seed-derived per-design init weights (one
    ``[p, q]`` array per config) — ``dse.explore`` uses it to key inits
    by *candidate* rather than by position, so journal-resumed partial
    sweeps reproduce the full run exactly.  ``stream_keys`` does the same
    for stochastic STDP's per-design stream keys (one [2] i32 array per
    config, ``stdp.stream_key``); by default design i draws under
    ``stream_key(fold_in(rng, i))``, ``rng`` being the half of
    ``split(key(seed))`` that init does not use.  ``bucket_callback(idxs,
    results)`` fires after each bucket's outcomes are final (the journal
    hook: a kill loses at most one bucket); ``monitor`` is an optional
    ``distributed.straggler.StepMonitor`` whose ``start``/``stop``
    bracket every bucket, flagging wall-time outliers.

    Returns one outcome per config, in input order: ``ClusteringResult``
    everywhere under ``'raise'``, ``ClusteringResult | EvalFailure``
    under ``'isolate'``.
    """
    from repro.clustering.metrics import rand_index as rand_index_fn

    if on_error not in ("raise", "isolate"):
        raise ValueError(
            f"unknown on_error: {on_error!r} ('raise' | 'isolate')"
        )
    if not cfgs:
        return []
    c0 = cfgs[0]
    lowering = backend_lib.padded_lowering(c0.neuron.response)
    for c in cfgs:
        fused_column.check_fusable(c, lowering)
        same = (
            c.neuron.response == c0.neuron.response
            and c.neuron.w_max == c0.neuron.w_max
            and c.stdp == c0.stdp
            and c.wta == c0.wta
        )
        if not same:
            raise ValueError(
                "cluster_time_series_many needs designs sharing response, "
                "w_max, STDP and WTA configs"
            )

    with obs.span("sim.many", designs=len(cfgs)):
        x = jnp.asarray(series)
        if x.shape[0] == 0:
            raise ValueError(
                "cluster_time_series_many needs a non-empty stream (got N=0 "
                "series)"
            )
        d = len(cfgs)

        # Encode + init per design BEFORE bucketing: the per-design PRNG key
        # assignment (and with it every result) is a function of the input
        # order alone, never of how designs were bucketed.  The encode
        # depends on the stream, the encoder and t_max alone, so designs
        # sharing a t_max share one array; each checks its own width.
        with obs.span("sim.encode"):
            by_t_max: dict = {}
            enc = []  # D x [N, p]
            for c in cfgs:
                if c.t_max not in by_t_max:
                    by_t_max[c.t_max] = encoding.encode(x, c.t_max, encoder)
                enc.append(_check_width(by_t_max[c.t_max], c.p))
            obs.count(ENCODE_DESIGNS, d)
            obs.count(ENCODE_RUNS, len(by_t_max))
        if w_init is None:
            rng = jax.random.key(seed)
            rng, init_key = jax.random.split(rng)
            keys = jax.random.split(init_key, d)
            w_init = [
                np.asarray(column_lib.init_params(k, c)["w"])
                for k, c in zip(keys, cfgs)
            ]
        else:
            if len(w_init) != d:
                raise ValueError(
                    f"w_init must provide one array per config "
                    f"({len(w_init)} != {d})"
                )
            w_init = [np.asarray(w, np.float32) for w in w_init]
            for w, c in zip(w_init, cfgs):
                if w.shape != (c.p, c.q):
                    raise ValueError(
                        f"w_init shape {w.shape} != design shape {(c.p, c.q)}"
                    )
        if c0.stdp.mode != "stochastic":
            stream_keys = None
        elif stream_keys is None:
            root, _ = jax.random.split(jax.random.key(seed))
            stream_keys = [
                np.asarray(stdp_lib.stream_key(jax.random.fold_in(root, i)))
                for i in range(d)
            ]
        elif len(stream_keys) != d:
            raise ValueError(
                f"stream_keys must provide one key per config "
                f"({len(stream_keys)} != {d})"
            )

        buckets = backend_lib.envelope_buckets(
            [(c.p, c.q, c.t_max) for c in cfgs],
            waste_cap=waste_cap, max_bucket=max_bucket,
            # stream-length hint: lets a calibrated host derive the waste cap
            # from the compile-vs-recurring-waste break-even (constants cap
            # otherwise; an explicit waste_cap always wins either way)
            n_volleys=series.shape[0], epochs=epochs,
        )

        out: list[Optional[SweepOutcome]] = [None] * d
        n_buckets = len(buckets)
        t0 = time.perf_counter()
        for envelope, idxs in buckets:
            if monitor is not None:
                monitor.start()
            if on_error == "isolate":
                evals = _eval_bucket_guarded(
                    cfgs, idxs, envelope, enc, w_init, epochs, lowering,
                    stream_keys,
                )
            else:
                asg_b, w_b, shards, plan_meta = _sweep_bucket(
                    cfgs, idxs, envelope, enc, w_init, epochs, lowering,
                    stream_keys,
                )
                evals = [
                    ("ok", asg_b[j], w_b[j], shards, lowering, 0, plan_meta)
                    for j in range(len(idxs))
                ]
            bucket_out: list[SweepOutcome] = []
            with obs.span("sim.score"):
                for j, i in enumerate(idxs):
                    ev = evals[j]
                    if isinstance(ev, EvalFailure):
                        out[i] = ev
                        bucket_out.append(ev)
                        continue
                    _, asg_i, w_i, shards_i, low_i, retries_i, plan_i = ev
                    if on_error == "isolate":
                        bad = _design_guard(cfgs[i], asg_i, w_i)
                        if bad is not None:
                            out[i] = EvalFailure(
                                index=i, stage=bad[0], error=bad[1],
                                lowerings=(low_i,), retries=retries_i,
                            )
                            bucket_out.append(out[i])
                            continue
                    ri = float("nan")
                    if labels is not None:
                        ri = float(rand_index_fn(
                            np.asarray(labels), np.asarray(asg_i)
                        ))
                    res = ClusteringResult(
                        np.asarray(asg_i), ri, {"w": w_i}, 0.0, "pallas",
                        low_i, buckets=n_buckets, shards=shards_i,
                        retries=retries_i, plan=plan_i,
                    )
                    out[i] = res
                    bucket_out.append(res)
            if monitor is not None:
                monitor.stop()
            if bucket_callback is not None:
                bucket_callback(list(idxs), bucket_out)
        train_seconds = time.perf_counter() - t0
        # every result reports the whole sweep's wall time (documented
        # contract) — patched after the loop so bucket callbacks always see
        # otherwise-final records
        for r in out:
            if isinstance(r, ClusteringResult):
                r.train_seconds = train_seconds
        return out


# --------------------------------------------------- multi-layer networks
def cluster_time_series_network(
    series: np.ndarray,
    labels: Optional[np.ndarray],
    cfg: NetworkConfig,
    epochs: int = 8,
    mode: str = "auto",
    seed: int = 0,
    encoder: str = "latency",
) -> ClusteringResult:
    """End-to-end clustering with a multi-layer TNN design.

    Same loop as ``cluster_time_series`` — encode -> greedy layer-wise
    online STDP -> assign clusters -> rand index — but the design is a
    ``NetworkConfig``: layer l's post-WTA volleys feed layer l+1, each layer
    trains as ONE jitted donated scan on the backend ``mode`` resolves to
    (see ``network.fit_greedy``), and the cluster id of a volley is the
    winner index in the final layer's concatenated output (out_width ==
    the 'unclustered' bucket).

    ``mode`` is resolved per layer (same knob semantics as
    ``network.fit_greedy``); fused layers run the lowering
    ``backend.padded_lowering`` selects, recorded on the result.  ``seed``
    derives both the weight init and the training key handed to
    ``fit_greedy``, so stochastic layer configs are always legally keyed
    here and equal seeds reproduce the run exactly.

    The encoded width must match layer 0's connectivity plan
    (``network.validate``); ``cfg.layers[0]`` fixes the encoder geometry the
    way ``cfg.p`` does for single columns.
    """
    from repro.clustering.metrics import rand_index as rand_index_fn
    from repro.core import network as network_lib

    volleys = _encode_width(
        jnp.asarray(series), cfg.layers[0].column.t_max,
        network_lib.in_width(cfg), encoder,
    )
    rng = jax.random.key(seed)
    rng, init_key = jax.random.split(rng)
    params = network_lib.init_params(init_key, cfg, volleys.shape[-1])

    t0 = time.perf_counter()
    layer_plans: list = []
    params = network_lib.fit_greedy(
        params, volleys, cfg, epochs=epochs, mode=mode, rng=rng,
        plan_sink=layer_plans,
    )
    assignments = np.asarray(
        network_lib.cluster_assignments(params, volleys, cfg, mode)
    )
    train_seconds = time.perf_counter() - t0

    ri = float("nan")
    if labels is not None:
        ri = float(rand_index_fn(np.asarray(labels), assignments))
    lows = {
        backend_lib.padded_lowering(layer.column.neuron.response)
        for layer in cfg.layers
        if backend_lib.resolve(mode, layer.column, training=True) == "pallas"
    }
    # '' when no layer trained fused; comma-joined when fused layers mixed
    # lowerings (e.g. RNL on the Mosaic kernel + SNL on the reference body)
    return ClusteringResult(
        # unified params contract (see ClusteringResult): always a dict —
        # the per-layer param list rides under 'layers'
        assignments, ri, {"layers": params}, train_seconds, mode,
        ",".join(sorted(lows)),
        plan={"layers": layer_plans} if layer_plans else None,
    )
