"""Unified simulation-backend registry and central accelerator dispatch.

Before this module existed, ``core/column.py`` and ``kernels/ops.py`` were
two parallel implementations of the same column semantics, and every Pallas
entry point re-decided ``interpret=True`` on its own.  All execution-path
policy now lives here:

* **Registry** — three named backends sharing one contract:
    'event'  — closed-form event-driven solver (exact for RNL/SNL).
    'cycle'  — cycle-accurate lax.scan (bit-identical to generated RTL,
               required for LIF).
    'pallas' — the fused column step (``kernels/fused_column.py``): RNL fire
               + k-WTA + expected or stochastic STDP in one kernel
               invocation.
  Each backend provides ``fire`` (batched post-WTA forward) and ``fit``
  (online STDP training as ONE jitted, donated lax.scan over epochs x
  volleys — a single compilation per config, no per-epoch dispatch).

* **Lowering policy** — ``pallas_interpret()`` / ``pallas_lowering()`` /
  ``padded_lowering()`` are the ONE place that inspects
  ``jax.default_backend()``.  On TPU the fused step compiles through Mosaic
  — including the padded-envelope scans (design sweep, network layers),
  whose per-design scalars are runtime SMEM operands of the kernel;
  elsewhere it lowers to the pure-jnp reference body (same algebra, same
  results) because the Pallas interpreter is a validation tool, not an
  execution engine.  Pass ``lowering='interpret'`` explicitly to validate
  the kernel off-TPU.

* **Resolution** — ``resolve(mode, cfg, training=...)`` maps the public
  ``mode`` knob ('auto' | 'event' | 'cycle' | 'pallas') to a registry name.
  'auto' keeps the paper's hybrid forward semantics (event where exact,
  cycle for LIF) and routes *training* to the fused path whenever the
  config fits its contract (RNL, expected or stochastic STDP, index
  tie-break).

Multi-layer networks (``repro.core.network``) resolve here too, layer by
layer against each layer's column config.  The full contract is documented
in ``docs/backends.md``.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import pickle
import warnings
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import neuron, stdp, wta
from repro.core.types import ColumnConfig, TIME_DTYPE
from repro.kernels import fused_column


# ----------------------------------------------------------- central policy
def on_tpu() -> bool:
    """True iff jax is executing on TPU.  The ONLY backend probe."""
    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """Central ``interpret`` decision for raw Pallas kernel entry points."""
    return not on_tpu()


def pallas_lowering() -> str:
    """How the fused column step should lower on this host.

    'mosaic' on TPU (real kernels), 'reference' elsewhere — the jnp body of
    the same fused step; the interpreter is only ever chosen explicitly.
    """
    return "mosaic" if on_tpu() else "reference"


def padded_lowering(response: str) -> str:
    """Response-aware lowering for the fused (padded-kernel) paths.

    The Mosaic kernel takes the per-design scalars (threshold, t_max,
    live q, STDP mus) as runtime SMEM operands, so padded heterogeneous
    batches — the design sweep and network layer training — run the real
    kernel on TPU; single-column 'pallas' entry points resolve here too
    (they are the D=1 slice of the same kernel).  The kernel implements
    the RNL plane decomposition only; SNL lowers to the reference body of
    the same algebra everywhere (bit-identical on integer weight grids, so
    this is a lowering choice, not a semantic switch).  The interpreter is
    never chosen here — validation passes ``lowering='interpret'``
    explicitly.

    This is the ``lowering`` input of every :func:`execution_plan` — the
    plan RECORDS the lowering it was chosen for (plan metadata surfaces
    it in bench rows / serve stats), it never overrides it: which algebra
    body runs is a correctness-scoped decision, which blocking it runs
    under is the cost model's.
    """
    low = pallas_lowering()
    if response in fused_column.fire_responses(low):
        return low
    return "reference"


def volley_block(
    lowering: str, n_volleys: int, d: Optional[int] = None
) -> int:
    """Hand-tuned fallback volley-block size for the blocked fused scans.

    Since the cost model landed this is the CONSTANTS HALF of the block
    policy: :func:`execution_plan` consults the device-calibrated cost
    model (``roofline.costmodel``) when a calibration is active and falls
    back to exactly these numbers when none is — un-calibrated hosts (and
    every existing test pin) behave as before.  Prefer
    ``execution_plan(...).v_blk`` in new code; call this directly only to
    name the constants themselves (the bench head-to-heads do).

    The padded training scan (``fused_column.fit_scan_padded``) advances
    ``v_blk`` volleys per outer scan step; this is the ONE place the
    fallback block size is decided.  Kernel lowerings fold the block inside
    a single kernel invocation (an in-kernel ``fori_loop`` with the weight
    buffer VMEM-resident), so a larger block amortizes kernel launches and
    HBM weight round-trips at no code-size cost.  The reference lowering
    statically *unrolls* the block into one fused XLA body — the block
    must stay small enough that compile time and the unrolled graph stay
    bounded (8 is the measured CPU sweet spot; beyond ~16 the win
    regresses), and when the caller knows the design-axis length ``d`` of
    the padded batch, the block is additionally capped at
    ``max(2, 2 * d)``: small-D batches get cheap traces, large-D batches
    keep the full block.  Clamped to the stream length so a short fit
    never pays for block-tail padding.  Blocking is a throughput knob
    only — results are bit-identical for every block size.
    """
    base = 8 if lowering == "reference" else 32
    if d is not None and lowering == "reference":
        # Envelope-aware unroll cap: the reference block's compile time
        # grows ~linearly with v_blk (each unrolled volley is another copy
        # of the fused body in ONE XLA computation) while warm throughput
        # is flat past a couple of volleys once the design axis is small —
        # measured on the bench geometries, v_blk 8 -> 2 cuts the cold
        # trace ~3x at D <= 2 with warm time unchanged.  So a 1-column
        # network layer or a 2-design DSE bucket must not pay the full
        # 8-volley unroll; at D >= 4 the cap leaves the block at 8, which
        # keeps every PR 4/5 warm number intact.
        base = min(base, max(2, 2 * int(d)))
    return max(1, min(base, int(n_volleys)))


# Lane-aligned kernel time-block fallback (the old hard-coded keyword
# default of every padded entry point; still the constants-policy choice).
DEFAULT_T_BLK = 128


def execution_plan(
    kind: str,
    lowering: str,
    d: int,
    p_pad: int,
    q_pad: int,
    t_window: int,
    n_volleys: int,
    epochs: int = 1,
    *,
    w_max: Optional[int] = None,
    response: str = "rnl",
):
    """The ONE policy front door: an ``ExecutionPlan`` for a padded scan.

    Every knob the fused paths used to pick from scattered constants —
    ``volley_block``'s 8/32, the ``t_blk=128`` default, the envelope
    waste cap, the shard count — now routes through here.  With a device
    calibration active (``roofline.costmodel.load_or_calibrate()``; the
    benches and launchers opt in, libraries and tests never do
    implicitly) the plan minimizes roofline-predicted step time subject
    to the profile's footprint bound; without one it packages the
    hand-tuned constants verbatim (``plan.source == 'constants'``), so
    un-calibrated behavior is bit-for-bit the pre-costmodel policy.

    Deterministic for fixed inputs and memoized per profile, so a warmed
    executable key (``warm_fit_padded``) and a traffic-time key
    (``fit_padded``) resolve the SAME blocking by construction — the
    zero-compile-after-warmup guarantee survives the policy swap.  Plans
    change blocking/sharding only, never semantics: every candidate is
    bit-identical (the ``v_blk``/``t_blk``/shard contracts in
    ``docs/kernels.md``), so a mis-calibrated model can cost time, not
    correctness.  See ``docs/costmodel.md``.
    """
    from repro.roofline import costmodel

    return costmodel.choose_plan(
        kind, lowering, int(d), int(p_pad), int(q_pad), int(t_window),
        int(n_volleys), int(epochs),
        w_max=int(w_max) if w_max is not None else 7,
        response=response,
    )


def _plan_blocks(
    kind: str,
    lowering: str,
    d: int,
    p_pad: int,
    q_pad: int,
    t_window: int,
    n_volleys: int,
    epochs: int,
    w_max: Optional[int],
    response: str,
    v_blk: Optional[int],
    t_blk: Optional[int],
) -> tuple[int, int]:
    """Resolve the (v_blk, t_blk) a padded entry point should run under:
    caller-pinned values win untouched; unset knobs come from the plan
    (cost model when calibrated, the documented constants otherwise)."""
    if v_blk is not None and t_blk is not None:
        return int(v_blk), int(t_blk)
    plan = execution_plan(
        kind, lowering, d, p_pad, q_pad, t_window, n_volleys, epochs,
        w_max=w_max, response=response,
    )
    return (
        int(v_blk) if v_blk is not None else plan.v_blk,
        int(t_blk) if t_blk is not None else plan.t_blk,
    )


def assign_lowering(response: str, w) -> str:
    """Lowering for the batched assignment pass, given the trained weights.

    The assignment kernel fires on the integer weight grid (its one-hot
    plane decomposition needs integral weights), while the reference body
    keeps the established float-weight fire.  That makes the kernel a pure
    *lowering* choice only when the weights already sit on the grid — true
    after integer-mu, unstabilized training from integer init, checked
    concretely here — and a semantic switch otherwise, so off-grid weights
    always take the reference body, on every host.  ``w`` must be a
    concrete array (call this outside jit); abstract values (tracers)
    fall back to 'reference'.
    """
    low = padded_lowering(response)
    if low == "reference":
        return low
    try:
        # concreteness probe: under a trace this bool() raises instead of
        # answering, which is exactly the "not concrete" signal we need —
        # no reliance on tracer internals
        on_grid = bool(jnp.all(w == jnp.round(w)))
    except jax.errors.ConcretizationTypeError:
        return "reference"
    return low if on_grid else "reference"


# ------------------------------------------------- lowering degradation
# Fused-scan lowerings ordered top (fastest, most machinery) to bottom
# (plainest): a failing rung re-resolves one level down.  'cycle' sits
# below them all but is a *solver*, not a lowering of the fused step —
# it only joins a ladder when it is provably bit-identical for the
# design at hand (``cycle_exact``), because a fallback may change how a
# result is computed, never what it is.
LOWERING_LADDER = ("mosaic", "interpret", "reference")

# Bound on degradation attempts per evaluation: at most every rung of the
# ladder below the starting lowering, plus the optional 'cycle' solver
# rung.  There is no "try the same rung twice" retry — the scans are
# deterministic, so an identical retry reproduces the identical failure.
MAX_EVAL_RETRIES = len(LOWERING_LADDER)


def lowering_ladder(start: str, cycle_exact: bool = False) -> tuple[str, ...]:
    """Degradation ladder for a fused evaluation starting at ``start``.

    The central retry policy for fault-tolerant sweeps
    (``simulator.cluster_time_series_many(on_error='isolate')`` and
    ``dse.explore``): when a rung fails — a Mosaic lowering error, an OOM,
    a kernel miscompile guard — the evaluation re-resolves one rung down
    and retries, bounded by the ladder length (``MAX_EVAL_RETRIES``).
    Every fused rung computes the same algebra (bit-identical on any
    host, see ``docs/backends.md``), so degradation changes *how* a
    result is produced, never the result.

    ``cycle_exact=True`` appends the 'cycle' solver as a last rung; pass
    it only when ``cycle_exact(cfg, w0)`` holds — i.e. the solver is
    bit-identical to the fused path for this design — otherwise the
    ladder ends at 'reference' and an evaluation failing every rung is
    quarantined rather than silently re-scored under different fire
    semantics.
    """
    if start == "cycle":
        return ("cycle",)
    if start in LOWERING_LADDER:
        rungs = LOWERING_LADDER[LOWERING_LADDER.index(start):]
        # the interpreter is validation-only: never auto-degrade INTO it,
        # only out of it when a caller started there explicitly
        rungs = tuple(r for r in rungs if r == start or r != "interpret")
    else:
        raise ValueError(
            f"unknown lowering: {start!r} (have {LOWERING_LADDER + ('cycle',)})"
        )
    return rungs + (("cycle",) if cycle_exact else ())


def warn_step_down(where: str, lowering: str, error) -> None:
    """Make one failed ladder rung loud: a ``RuntimeWarning`` naming the
    rung and its error.  Every caller that walks ``lowering_ladder`` calls
    this for each rung it leaves, so a kernel the device refuses can never
    pass as a quiet fallback to a plainer lowering."""
    warnings.warn(
        f"{where}: lowering {lowering!r} failed: {error}",
        RuntimeWarning,
        stacklevel=3,
    )


# Degraded-mode backoff for the ONLINE re-fit path (the serving analogue
# of MAX_EVAL_RETRIES): after the k-th consecutive re-fit failure a
# bucket sits out 2^(k-1) re-fit windows — capped so a long outage never
# pushes the retry horizon out indefinitely — and keeps serving from its
# last-good weights in the meantime.
REFIT_BACKOFF_CAP = 8


def refit_backoff(failures: int) -> int:
    """Re-fit windows to sit out after the ``failures``-th consecutive
    online re-fit failure (central policy; the streaming service consumes
    this through its degraded mode, see ``docs/serving.md``)."""
    return int(min(2 ** (max(int(failures), 1) - 1), REFIT_BACKOFF_CAP))


def cycle_exact(cfg: ColumnConfig, w0) -> bool:
    """True iff the 'cycle' solver is bit-identical to the fused path for
    this design, making it a legal bottom rung of the degradation ladder.

    The fused fire rounds weights to the integer grid {0..w_max}; the
    solvers fire on float weights.  The two coincide exactly when
    training keeps the weights on the grid and the init weights are
    already integral (checked concretely, like ``assign_lowering`` —
    abstract weights answer False): stochastic STDP always keeps them
    there (unit steps from the shared stream, ``stdp.stochastic_update``),
    expected STDP only with integer mus and no stabilizer.
    """
    s = cfg.stdp
    if s.mode != "stochastic" and (
        s.stabilizer != "none"
        or not all(
            float(mu).is_integer()
            for mu in (s.mu_capture, s.mu_backoff, s.mu_search)
        )
    ):
        return False
    try:
        return bool(jnp.all(w0 == jnp.round(w0)))
    except jax.errors.ConcretizationTypeError:
        return False


# ---------------------------------------------------- bucket / shard policy
# A design joins a shared padding envelope only while padding inflates no
# member's per-volley fire volume (p * q * t_max) beyond this factor:
# sharing one compiled step saves a one-time compilation, but padded FLOPs
# recur every volley of every fit, so a tiny design must never ride a huge
# design's envelope.  Shared by heterogeneous design sweeps
# (``simulator.cluster_time_series_many``) and network layer grouping
# (``network._fused_envelopes``).
ENVELOPE_WASTE_CAP = 4.0


def envelope_buckets(
    shapes: Sequence[tuple[int, int, int]],
    waste_cap: Optional[float] = None,
    max_bucket: Optional[int] = None,
    n_volleys: Optional[int] = None,
    epochs: int = 1,
) -> list[tuple[tuple[int, int, int], list[int]]]:
    """Pack (p, q, t_max) design shapes into shared padding envelopes.

    Members pack greedily (largest fire volume first) into buckets whose
    envelope is the elementwise max of its members' shapes, subject to two
    caps:

    * ``waste_cap`` (None -> plan policy): with a device calibration
      active AND a stream length hint (``n_volleys``/``epochs``), the cap
      comes from the cost model's compile-vs-recurring-waste break-even
      (``costmodel.choose_waste_cap`` — padding waste recurs every
      volley, sharing an envelope saves one compile, so short streams
      tolerate more waste than long ones); otherwise the hand-tuned
      ``ENVELOPE_WASTE_CAP`` constant.  Either way the cap bounds how far
      padding may inflate any member's per-volley fire volume —
      size-compatible designs share one compiled scan, badly mismatched
      ones get their own envelope (and their own, cheap, compilation).
    * ``max_bucket`` (None -> unbounded): upper bound on designs per
      bucket.  Bounds the working set of one compiled sweep (the padded
      volley/assignment buffers scale with the bucket's design axis) and
      keeps the design axis shard-friendly.  Buckets whose envelope
      shapes AND member counts coincide (e.g. same-shape designs split
      into full ``max_bucket`` groups) share one compiled trace via the
      ordinary jit cache; an unequal-sized tail bucket is its own trace.

    Returns ``[(envelope, member_indices), ...]``; every input index
    appears in exactly one bucket.  Bucketing never changes results — each
    design's padded scan is bit-identical under any envelope that contains
    it (the padding contract in ``docs/kernels.md``).
    """
    if waste_cap is None:
        waste_cap = ENVELOPE_WASTE_CAP
        if n_volleys is not None and shapes:
            from repro.roofline import costmodel

            pm = max(p for (p, _, _) in shapes)
            qm = max(q for (_, q, _) in shapes)
            tm = max(t for (_, _, t) in shapes)
            waste_cap = costmodel.choose_waste_cap(
                None, len(shapes), pm, qm, tm,
                n_volleys=int(n_volleys), epochs=int(epochs),
            )
    vols = [p * q * t for (p, q, t) in shapes]
    order = sorted(range(len(shapes)), key=lambda i: -vols[i])
    buckets: list[tuple[tuple[int, int, int], list[int]]] = []
    for i in order:
        p, q, t = shapes[i]
        placed = False
        for bi, (env, members) in enumerate(buckets):
            if max_bucket is not None and len(members) >= max_bucket:
                continue
            cand = (max(env[0], p), max(env[1], q), max(env[2], t))
            vol = cand[0] * cand[1] * cand[2]
            if all(vol <= waste_cap * vols[m] for m in members + [i]):
                buckets[bi] = (cand, members + [i])
                placed = True
                break
        if not placed:
            buckets.append(((p, q, t), [i]))
    return buckets


DESIGN_AXIS = "design"


def design_shards(d: int, volume: Optional[float] = None) -> int:
    """Shard count policy for a design axis of length ``d``.

    Default policy: the largest divisor of ``d`` that fits the local
    device count — the design axis of a padded sweep is embarrassingly
    parallel (every design's fire/WTA/STDP is independent), so it shards
    with no collectives at all.  1 on a single-device host or when
    nothing divides: the single-device fallback is simply "no sharding".

    With a per-design fire ``volume`` hint (``p * q * t``) AND an active
    device calibration, the cost model picks the shard count instead
    (``costmodel.choose_shards``): shard only while the compute saved per
    volley exceeds the added per-device dispatch, so a microsecond-sized
    bucket stops paying k launches to split sub-dispatch work.  Sharding
    is a throughput knob only — results are bit-identical for any count.
    """
    if volume is not None:
        from repro.roofline import costmodel

        return costmodel.choose_shards(int(d), float(volume))
    n_dev = jax.local_device_count()
    k = min(int(d), n_dev)
    while k > 1 and d % k:
        k -= 1
    return max(k, 1)


def design_mesh(
    d: int, volume: Optional[float] = None, shards: Optional[int] = None
):
    """1-D device mesh over ``DESIGN_AXIS`` for a design axis of length
    ``d``, or None on a single device / when ``d`` has no usable divisor
    (the clean single-device fallback — callers treat None as 'leave the
    arrays where they are').  ``volume`` is the optional per-design fire
    volume hint forwarded to the ``design_shards`` plan policy; callers
    that already hold an ``ExecutionPlan`` pass its ``shards`` count
    directly so the mesh and the recorded plan can never disagree."""
    k = shards if shards is not None else design_shards(d, volume)
    if k <= 1:
        return None
    # Auto axis: make_mesh's default (Explicit) types shardings into the
    # arrays, and its rules refuse the gathers the sweep runs on them
    return jax.make_mesh(
        (k,), (DESIGN_AXIS,), axis_types=(jax.sharding.AxisType.Auto,)
    )


def shard_design_axis(mesh, x: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
    """Place ``x`` with dimension ``axis`` sharded over ``mesh``'s design
    axis (no-op when ``mesh`` is None).  Sharding the operands is all it
    takes: the padded scans are jitted, so GSPMD propagates the design
    partitioning through the whole fit/assign program — per-design
    arithmetic is untouched and results stay bit-identical to the
    unsharded run."""
    if mesh is None:
        return x
    spec = PartitionSpec(*((None,) * axis + (DESIGN_AXIS,)))
    return jax.device_put(x, NamedSharding(mesh, spec))


def shard_designs(mesh, fn, arg_axes: Sequence[int], **statics):
    """``fn(*args, **statics)`` run per device on its slice of the design
    axis: ``jax.shard_map`` over ``mesh``'s design axis, jitted.

    ``arg_axes[i]`` is the design axis of positional argument ``i``; the
    output's design axis is 0.  Per-design work needs no collectives, and
    a Mosaic kernel cannot be partitioned by GSPMD at all, so the sharded
    sweep maps the padded scans over the mesh explicitly — each device
    runs the same program on its own designs, bit-identical to the
    unsharded run.  The wrapper is memoized on (mesh, fn, axes, statics),
    so repeated buckets reuse one trace (a bounded memo: a sweep touches a
    handful of envelopes).  The program takes ``fn``'s name
    (``jit_<name>``), so profiles tell the sharded fit from the assign.
    """
    return _shard_designs(
        mesh, fn, tuple(arg_axes), tuple(sorted(statics.items()))
    )


@functools.lru_cache(maxsize=64)
def _shard_designs(mesh, fn, arg_axes: tuple, statics: tuple):
    kw = dict(statics)
    in_specs = tuple(
        PartitionSpec(*((None,) * a + (DESIGN_AXIS,))) for a in arg_axes
    )

    def per_device(*args):
        return fn(*args, **kw)

    per_device.__name__ = per_device.__qualname__ = fn.__name__
    return jax.jit(jax.shard_map(
        per_device, mesh=mesh, in_specs=in_specs,
        out_specs=PartitionSpec(DESIGN_AXIS),
        # every operand is design-sharded and nothing is reduced across
        # devices; pallas_call outputs carry no varying-axes annotation
        check_vma=False,
    ))


# ----------------------------------------- persistent compilation cache
# Compilation must be a one-time, cross-process cost: a bench restart, a
# resumed DSE run, or a service process coming up must never re-pay XLA
# compilation for an envelope any prior process already compiled.  ONE
# directory holds every compile artifact — JAX's persistent cache, the
# serialized AOT executables (``aot/``) and the device calibration
# (``calibration.json``) — and it is placed from outside: JAX's own
# ``JAX_COMPILATION_CACHE_DIR`` when set (enabled at import, below),
# otherwise one fixed path inside the checkout.  Nothing else in the tree
# touches ``jax_compilation_cache_dir``.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))),
    ".jax_cache",
)

_compile_cache_path: Optional[str] = None


def default_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else the checkout's
    ``.jax_cache`` — the only cache directory entry points enable."""
    return os.environ.get(CACHE_ENV) or REPO_CACHE_DIR


def compile_cache(dir=None) -> Optional[str]:
    """Enable JAX's persistent compilation cache under ``dir``.

    Entry points (``chip_smoke.py``, the benches, ``dse.explore``) call
    this with no argument, which enables ``default_cache_dir()``; an
    explicit ``dir`` exists for tests that need an isolated cache.  Every
    XLA compilation then lands in the directory and every later process
    that enables it skips straight to the cached executable — zero
    envelope compiles, bit-identical results (pinned by
    ``tests/test_aot_cache.py``).  The same directory also holds whole
    serialized AOT envelope executables (``aot/``, see ``_aot_store``),
    which additionally skip tracing + lowering — the cost JAX's own cache
    still pays every process.  The entry-size/compile-time thresholds are
    dropped to zero because the padded envelope traces are exactly the
    small-but-slow tail the defaults would skip.

    The directory is created (and re-created — a deleted cache dir on a
    resumed run is repaired, not fatal) and probed for writability.  An
    unusable directory degrades gracefully: a ``RuntimeWarning`` and a
    ``None`` return, with compilation simply staying in-process — never
    an error on a hot path.  Returns the absolute cache path on success.
    JAX keys entries on jaxlib version + compiled module + compile
    options, so a stale directory is merely ignored, never wrong.
    """
    global _compile_cache_path
    if dir is None:
        dir = default_cache_dir()
    try:
        path = os.path.abspath(os.fspath(dir))
        os.makedirs(path, exist_ok=True)
        probe = os.path.join(path, ".write-probe")
        with open(probe, "w"):
            pass
        os.remove(probe)
    except OSError as e:
        warnings.warn(
            f"persistent compilation cache disabled: {dir!r} is not a "
            f"writable directory ({e})",
            RuntimeWarning,
            stacklevel=2,
        )
        return None
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _compile_cache_path = path
    return path


def compile_cache_dir() -> Optional[str]:
    """Directory of the persistent compilation cache enabled through
    ``compile_cache`` (None when it never was)."""
    return _compile_cache_path


# --------------------------------------- AOT envelope executable cache
# In-process twin of the persistent cache: one ahead-of-time compiled
# executable per (entry point, envelope, statics).  PR 5 deduped traces
# across equal-envelope buckets only within a single
# ``cluster_time_series_many`` call (the jit cache keyed on the Python
# callable); this cache keys on the envelope itself, so equal-envelope
# buckets share ONE executable across sweep calls, network layers, and
# DSE rounds in the same process — and the persistent cache extends the
# same guarantee across processes.
_AOT_CACHE: dict[tuple, object] = {}


def aot_cache_size() -> int:
    """Number of distinct (entry point, envelope) executables compiled."""
    return len(_AOT_CACHE)


def aot_cache_clear() -> None:
    """Drop the in-process executables (tests; the persistent cache — if
    enabled — still makes recompiles near-free)."""
    _AOT_CACHE.clear()


# JAX's persistent cache only skips ``backend_compile`` — a fresh process
# still pays tracing + StableHLO lowering for every envelope, and for the
# big blocked reference traces that cost rivals the compile itself.  So
# when ``compile_cache`` is enabled, the AOT executables are ALSO
# serialized whole (``jax.experimental.serialize_executable``) into
# ``<cache dir>/aot/``: a warm process deserializes the finished
# executable (~ms) and never traces at all.  Entries are keyed on the
# envelope key + jax version + platform + device count; a stale or
# corrupt entry deserializes as a failure and falls back to a fresh
# compile that overwrites it — never wrong, at worst slow once.
def _aot_disk_path(key: tuple) -> Optional[str]:
    if _compile_cache_path is None:
        return None
    tag = hashlib.sha256(repr(
        (key, jax.__version__, jax.default_backend(),
         jax.local_device_count())
    ).encode()).hexdigest()[:32]
    return os.path.join(_compile_cache_path, "aot", f"{tag}.pkl")


def _aot_load(key: tuple):
    path = _aot_disk_path(key)
    if path is None or not os.path.exists(path):
        return None
    try:
        from jax.experimental.serialize_executable import (
            deserialize_and_load,
        )
        with open(path, "rb") as f:
            payload = pickle.load(f)
        return deserialize_and_load(*payload)
    except Exception:
        return None


def _aot_store(key: tuple, exe) -> None:
    path = _aot_disk_path(key)
    if path is None:
        return
    try:
        from jax.experimental.serialize_executable import serialize
        payload = serialize(exe)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # write-then-rename, same publish discipline as the DSE journal:
        # concurrent writers race to an identical payload, readers never
        # see a torn file
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            pickle.dump(payload, f)
        os.replace(tmp, path)
    except Exception as e:  # serialization is an optimization, never fatal
        warnings.warn(
            f"could not persist AOT executable: {e}", RuntimeWarning
        )


def _coerce(x, dtype):
    """Dtype coercion with a fast path: the AOT dispatchers normalize all
    five operands on every call, and ``jnp.asarray`` costs ~20us of pure
    Python even when it has nothing to do — on already-correct device
    arrays (the common case: simulator and network pass exactly these)
    that is visible dispatch overhead, so skip it."""
    if isinstance(x, jax.Array) and x.dtype == dtype:
        return x
    return jnp.asarray(x, dtype)


def _fit_key(
    w_shape, xs_shape, t_window, w_max, wta_k, stabilize, response,
    epochs, lowering, t_blk, v_blk, stochastic=False,
) -> tuple:
    """AOT cache key for one fit envelope: shapes + statics, never values.
    A stochastic envelope extends the key with its flag and the names of
    the stream operands its executable takes (only when set, so an
    expected-mode envelope keeps its key)."""
    return (
        "fit", tuple(w_shape), tuple(xs_shape), t_window, w_max, wta_k,
        bool(stabilize), response, epochs, lowering, t_blk, v_blk,
    ) + (("stochastic", "keys", "v0") if stochastic else ())


def _assign_key(
    w_shape, xs_shape, t_window, wta_k, response, lowering, t_blk, v_blk,
    w_max,
) -> tuple:
    return (
        "assign", tuple(w_shape), tuple(xs_shape), t_window, wta_k, response,
        lowering, t_blk, v_blk, w_max,
    )


def _resolve_executable(key: tuple, build):
    """Executable lookup ladder: in-process -> serialized on disk -> compile.

    The single resolution path under ``fit_padded``/``assign_padded`` and
    the ``warm_*`` pre-compilers, so a warmed key and a traffic-time key
    hit the SAME entry by construction."""
    exe = _AOT_CACHE.get(key)
    if exe is None:
        exe = _aot_load(key)
    if exe is None:
        exe = build()
        _aot_store(key, exe)
    _AOT_CACHE[key] = exe
    return exe


def warm_fit_padded(
    d: int,
    p_pad: int,
    q_pad: int,
    n_volleys: int,
    *,
    t_window: int,
    w_max: int,
    wta_k: int,
    stabilize: bool,
    response: str,
    epochs: int,
    lowering: str,
    t_blk: Optional[int] = None,
    v_blk: Optional[int] = None,
    stochastic: bool = False,
) -> bool:
    """Make one envelope's fit executable resident *before* traffic.

    Long-lived callers (the streaming service, a resumed DSE run) know
    their envelopes up front; warming moves the one-time trace/compile —
    or the millisecond disk deserialize under ``compile_cache`` — out of
    the first request's latency.  No operands are needed and nothing is
    donated.  Unset ``v_blk``/``t_blk`` resolve through
    ``execution_plan`` — the same deterministic resolution ``fit_padded``
    performs, so a warmed key and a traffic key always coincide.  Returns
    True when the executable was already resident in-process (a later
    ``fit_padded`` with the same shapes+statics is then dispatch-only).
    When the module entry point has been replaced by a plain callable
    (the fault-injection seam — see ``fit_padded``) there is nothing to
    compile and this is a no-op returning False.  ``stochastic`` warms
    the stochastic envelope's executable (the one ``fit_padded`` runs when
    given ``keys``).
    """
    if not hasattr(fused_column.fit_scan_padded, "lower"):
        return False
    v_blk, t_blk = _plan_blocks(
        "fit", lowering, d, p_pad, q_pad, t_window, n_volleys, epochs,
        w_max, response, v_blk, t_blk,
    )
    key = _fit_key(
        (d, p_pad, q_pad), (n_volleys, d, p_pad), t_window, w_max, wta_k,
        stabilize, response, epochs, lowering, t_blk, v_blk, stochastic,
    )
    hot = key in _AOT_CACHE
    _resolve_executable(
        key,
        lambda: fused_column.precompile_fit_scan_padded(
            d, p_pad, q_pad, n_volleys,
            t_window=t_window, w_max=w_max, wta_k=wta_k,
            stabilize=bool(stabilize), response=response, epochs=epochs,
            lowering=lowering, t_blk=t_blk, v_blk=v_blk,
            stochastic=stochastic,
        ),
    )
    return hot


def warm_assign_padded(
    d: int,
    p_pad: int,
    q_pad: int,
    n_volleys: int,
    *,
    t_window: int,
    wta_k: int,
    response: str,
    lowering: str,
    t_blk: Optional[int] = None,
    v_blk: Optional[int] = None,
    w_max: Optional[int] = None,
) -> bool:
    """Assignment twin of ``warm_fit_padded`` (same contract)."""
    if not hasattr(fused_column.assign_padded, "lower"):
        return False
    v_blk, t_blk = _plan_blocks(
        "assign", lowering, d, p_pad, q_pad, t_window, n_volleys, 1,
        w_max, response, v_blk, t_blk,
    )
    key = _assign_key(
        (d, p_pad, q_pad), (n_volleys, d, p_pad), t_window, wta_k, response,
        lowering, t_blk, v_blk, w_max,
    )
    hot = key in _AOT_CACHE
    _resolve_executable(
        key,
        lambda: fused_column.precompile_assign_padded(
            d, p_pad, q_pad, n_volleys,
            t_window=t_window, wta_k=wta_k, response=response,
            lowering=lowering, t_blk=t_blk, v_blk=v_blk, w_max=w_max,
        ),
    )
    return hot


@functools.lru_cache(maxsize=None)
def _f32_scalar(v: float):
    """Memoized scalar device transfer: the AOT dispatchers pass the STDP
    mus as f32 device scalars on EVERY call, and three fresh host-to-
    device puts per dispatch are pure overhead on a parity-level case —
    the sweep bench sits at ~24 ms/call, where ~0.3 ms of scalar puts is
    a visible warm regression.  Values come from config floats, so the
    working set is tiny and the cache never grows past a handful."""
    return jnp.float32(v)


@functools.lru_cache(maxsize=1)
def _i32_zero():
    """The stream offset of a fit that starts its stream (a sweep), put on
    the device once, like the mus above."""
    return jnp.int32(0)


def fit_padded(
    w,
    xs,
    thresholds,
    t_maxes,
    q_actives,
    *,
    t_window: int,
    w_max: int,
    wta_k: int,
    mu_capture,
    mu_backoff,
    mu_search,
    stabilize: bool,
    response: str,
    epochs: int,
    lowering: str,
    t_blk: Optional[int] = None,
    v_blk: Optional[int] = None,
    keys=None,
    v0: int = 0,
):
    """Envelope-cached AOT front door to ``fused_column.fit_scan_padded``.

    Dispatches to a ``jit(...).lower().compile()`` executable cached on
    the padded envelope ``(D, N, p, q, v_blk, lowering, statics)`` — see
    ``fused_column.precompile_fit_scan_padded`` — and bit-identical to
    calling the jitted entry point directly.  Operand *values* (weights,
    volleys, per-design thresholds/windows/mus) are runtime inputs and
    never part of the key, so designs that share an envelope share an
    executable while their results stay their own.  Like the underlying
    scan, the weight buffer ``w`` is donated: pass a fresh array.

    Unset ``v_blk``/``t_blk`` resolve through ``execution_plan`` (cost
    model when a calibration is active, the documented constants
    otherwise) BEFORE the cache key is formed, so plan choices and AOT
    keys can never disagree between warmup and traffic.

    Callers with sharded operands must use ``fit_scan_padded`` directly —
    these executables are compiled against unsharded specs, while the jit
    path lets GSPMD propagate the design partitioning at trace time.

    ``keys`` ([D, 2] i32 stream keys, one per design) selects stochastic
    STDP — the envelope's static flag — and ``v0`` (an int, default 0) is
    the stream index of the window's first volley: volley n of epoch e
    draws at ``v0 + e * N + n``.  ``v0`` is a runtime operand of the
    stochastic executable, so a caller that advances it (the streaming
    service, re-fit after re-fit) never recompiles.
    """
    stochastic = keys is not None
    if v0 and not stochastic:
        raise ValueError("v0 is a stream index: stochastic STDP only")
    w = _coerce(w, jnp.float32)
    xs = _coerce(xs, TIME_DTYPE)
    thresholds = _coerce(thresholds, jnp.float32)
    t_maxes = _coerce(t_maxes, TIME_DTYPE)
    q_actives = _coerce(q_actives, TIME_DTYPE)
    d, p_pad, q_pad = w.shape
    v_blk, t_blk = _plan_blocks(
        "fit", lowering, d, p_pad, q_pad, t_window, xs.shape[0], epochs,
        w_max, response, v_blk, t_blk,
    )
    stream = {}
    if stochastic:
        stream = dict(
            keys=_coerce(keys, jnp.int32),
            v0=_i32_zero() if not v0 else jnp.int32(v0),
        )
    if not hasattr(fused_column.fit_scan_padded, "lower"):
        # the module entry point has been replaced by a plain callable —
        # the fault-injection / instrumentation seam the fault tests (and
        # any profiling wrapper) rely on.  A wrapper cannot be .lower()ed
        # into an executable, and dispatching a cached executable AROUND
        # it would silently disarm the seam, so honor the wrapper.
        return fused_column.fit_scan_padded(
            w, xs, thresholds, t_maxes, q_actives,
            t_window=t_window, w_max=w_max, wta_k=wta_k,
            mu_capture=mu_capture, mu_backoff=mu_backoff,
            mu_search=mu_search, stabilize=stabilize, response=response,
            epochs=epochs, lowering=lowering, t_blk=t_blk, v_blk=v_blk,
            **(dict(stochastic=True, **stream) if stochastic else {}),
        )
    key = _fit_key(
        w.shape, xs.shape, t_window, w_max, wta_k, stabilize, response,
        epochs, lowering, t_blk, v_blk, stochastic,
    )
    exe = _resolve_executable(
        key,
        lambda: fused_column.precompile_fit_scan_padded(
            d, p_pad, q_pad, xs.shape[0],
            t_window=t_window, w_max=w_max, wta_k=wta_k,
            stabilize=bool(stabilize), response=response, epochs=epochs,
            lowering=lowering, t_blk=t_blk, v_blk=v_blk,
            stochastic=stochastic,
        ),
    )
    # the call must mirror the precompile specs exactly: five positional
    # arrays, mus (and a stochastic fit's keys and v0) by keyword
    return exe(
        w, xs, thresholds, t_maxes, q_actives,
        mu_capture=_f32_scalar(float(mu_capture)),
        mu_backoff=_f32_scalar(float(mu_backoff)),
        mu_search=_f32_scalar(float(mu_search)),
        **stream,
    )


def assign_padded(
    w,
    xs,
    thresholds,
    t_maxes,
    q_actives,
    *,
    t_window: int,
    wta_k: int,
    response: str,
    lowering: str,
    t_blk: Optional[int] = None,
    v_blk: Optional[int] = None,
    w_max: Optional[int] = None,
):
    """Envelope-cached AOT front door to ``fused_column.assign_padded``.

    Same contract as ``fit_padded`` (envelope-keyed executable, runtime
    operands, plan-resolved blocking, bit-identical to the jit path) for
    the batched assignment pass; nothing is donated.
    """
    w = _coerce(w, jnp.float32)
    xs = _coerce(xs, TIME_DTYPE)
    thresholds = _coerce(thresholds, jnp.float32)
    t_maxes = _coerce(t_maxes, TIME_DTYPE)
    q_actives = _coerce(q_actives, TIME_DTYPE)
    v_blk, t_blk = _plan_blocks(
        "assign", lowering, w.shape[0], w.shape[1], w.shape[2], t_window,
        xs.shape[0], 1, w_max, response, v_blk, t_blk,
    )
    if not hasattr(fused_column.assign_padded, "lower"):
        # same instrumentation-seam rule as fit_padded above
        return fused_column.assign_padded(
            w, xs, thresholds, t_maxes, q_actives,
            t_window=t_window, wta_k=wta_k, response=response,
            lowering=lowering, t_blk=t_blk, v_blk=v_blk, w_max=w_max,
        )
    key = _assign_key(
        w.shape, xs.shape, t_window, wta_k, response, lowering, t_blk,
        v_blk, w_max,
    )
    exe = _resolve_executable(
        key,
        lambda: fused_column.precompile_assign_padded(
            w.shape[0], w.shape[1], w.shape[2], xs.shape[0],
            t_window=t_window, wta_k=wta_k, response=response,
            lowering=lowering, t_blk=t_blk, v_blk=v_blk, w_max=w_max,
        ),
    )
    return exe(w, xs, thresholds, t_maxes, q_actives)


# ------------------------------------------------------------- generic fit
def solver_volley_step(
    w: jnp.ndarray,
    x_t: jnp.ndarray,
    key: jax.Array,
    cfg: ColumnConfig,
    solver_mode: str,
    y_target: Optional[jnp.ndarray] = None,
    stream=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One online-STDP step on the event/cycle solvers: fire -> WTA -> STDP.

    This is the shared scan body of the generic (non-fused) training path —
    ``_solver_fit_scan`` folds it over a column's volleys and
    ``network._layer_solver_fit_scan`` additionally ``vmap``s it over a
    layer's columns.  ``key`` must already be folded per volley; it feeds
    the random WTA tie-break.  Stochastic STDP draws from the design's
    counter-based stream instead: ``stream`` is ``(stream key, global
    volley index)`` (``stdp.stream_key`` / ``stdp.stream_uniform``), the
    bits the fused kernels draw for the same synapse and volley.

    Returns (updated weights [p, q], post-WTA winner times [q]).
    """
    solver = (
        neuron.fire_times_event
        if solver_mode == "event"
        else neuron.fire_times_cycle
    )
    k_wta, _ = jax.random.split(key)
    t = solver(x_t[None], w, cfg.neuron, cfg.t_max)[0]
    y, _ = wta.wta(
        t, cfg.wta, cfg.t_max,
        rng=k_wta if cfg.wta.tie_break == "random" else None,
    )
    teacher = y if y_target is None else y_target
    s_key, volley = (None, 0) if stream is None else stream
    w2 = stdp.stdp_update(
        w, x_t, teacher, cfg.stdp, cfg.neuron.w_max, cfg.t_max,
        rng=s_key if cfg.stdp.mode == "stochastic" else None, volley=volley,
    )
    return w2, y


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "mode", "epochs", "trace", "supervised"),
    donate_argnums=(0,),
)
def _solver_fit_scan(
    w: jnp.ndarray,
    xs: jnp.ndarray,
    y_target: Optional[jnp.ndarray],
    rng: jax.Array,
    cfg: ColumnConfig,
    mode: str,
    epochs: int,
    trace: bool,
    supervised: bool,
    v0=0,
):
    """Online STDP as one compiled scan using the event/cycle solvers.

    Handles the full config surface (LIF, stochastic STDP, random/all WTA
    tie-breaks, supervised targets).  Stochastic STDP draws volley n of
    epoch e at stream index v0 + e * N + n under ``stdp.stream_key(rng)``
    — the stream the fused path draws — so 'cycle' is its bit-exact
    reference.
    """
    n = xs.shape[0]
    s_key = stdp.stream_key(rng)

    def volley(carry, inp):
        wc, key = carry
        xt, yt, i, v = inp
        kv = jax.random.fold_in(key, i)
        w2, y = solver_volley_step(
            wc, xt, kv, cfg, mode, y_target=yt if supervised else None,
            stream=(s_key, v),
        )
        return (w2, key), (y if trace else None)

    yts = y_target if supervised else jnp.zeros((n, 1), TIME_DTYPE)

    def epoch(carry, e):
        wc, key = carry
        ke = jax.random.fold_in(key, e)
        idx = jnp.arange(n, dtype=jnp.int32)
        (w2, _), ys = jax.lax.scan(
            volley, (wc, ke), (xs, yts, idx, v0 + e * n + idx)
        )
        return (w2, key), ys

    (w, _), ys = jax.lax.scan(epoch, (w, rng), jnp.arange(epochs))
    return w, ys


def _solver_fit(
    params: dict,
    x: jnp.ndarray,
    cfg: ColumnConfig,
    mode: str,
    epochs: int,
    rng: Optional[jax.Array],
    trace: bool,
    y_target: Optional[jnp.ndarray] = None,
):
    if rng is None:
        if cfg.wta.tie_break == "random":
            raise ValueError("tie_break='random' requires a PRNG key")
        if cfg.stdp.mode == "stochastic":
            raise ValueError("stochastic STDP requires a PRNG key")
        rng = jax.random.key(0)
    w = jnp.array(params["w"], jnp.float32, copy=True)  # scan donates w
    w_new, ys = _solver_fit_scan(
        w, x, y_target, rng, cfg, mode, epochs,
        trace, y_target is not None,
    )
    return {"w": w_new}, ys


def solver_fit_stream(w, xs, cfg: ColumnConfig, *, epochs: int, stream_key,
                      v0: int = 0):
    """One design's online STDP on the 'cycle' solver, resuming a
    stochastic stream.

    ``w`` [p, q], ``xs`` [N, p] volleys; stochastic STDP draws volley n of
    epoch e at stream index ``v0 + e * N + n`` under ``stream_key`` ([2]
    i32) — the bits ``fit_padded(keys=..., v0=...)`` draws for the same
    design, so with ``cycle_exact`` this is the bit-exact bottom rung of
    a resumed fused fit.  Returns the new weights [p, q].
    """
    # a raw key whose words are the stream key: the solver draws from
    # stdp.stream_key(rng), the very key the fused rungs were given
    rng = jax.lax.bitcast_convert_type(
        jnp.asarray(stream_key, jnp.int32), jnp.uint32
    )
    w_new, _ = _solver_fit_scan(
        jnp.array(w, jnp.float32, copy=True), jnp.asarray(xs, TIME_DTYPE),
        None, rng, cfg, "cycle", epochs, False, False, jnp.int32(v0),
    )
    return w_new


def _solver_fire(mode: str):
    def fire(params, x, cfg, rng=None):
        t = neuron.fire_times(x, params["w"], cfg.neuron, cfg.t_max, mode)
        return wta.wta(t, cfg.wta, cfg.t_max, rng=rng)

    return fire


# -------------------------------------------------------------- pallas side
def _pallas_fire(params, x, cfg: ColumnConfig, rng=None):
    """Kernel-backed batched forward: integer-grid fire + WTA.

    Response-aware like the fused fit paths: RNL uses the kernel where one
    exists, SNL falls to the reference body of the same algebra (a
    lowering choice), anything else (LIF) raises.
    """
    from repro.kernels import ops  # late import: ops depends on this module

    allowed = fused_column.fire_responses("reference")
    if cfg.neuron.response not in allowed:
        raise ValueError(
            f"pallas forward supports response {allowed}, got "
            f"{cfg.neuron.response!r}; use mode='cycle'"
        )
    lowering = padded_lowering(cfg.neuron.response)
    w = jnp.round(jnp.clip(params["w"], 0.0, cfg.neuron.w_max))
    if lowering == "reference":
        # lax.map over volley *blocks* (vmapped inside): bounds the
        # [v_blk, p, q, t] dense transient while amortizing per-volley
        # dispatch — same arithmetic per volley, just batched.
        xb = x.reshape((-1, cfg.p))
        v_blk = volley_block("reference", xb.shape[0])
        xsb, _ = fused_column._pad_volley_blocks(xb, v_blk, cfg.t_max)

        def block(xt_blk):
            return jax.vmap(
                lambda xt: fused_column.fire_dense_ref(
                    w, xt, cfg.neuron.threshold, cfg.t_max,
                    response=cfg.neuron.response,
                )
            )(xt_blk)

        t = jax.lax.map(block, xsb).reshape((-1, cfg.q))
        t = t[: xb.shape[0]].reshape(x.shape[:-1] + (cfg.q,))
    else:
        t = ops.rnl_fire(
            x.reshape((-1, cfg.p)), w, cfg.neuron.threshold, cfg.t_max,
            cfg.neuron.w_max,
        ).reshape(x.shape[:-1] + (cfg.q,))
    return wta.wta(t, cfg.wta, cfg.t_max, rng=rng)


def _pallas_fit(params, x, cfg, mode, epochs, rng, trace, y_target=None):
    if y_target is not None:
        # Supervised targets need the generic scan.  That is a silent
        # semantic switch (float-weight fire instead of the fused integer
        # grid), so it is only legal when the caller asked for 'auto'.
        if mode == "pallas":
            raise ValueError(
                "the fused pallas backend has no supervised (y_target) "
                "path; use mode='auto', 'event' or 'cycle'"
            )
        fallback = "cycle" if cfg.neuron.response == "lif" else "event"
        return _solver_fit(
            params, x, cfg, fallback, epochs, rng, trace, y_target
        )
    return fused_column.fit_fused(
        params, x, cfg, epochs,
        lowering=padded_lowering(cfg.neuron.response), trace=trace, rng=rng,
    )


# ---------------------------------------------------------------- registry
@dataclasses.dataclass(frozen=True)
class Backend:
    """One simulation backend: batched forward + online-STDP training.

    fire(params, x, cfg, rng) -> (post-WTA times [..., q], winner mask).
    fit(params, x, cfg, mode, epochs, rng, trace, y_target)
        -> (params, ys or None); ys is [epochs, N, q] online winner times.
    """

    name: str
    fire: Callable
    fit: Callable


_REGISTRY: dict[str, Backend] = {}


def register(backend: Backend) -> None:
    _REGISTRY[backend.name] = backend


def get(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend: {name!r} (have {sorted(_REGISTRY)})"
        ) from None


def names() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


register(
    Backend(
        "event",
        _solver_fire("event"),
        lambda params, x, cfg, mode, epochs, rng, trace, y_target=None:
            _solver_fit(params, x, cfg, "event", epochs, rng, trace, y_target),
    )
)
register(
    Backend(
        "cycle",
        _solver_fire("cycle"),
        lambda params, x, cfg, mode, epochs, rng, trace, y_target=None:
            _solver_fit(params, x, cfg, "cycle", epochs, rng, trace, y_target),
    )
)
register(Backend("pallas", _pallas_fire, _pallas_fit))


def _fused_ok(cfg: ColumnConfig) -> bool:
    # Evaluated against the STRICTEST lowering ('mosaic', RNL-only).  SNL
    # *could* now train fused uniformly on every host (padded_lowering
    # routes it to the reference body), but 'auto' has always trained SNL
    # on the float-weight event solver, and the fused path's integer-grid
    # fire gives different (not wrong, different) results — so routing SNL
    # fused under 'auto' would silently change established results.  Users
    # who want SNL on the fused path opt in with mode='pallas'.
    try:
        fused_column.check_fusable(cfg, "mosaic")
        return True
    except ValueError:
        return False


def resolve(mode: str, cfg: ColumnConfig, training: bool = False) -> str:
    """Map the public mode knob to a registry name.

    Forward 'auto' keeps the paper's hybrid: event where exact, cycle for
    LIF.  Training 'auto' prefers the fused pallas path whenever the config
    fits its contract, falling back to the hybrid solvers otherwise.
    """
    if mode != "auto":
        get(mode)  # validate
        return mode
    if cfg.neuron.response == "lif":
        return "cycle"
    if training and _fused_ok(cfg):
        return "pallas"
    return "event"


# A cache directory placed from outside is the one directory for every
# compile artifact: enable it at import, so the AOT store and the
# calibration land beside JAX's own cache from the first compile on.
if os.environ.get(CACHE_ENV):
    compile_cache(os.environ[CACHE_ENV])
