"""Fused TNN column training step: RNL fire + k-WTA + expected STDP.

This is the hot path of the paper's "rapid application exploration" loop:
online STDP folds one volley at a time into the weights, so training is a
``lax.scan`` over epochs x volleys whose body is ONE fused column step.  The
step exists in two lowerings behind the same semantics:

* ``fused_step_pallas_padded`` — a single ``pl.pallas_call`` over a grid of
  (designs, time blocks): the RNL body potential is evaluated via the
  one-hot weight-plane decomposition (MXU matmuls, planes built *in-kernel*
  from the VMEM-resident weights — ``make_weight_planes`` never runs per
  volley), firing times fall out as sub-threshold cycle counts, the k-WTA
  priority encoder and the per-synapse expected-STDP update run in the same
  kernel invocation, and the updated weights are written back.  Per-design
  scalars (threshold, effective ``t_max``, live-neuron count, STDP mus)
  enter as a *runtime* SMEM operand (``design_operands``) masked against a
  single static envelope — one compiled kernel serves a whole heterogeneous
  design batch, and changing a threshold never retraces.  Weights stay
  padded/resident across the whole scan; padding happens once per ``fit``.
* ``fused_step_ref`` — the pure-jnp lowering of the same algebra (dense
  sub-threshold count over the time window).  Exact for RNL/SNL: V(t) is
  nondecreasing, so the count of sub-threshold integer cycles *is* the first
  crossing — bit-identical to ``mode='cycle'``.  This is what the central
  dispatch (``repro.core.backend``) lowers to off-TPU, where the Pallas
  interpreter would serialize 100x slower; the interpreter remains available
  for validation via ``lowering='interpret'``.

Scope (enforced by ``check_fusable``): ``response in ('rnl', 'snl')``
(``'rnl'`` only for the Pallas lowering), expected- or stochastic-mode
STDP, index tie-break WTA.  Other configs take the generic per-solver scan
in ``repro.core.backend``.  Stochastic STDP is a static flag of the
envelope: the STDP stage then draws each synapse's uniform from the
counter-based stream of ``repro.core.stdp`` (per-design stream keys and
the global volley index ride as runtime operands beside the design
operands) and applies ``stdp.stochastic_update`` — the same bits and the
same rule as the solvers, so every lowering stays bit-identical to
``mode='cycle'`` from integer initial counters.

The per-design quantities (threshold, t_max, active q, STDP mus) are traced
values in *both* lowerings — the reference ``vmap``s over them, the kernel
reads them from SMEM — so a stacked sweep of heterogeneous designs
(``simulator.cluster_time_series_many``) or network layers
(``network.fit_greedy``) compiles once per envelope shape, never per
design.  The full kernel contract is documented in ``docs/kernels.md``.

The padded scans advance in **volley blocks** (``v_blk=``): each step of the
outer ``lax.scan`` folds ``v_blk`` sequential online-STDP volleys in one
fused body — ONE kernel invocation whose in-kernel loop keeps the weight
buffer VMEM-resident for the whole block (Mosaic), or one statically
unrolled jnp block sharing precomputed input ramps (reference) — exactly
online either way: volley i inside a block still sees the weights updated
by volley i-1, bit-identical to ``v_blk=1`` and to ``mode='cycle'`` on
integer weight grids.  Block tails are silent-padded (the sentinel
contract) AND masked out of the weight fold by a per-block valid count,
so a tail step is an exact weight no-op for any design — even degenerate
``threshold <= 0`` ones.  ``assign_padded``, which has no sequential
dependency at all, batches volleys into the kernel grid instead (one
``pallas_call`` for the whole assignment pass).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import stdp as stdp_lib
from repro.core.types import ColumnConfig, TIME_DTYPE
from repro.kernels import ref

LANE = 128
SUBLANE = 8

LOWERINGS = ("mosaic", "interpret", "reference")

# Columns of the runtime design-operand array (see ``design_operands``):
# one row of per-design scalars the kernel reads from SMEM at run time.
OPERAND_COLS = (
    "threshold", "t_max", "q_active", "mu_capture", "mu_backoff", "mu_search"
)
N_OPERANDS = len(OPERAND_COLS)


def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad_volleys_silent(x: jnp.ndarray, p_pad: int, sentinel: float):
    """Widen volleys [..., p] -> [..., p_pad] f32, padding with ``sentinel``.

    The kernel's silence contract is ``time >= design t_max`` (see
    docs/kernels.md); any sentinel satisfying that for every design in the
    batch is equivalent — this helper is the one place the fill happens.
    """
    xs = jnp.full(x.shape[:-1] + (p_pad,), float(sentinel), jnp.float32)
    return xs.at[..., : x.shape[-1]].set(x.astype(jnp.float32))


def pad_stream_silent(xs, n_total: int, sentinel):
    """Ragged micro-batch seam: pad a volley stream [n, ...] to [n_total, ...]
    with silent rows (every time set to ``sentinel``, which must be >= every
    design's ``t_max``).

    A serving front-end keeps ONE compiled executable per envelope by
    padding partial request batches up to the compiled batch size; silent
    rows assign to the "unclustered" id (``q_active``) and are sliced away
    by the caller, and — for the positive thresholds real designs use — a
    silent volley is an exact weight no-op under the fused STDP step, so
    the same trick pads ragged re-fit windows.  Accepts numpy or jax
    arrays and stays in that family (serving assembles batches host-side).
    """
    n = xs.shape[0]
    if n > n_total:
        raise ValueError(f"stream of {n} volleys exceeds batch of {n_total}")
    if n == n_total:
        return xs
    if isinstance(xs, np.ndarray):
        pad = np.full((n_total - n,) + xs.shape[1:], sentinel, xs.dtype)
        return np.concatenate([xs, pad], axis=0)
    pad = jnp.full((n_total - n,) + xs.shape[1:], sentinel, xs.dtype)
    return jnp.concatenate([xs, pad], axis=0)


def fire_responses(lowering: str) -> tuple[str, ...]:
    """Response functions the fused fire supports under a given lowering
    (the Pallas kernel implements the RNL plane decomposition only)."""
    return ("rnl", "snl") if lowering == "reference" else ("rnl",)


def check_fusable(cfg: ColumnConfig, lowering: str) -> None:
    """Raise ValueError if cfg falls outside the fused step's contract."""
    if lowering not in LOWERINGS:
        raise ValueError(f"unknown lowering: {lowering!r}")
    ok_resp = fire_responses(lowering)
    if cfg.neuron.response not in ok_resp:
        raise ValueError(
            f"fused step ({lowering}) supports response {ok_resp}, got "
            f"{cfg.neuron.response!r}"
        )
    if cfg.stdp.mode not in ("expected", "stochastic"):
        raise ValueError(f"unknown STDP mode: {cfg.stdp.mode!r}")
    if cfg.wta.tie_break != "index":
        raise ValueError("fused step supports index tie-break WTA only")


# --------------------------------------------------------------- reference
def fire_dense_ref(
    w: jnp.ndarray,
    t_in: jnp.ndarray,
    threshold,
    t_window: int,
    t_max=None,
    response: str = "rnl",
) -> jnp.ndarray:
    """Firing times by dense sub-threshold cycle count.  [p],[p,q] -> [q].

    ``t_window`` is the static evaluation length; ``t_max`` (traced OK) is
    the effective window — spike times >= t_max are silent and crossings at
    or past t_max report t_max.  Exact for RNL/SNL (V nondecreasing).
    """
    if t_max is None:
        t_max = t_window
    tv = jnp.arange(t_window, dtype=jnp.float32)  # [T]
    ti = t_in.astype(jnp.float32)
    live = ti < t_max  # [p]
    if response == "rnl":
        a = jax.nn.relu(tv[None, :] - ti[:, None])  # [p, T]
        a = jnp.where(live[:, None], a, 0.0)
        contrib = jnp.minimum(a[:, None, :], w[:, :, None])  # [p, q, T]
    else:  # snl
        s = (tv[None, :] >= ti[:, None]) & live[:, None]
        contrib = s[:, None, :].astype(w.dtype) * w[:, :, None]
    v = contrib.sum(axis=0)  # [q, T]
    below = (v < threshold) & (tv[None, :] < t_max)
    count = below.sum(axis=-1)
    return jnp.minimum(count, t_max).astype(TIME_DTYPE)


def _masked_steps(t_in: jnp.ndarray, t_max, t_window: int) -> jnp.ndarray:
    """Input-only fire transient: binary step functions [..., p, T].

    ``s[p, t] = 1[t >= t_in[p]]`` for live inputs, 0 for silent ones — the
    one weight-independent ingredient of the fire under BOTH responses
    (see ``fire_planes_ref``), so a volley block precomputes it ONCE and
    reuses it across the block's sequential weight updates.  ``t_max`` may
    be traced (and broadcast against leading batch axes).
    """
    tv = jnp.arange(t_window, dtype=jnp.float32)
    ti = t_in.astype(jnp.float32)
    live = ti < t_max
    return ((tv >= ti[..., None]) & live[..., None]).astype(jnp.float32)


def fire_planes_ref(
    w: jnp.ndarray,
    s: jnp.ndarray,
    threshold,
    t_window: int,
    t_max,
    response: str,
    w_max: int,
) -> jnp.ndarray:
    """Firing times from precomputed step transients, shift-GEMM form. -> [q].

    The plane algebra of the Mosaic kernel (docs/kernels.md) restructured
    for a memory-bound host.  With *integer* spike times and integer-grid
    weights, ``min(relu(t - ti), w) = sum_{v=1..w_max} 1[w >= v] *
    1[t - ti >= v]``, and the v-th indicator is just the step function
    delayed by v cycles: ``1[t - ti >= v] == s[t - v]``.  So the RNL
    potential needs NO ramp values and NO base term at all: the ``w_max``
    cumulative weight planes ``1[w >= v]`` contract against the one small
    shared binary step block in a single GEMM, and the per-plane delays
    are applied afterwards on the tiny ``[q, T]`` products — a fraction of
    the memory traffic of materializing per-plane ramp operands.  For SNL
    the potential IS a matmul of the same steps against the weights.  All
    intermediates are small integers in f32, so this is bit-identical to
    ``fire_dense_ref`` on the integer weight grid (weights are rounded
    here, mirroring the kernel) — integer spike times are a precondition
    (they are the repo's time contract, ``types.TIME_DTYPE``).

    Args:
      w: [p, q] weights (rounded to the integer grid internally).
      s: [p, T] step transient from ``_masked_steps``.
    """
    p, q = w.shape
    tv = jnp.arange(t_window, dtype=jnp.float32)
    wi = jnp.round(jnp.clip(w, 0.0, float(w_max)))
    if response == "rnl":
        vs = jnp.arange(1, w_max + 1, dtype=jnp.float32)
        ge = (wi[:, None, :] >= vs[None, :, None]).astype(jnp.float32)
        g = jax.lax.dot_general(
            ge.reshape(p, w_max * q), s,
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        ).reshape(w_max, q, t_window)  # per-plane products, undelayed
        gp = jnp.pad(g, ((0, 0), (0, 0), (w_max, 0)))
        v = gp[0, :, w_max - 1: w_max - 1 + t_window]  # plane v=1
        for sh in range(2, w_max + 1):  # static unroll: tiny [q, T] slices
            v = v + gp[sh - 1, :, w_max - sh: w_max - sh + t_window]
    else:  # snl: V = w^T @ steps
        v = jax.lax.dot_general(
            wi, s, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [q, T]
    below = (v < threshold) & (tv[None, :] < t_max)
    count = below.sum(axis=-1)
    return jnp.minimum(count, t_max).astype(TIME_DTYPE)


def fused_step_ref(
    w: jnp.ndarray,
    t_in: jnp.ndarray,
    threshold,
    t_window: int,
    w_max: int,
    wta_k: int,
    mu_capture: float,
    mu_backoff: float,
    mu_search: float,
    stabilize: bool,
    t_max=None,
    response: str = "rnl",
    integer_fire: bool = False,
    q_active=None,
    stream=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One fused column step, jnp lowering.  Returns (w_new, y).

    Args:
      w: [p, q] resident weights.
      t_in: [p] one input volley.
      threshold / t_max / q_active: traced-friendly per-design scalars
        (q_active masks neurons >= q_active out of WTA and STDP — used by the
        padded multi-design sweep; None means all q are live).
      t_window: static dense evaluation length (>= t_max).
      integer_fire: round weights to the hardware integer grid for the fire
        step (the Pallas lowering always does; planes need w in {0..w_max}).
      stream: None for expected STDP, else ``(key, volley)`` — the design's
        stream key and the global volley index of stochastic STDP.
    """
    if t_max is None:
        t_max = t_window
    w_fire = jnp.round(jnp.clip(w, 0.0, w_max)) if integer_fire else w
    t_fire = fire_dense_ref(w_fire, t_in, threshold, t_window, t_max, response)
    if q_active is not None:
        qi = jnp.arange(w.shape[1], dtype=TIME_DTYPE)
        t_fire = jnp.where(qi < q_active, t_fire, t_max)
    y = ref.wta_ref(t_fire[None], wta_k, t_max)[0]
    w_new = _stdp_ref(
        w, t_in, y, t_max, mu_capture, mu_backoff, mu_search, stream,
        w_max=w_max, stabilize=stabilize,
    )
    if q_active is not None:
        qi = jnp.arange(w.shape[1], dtype=TIME_DTYPE)
        w_new = jnp.where(qi[None, :] < q_active, w_new, w)
    return w_new, y


def _stdp_ref(
    w, t_in, y, t_max, mu_capture, mu_backoff, mu_search, stream, *,
    w_max, stabilize,
):
    """STDP of one volley on [p, q] weights, jnp lowering: expected mode
    (``ref.stdp_ref``) when ``stream`` is None, else stochastic mode with
    ``stream`` = ``(key, volley)``."""
    if stream is None:
        return ref.stdp_ref(
            w, t_in, y, mu_capture, mu_backoff, mu_search, w_max, t_max,
            stabilize=stabilize,
        )
    key, volley = stream
    u = stdp_lib.stream_uniform(
        key, volley, stdp_lib.synapse_counter(w.shape)
    )
    return stdp_lib.stochastic_update(
        w, t_in[:, None], y[None, :], t_max, mu_capture, mu_backoff,
        mu_search, u, w_max=w_max, stabilize=stabilize,
    )


def _block_step_ref(
    w: jnp.ndarray,
    s: jnp.ndarray,
    xt: jnp.ndarray,
    threshold,
    t_max,
    q_active,
    stream=None,
    *,
    t_window: int,
    w_max: int,
    wta_k: int,
    mu_capture,
    mu_backoff,
    mu_search,
    stabilize: bool,
    response: str,
    valid=True,
) -> jnp.ndarray:
    """One volley of a reference volley block: GEMM fire + WTA + STDP.

    Same semantics as ``fused_step_ref`` with ``integer_fire=True`` (the
    fused contract), but fed the precomputed step transient so the block's
    unrolled loop shares the input-side work, and with the kernel's
    min-round k-WTA (identical to ``ref.wta_ref`` — keys are unique —
    without a sort in the hot loop).  ``valid`` (traced bool OK) marks
    silent-padded block-tail volleys, which must fold nothing for ANY
    design; it rides the existing out-of-envelope mask, costing no extra
    op.  ``stream`` as in ``fused_step_ref``.  [p, q], [p, T], [p] ->
    [p, q].
    """
    q = w.shape[1]
    qi = jnp.arange(q, dtype=TIME_DTYPE)
    t_fire = fire_planes_ref(
        w, s, threshold, t_window, t_max, response, w_max
    )
    t_fire = jnp.where(qi < q_active, t_fire, t_max)
    # the kernels' WTA helper, shared verbatim (dtype-generic), so WTA
    # semantics live in exactly one place
    y = _kernel_wta(
        t_fire, qi, t_max, wta_k=wta_k, t_window=t_window
    ).astype(TIME_DTYPE)
    w_new = _stdp_ref(
        w, xt, y, t_max, mu_capture, mu_backoff, mu_search, stream,
        w_max=w_max, stabilize=stabilize,
    )
    return jnp.where((qi[None, :] < q_active) & valid, w_new, w)


def _pad_volley_blocks(
    xs: jnp.ndarray, v_blk: int, sentinel
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[N, ...] volleys -> ([S, v_blk, ...] blocks, [S] valid counts).

    Tail volleys of the last block are silent-padded (the sentinel
    contract: every synapse at/past ``t_max``) AND masked out of the weight
    fold by the per-block valid count — tail steps carry the weights
    through unchanged for any design, unconditionally (a silent volley is
    already a no-op for the positive thresholds real designs use, but the
    explicit mask keeps bit-identity across ``v_blk`` even for degenerate
    ``threshold <= 0`` designs, where silence still fires every neuron).
    """
    n = xs.shape[0]
    s = -(-n // v_blk)
    n_valid = jnp.minimum(
        jnp.full((s,), v_blk, TIME_DTYPE),
        n - v_blk * jnp.arange(s, dtype=TIME_DTYPE),
    )
    if s * v_blk != n:
        pad = jnp.full((s * v_blk - n,) + xs.shape[1:], sentinel, xs.dtype)
        xs = jnp.concatenate([xs, pad], axis=0)
    return xs.reshape((s, v_blk) + xs.shape[1:]), n_valid


# ------------------------------------------------------------ pallas kernel
def design_operands(
    thresholds,
    t_maxes,
    q_actives,
    mu_capture,
    mu_backoff,
    mu_search,
) -> jnp.ndarray:
    """Pack per-design runtime scalars into the kernel's SMEM operand array.

    Returns [D, N_OPERANDS] f32, one row per design, columns ordered as
    ``OPERAND_COLS``.  Every entry is a *runtime* value: the kernel masks
    against them inside one static envelope, so heterogeneous designs share
    a single compiled kernel and changing any of them never retraces.  The
    mus may be Python floats (broadcast across designs) or [D] arrays.
    """
    d = jnp.shape(thresholds)[0]
    cols = (thresholds, t_maxes, q_actives, mu_capture, mu_backoff, mu_search)
    return jnp.stack(
        [
            jnp.broadcast_to(jnp.asarray(c, jnp.float32), (d,))
            for c in cols
        ],
        axis=1,
    )


# The three kernels below (per-volley fused step, volley-blocked fused
# step, batched assignment fire) share one in-kernel algebra.  It lives in
# the value-level helpers here — plain jnp on values, traced into each
# kernel — so a change to the fire/WTA/STDP semantics lands in every
# lowering path at once (the cross-lowering bit-identity contract).
def _lane_iota(n: int) -> jnp.ndarray:
    """[1, n] f32 lane indices 0..n-1 (Mosaic iotas are integer-only)."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, n), 1).astype(jnp.float32)


def _kernel_fire_counts(wi, ti_col, t0, threshold, t_max, *, t_blk, n_planes):
    """Sub-threshold cycle counts of one time block starting at ``t0``.

    ``wi``: [p_pad, q_pad] integer-grid weights; ``ti_col``: [p_pad, 1]
    input times down the sublanes.  Returns [1, q_pad] counts to add to the
    design's accumulator: the RNL body potential via the in-kernel one-hot
    plane matmuls, compared against the runtime threshold and masked by the
    runtime window ``t_max``.
    """
    q_pad = wi.shape[1]
    tv = t0 + _lane_iota(t_blk)
    a = jnp.maximum(tv - ti_col, 0.0)  # [p_pad, t_blk] ramps
    base = jnp.sum(a, axis=0, keepdims=True)  # [1, t_blk]
    acc = jnp.zeros((q_pad, t_blk), jnp.float32)
    for v in range(n_planes):  # static unroll: planes from resident weights
        plane = (wi == float(v)).astype(jnp.float32)  # [p_pad, q_pad]
        av = a if v == 0 else jnp.maximum(a - float(v), 0.0)
        acc = acc + jax.lax.dot_general(
            plane, av, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [q_pad, t_blk]
    vqt = base - acc  # [q_pad, t_blk] body potential
    below = (vqt < threshold) & (tv < t_max)  # mask window padding
    return jnp.sum(below.astype(jnp.float32), axis=1)[None, :]


def _kernel_wta(t_fire, qi, t_max, *, wta_k, t_window):
    """k-WTA priority encoder on [1, q_pad] firing times -> winner times.

    Lexicographic (time, index) packed key; keys are unique, so k unrolled
    min rounds find the k-th smallest.  ``big`` only needs to exceed every
    live key, so the static envelope bound serves all designs.  Dtype
    follows ``t_fire``/``qi`` (f32 in the kernels, TIME_DTYPE on the
    blocked reference path — keys are small integers, exact either way).
    """
    q_pad = t_fire.shape[-1]
    big = (t_window + 1) * q_pad  # python int: weakly typed either way
    key = t_fire * q_pad + qi
    rem = key
    kth = key.dtype.type(0)
    for _ in range(wta_k):
        kth = jnp.min(rem)
        rem = jnp.where(rem <= kth, big, rem)
    win = (key <= kth) & (t_fire < t_max)
    return jnp.where(win, t_fire, t_max)  # [1, q_pad]


def _kernel_stdp(
    w, ti_col, y, qi, t_max, q_live,
    mu_capture, mu_backoff, mu_search, *, w_max, stabilize, stream=None,
):
    """Expected STDP on the resident float weights (same algebra as
    ``kernels/ref.stdp_ref``), padded neurons (>= ``q_live``) frozen.

    With ``stream`` — ``(key, volley, counter)``: the design's (k0, k1)
    SMEM scalars, the global volley index and the resident
    ``stdp.synapse_counter`` block — it is stochastic STDP instead: the
    stream's Threefry rounds in int32 VPU ops, then
    ``stdp.stochastic_update``.
    """
    if stream is not None:
        key, volley, counter = stream
        u = stdp_lib.stream_uniform(key, volley, counter)
        w_new = stdp_lib.stochastic_update(
            w, ti_col, y, t_max, mu_capture, mu_backoff, mu_search, u,
            w_max=w_max, stabilize=stabilize,
        )
        return jnp.where(qi < q_live, w_new, w)
    xs = ti_col < t_max
    ys = y < t_max
    if stabilize:
        frac = jnp.clip(w * (1.0 / w_max), 0.0, 1.0)
        eps = 1.0 / (2 * w_max)
        s_plus = (1.0 - frac) + eps
        s_minus = frac + eps
    else:
        s_plus = s_minus = jnp.ones_like(w)
    capture = xs & ys & (ti_col <= y)
    backoff = (xs & ys & (ti_col > y)) | ((~xs) & ys)
    search = xs & (~ys)
    delta = jnp.where(capture, mu_capture * s_plus, 0.0)
    delta = jnp.where(backoff, -mu_backoff * s_minus, delta)
    delta = jnp.where(search, mu_search, delta)
    delta = jnp.where(qi < q_live, delta, 0.0)
    return jnp.clip(w + delta, 0.0, float(w_max))


def _stream_operands(keys, volley):
    """The stochastic kernels' extra SMEM operands and their specs: [D, 2]
    i32 stream keys and the [1] i32 volley index; none for expected STDP
    (``keys`` None)."""
    if keys is None:
        return (), []
    ops = (
        keys.astype(jnp.int32),
        jnp.asarray(volley, jnp.int32).reshape((1,)),
    )
    return ops, [pl.BlockSpec(memory_space=pltpu.SMEM)] * 2


def _fused_kernel(
    scal_ref,  # [D, N_OPERANDS] f32 SMEM runtime design operands
    *refs,
    t_blk: int,
    t_window: int,
    n_planes: int,
    wta_k: int,
    w_max: int,
    stabilize: bool,
    stochastic: bool = False,
):
    """Fused fire + k-WTA + expected-STDP body, grid = (designs, time blocks).

    Static envelope: block shapes, ``t_window`` (padded evaluation length),
    ``n_planes``/``w_max``, ``wta_k`` and the stabilizer flag.  Everything
    per-design — threshold, effective window ``t_max``, live-neuron count
    ``q_active``, STDP mus — is read from ``scal_ref`` at run time and
    masked against the envelope, so one compiled kernel serves a whole
    heterogeneous design batch.

    ``refs``: ``t_ref`` [1, 1, p_pad] f32 input volley (silent >= design
    t_max), ``w_ref`` / ``w_out`` [1, p_pad, q_pad] f32 resident and
    updated weights, ``y_out`` [1, 1, q_pad] f32 counts accumulator ->
    winner times; under ``stochastic`` they follow ``key_ref`` [D, 2] i32
    (SMEM stream keys) and ``vb_ref`` [1] i32 (SMEM volley index).
    """
    if stochastic:
        key_ref, vb_ref, t_ref, w_ref, w_out, y_out = refs
    else:
        t_ref, w_ref, w_out, y_out = refs
    _, p_pad, q_pad = w_ref.shape
    d = pl.program_id(0)
    i = pl.program_id(1)
    last = pl.num_programs(1) - 1

    threshold = scal_ref[d, 0]
    t_max = scal_ref[d, 1]
    q_live = scal_ref[d, 2]
    mu_capture = scal_ref[d, 3]
    mu_backoff = scal_ref[d, 4]
    mu_search = scal_ref[d, 5]

    @pl.when(i == 0)
    def _init():
        y_out[...] = jnp.zeros_like(y_out)

    # --- fire: accumulate sub-threshold cycle counts for this time block.
    ti = t_ref[0].T  # [p_pad, 1] input times down the sublanes
    w = w_ref[0]
    wi = jnp.round(jnp.clip(w, 0.0, float(w_max)))  # integer fire grid
    y_out[0] += _kernel_fire_counts(
        wi, ti, (i * t_blk).astype(jnp.float32), threshold, t_max,
        t_blk=t_blk, n_planes=n_planes,
    )

    # --- WTA + STDP once all time blocks have accumulated.
    @pl.when(i == last)
    def _finalize():
        counts = y_out[0]  # [1, q_pad]
        qi = _lane_iota(q_pad)
        t_fire = jnp.minimum(counts, t_max)
        t_fire = jnp.where(qi < q_live, t_fire, t_max)  # pad neurons silent
        y = _kernel_wta(t_fire, qi, t_max, wta_k=wta_k, t_window=t_window)
        y_out[0] = y
        stream = None
        if stochastic:
            stream = ((key_ref[d, 0], key_ref[d, 1]), vb_ref[0],
                      stdp_lib.synapse_counter((p_pad, q_pad)))
        w_out[0] = _kernel_stdp(
            w, ti, y, qi, t_max, q_live,
            mu_capture, mu_backoff, mu_search,
            w_max=w_max, stabilize=stabilize, stream=stream,
        )

    @pl.when(i != last)
    def _carry():
        w_out[0] = w


def fused_step_pallas_padded(
    w: jnp.ndarray,
    t_in: jnp.ndarray,
    operands: jnp.ndarray,
    *,
    t_window: int,
    w_max: int,
    wta_k: int,
    stabilize: bool,
    t_blk: int = 128,
    interpret: bool = False,
    keys=None,
    volley=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One fused Pallas step for a whole padded design batch.

    Args:
      w: [D, p_pad, q_pad] resident weights (pad rows/cols zero).
      t_in: [D, p_pad] f32 volley, one per design; any time >= that design's
        runtime ``t_max`` operand is silent (padding synapses included).
      operands: [D, N_OPERANDS] f32 runtime design operands
        (``design_operands``) — lives in SMEM, read per grid step.
      t_window: static evaluation length of the envelope (>= every design's
        ``t_max``); padded up to a ``t_blk`` multiple.
      interpret: run under the Pallas interpreter — pass the value from
        ``repro.core.backend.pallas_interpret()``; do not hardcode.
      keys / volley: stochastic STDP only — [D, 2] i32 stream keys and the
        i32 global volley index (None: expected STDP).

    Returns:
      (w_new [D, p_pad, q_pad], y [D, q_pad] post-WTA winner times, f32).
    """
    d, p_pad, q_pad = w.shape
    t_pad = _pad_to(t_window, t_blk)
    stochastic = keys is not None
    kern = functools.partial(
        _fused_kernel,
        t_blk=t_blk,
        t_window=t_pad,
        n_planes=w_max + 1,
        wta_k=wta_k,
        w_max=w_max,
        stabilize=stabilize,
        stochastic=stochastic,
    )
    stream_ops, stream_specs = _stream_operands(keys, volley)
    # volleys and counts ride a unit middle axis, so every block's last
    # two dims are whole array dims (the Mosaic (8, 128) tiling rule)
    w_new, y = pl.pallas_call(
        kern,
        grid=(d, t_pad // t_blk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            *stream_specs,
            pl.BlockSpec((1, 1, p_pad), lambda di, i: (di, 0, 0)),
            pl.BlockSpec((1, p_pad, q_pad), lambda di, i: (di, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, p_pad, q_pad), lambda di, i: (di, 0, 0)),
            pl.BlockSpec((1, 1, q_pad), lambda di, i: (di, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, p_pad, q_pad), jnp.float32),
            jax.ShapeDtypeStruct((d, 1, q_pad), jnp.float32),
        ],
        interpret=interpret,
        name="fused_column_step",
    )(operands, *stream_ops, t_in[:, None, :], w)
    return w_new, y[:, 0, :]


def _fused_block_kernel(
    scal_ref,  # [D, N_OPERANDS] f32 SMEM runtime design operands
    nv_ref,  # [1] i32 SMEM      valid volleys in this block (tail masking)
    *refs,
    v_blk: int,
    t_blk: int,
    t_window: int,
    n_planes: int,
    wta_k: int,
    w_max: int,
    stabilize: bool,
    stochastic: bool = False,
):
    """Volley-blocked fused body: fire + k-WTA + STDP x ``v_blk`` volleys.

    Grid = (designs,).  ONE kernel invocation advances a whole volley block:
    the weights live in VMEM for the entire block, and the in-kernel
    ``fori_loop`` folds the block's volleys *sequentially* — volley i fires
    against the weights volley i-1 wrote, exactly the online rule of the
    per-volley kernel (``_fused_kernel``), with kernel launch, HBM weight
    round-trips and plane rebuild setup amortized over ``v_blk`` updates.
    Time blocks are an inner ``fori_loop`` here (they were the grid's inner
    axis in the per-volley kernel); everything per-design still arrives as
    runtime SMEM operands against the one static envelope.  Volleys at or
    past the runtime valid count (the silent-padded block tail) fold
    nothing.

    ``refs``: ``t_ref`` [1, v_blk, p_pad] f32 volley block (silent >=
    design t_max), ``w_ref`` / ``w_out`` [1, p_pad, q_pad] f32 resident and
    updated weights; under ``stochastic`` they follow ``key_ref`` [D, 2]
    i32 (SMEM stream keys) and ``vb_ref`` [1] i32 (SMEM global index of
    the block's first volley), and volley ``vi`` of the block draws at
    index ``vb + vi``.
    """
    if stochastic:
        key_ref, vb_ref, t_ref, w_ref, w_out = refs
    else:
        t_ref, w_ref, w_out = refs
    _, p_pad, q_pad = w_ref.shape
    d = pl.program_id(0)
    nv = nv_ref[0]

    threshold = scal_ref[d, 0]
    t_max = scal_ref[d, 1]
    q_live = scal_ref[d, 2]
    mu_capture = scal_ref[d, 3]
    mu_backoff = scal_ref[d, 4]
    mu_search = scal_ref[d, 5]

    qi = _lane_iota(q_pad)
    n_tb = t_window // t_blk
    if stochastic:
        key = (key_ref[d, 0], key_ref[d, 1])
        vb = vb_ref[0]
        counter = stdp_lib.synapse_counter((p_pad, q_pad))

    def volley(vi, w):
        ti = t_ref[0, pl.ds(vi, 1), :]  # [1, p_pad]
        ti_col = ti.T  # [p_pad, 1] input times down the sublanes
        wi = jnp.round(jnp.clip(w, 0.0, float(w_max)))  # integer fire grid

        def time_block(bi, counts):
            return counts + _kernel_fire_counts(
                wi, ti_col, (bi * t_blk).astype(jnp.float32),
                threshold, t_max, t_blk=t_blk, n_planes=n_planes,
            )

        counts = jax.lax.fori_loop(
            0, n_tb, time_block, jnp.zeros((1, q_pad), jnp.float32)
        )
        t_fire = jnp.minimum(counts, t_max)
        t_fire = jnp.where(qi < q_live, t_fire, t_max)
        y = _kernel_wta(t_fire, qi, t_max, wta_k=wta_k, t_window=t_window)
        w_new = _kernel_stdp(
            w, ti_col, y, qi, t_max, q_live,
            mu_capture, mu_backoff, mu_search,
            w_max=w_max, stabilize=stabilize,
            stream=(key, vb + vi, counter) if stochastic else None,
        )
        return jnp.where(vi < nv, w_new, w)  # tail volleys fold nothing

    w_out[0] = jax.lax.fori_loop(0, v_blk, volley, w_ref[0])


def fused_block_pallas_padded(
    w: jnp.ndarray,
    t_in: jnp.ndarray,
    operands: jnp.ndarray,
    n_valid: jnp.ndarray | None = None,
    *,
    t_window: int,
    w_max: int,
    wta_k: int,
    stabilize: bool,
    v_blk: int,
    t_blk: int = 128,
    interpret: bool = False,
    keys=None,
    v_base=None,
) -> jnp.ndarray:
    """One volley-blocked fused Pallas step for a whole padded design batch.

    Args:
      w: [D, p_pad, q_pad] resident weights (pad rows/cols zero).
      t_in: [D, v_blk, p_pad] f32 volley block per design; any time >= that
        design's runtime ``t_max`` operand is silent (padding synapses and
        block-tail volleys included).
      operands: [D, N_OPERANDS] f32 runtime design operands
        (``design_operands``).
      n_valid: [1] i32 count of live volleys in the block (None = all
        ``v_blk``); volleys at or past it fold nothing (tail masking).
      interpret: run under the Pallas interpreter — pass the value from
        ``repro.core.backend.pallas_interpret()``; do not hardcode.
      keys / v_base: stochastic STDP only — [D, 2] i32 stream keys and
        the i32 global index of the block's first volley (None: expected
        STDP, and the kernel is the expected-mode program).

    Returns:
      w_new [D, p_pad, q_pad] — the weights after the block's ``v_blk``
      sequential online-STDP updates.
    """
    d, p_pad, q_pad = w.shape
    t_pad = _pad_to(t_window, t_blk)
    if n_valid is None:
        n_valid = jnp.full((1,), v_blk, TIME_DTYPE)
    stochastic = keys is not None
    kern = functools.partial(
        _fused_block_kernel,
        v_blk=v_blk,
        t_blk=t_blk,
        t_window=t_pad,
        n_planes=w_max + 1,
        wta_k=wta_k,
        w_max=w_max,
        stabilize=stabilize,
        stochastic=stochastic,
    )
    stream_ops, stream_specs = _stream_operands(keys, v_base)
    return pl.pallas_call(
        kern,
        grid=(d,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            *stream_specs,
            pl.BlockSpec((1, v_blk, p_pad), lambda di: (di, 0, 0)),
            pl.BlockSpec((1, p_pad, q_pad), lambda di: (di, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, p_pad, q_pad), lambda di: (di, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((d, p_pad, q_pad), jnp.float32),
        interpret=interpret,
        name="fit_block",
    )(operands, n_valid.astype(TIME_DTYPE), *stream_ops, t_in, w)


def fused_step_pallas(
    w_pad: jnp.ndarray,
    t_in_pad: jnp.ndarray,
    cfg: ColumnConfig,
    t_blk: int = 128,
    interpret: bool = False,
    stream=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One fused Pallas column step on pre-padded single-column operands.

    Thin D=1 wrapper over ``fused_step_pallas_padded`` — the config's
    threshold / window / q / mus become runtime operands of the same kernel
    that serves the padded design batch.

    Args:
      w_pad: [p_pad, q_pad] resident weights (pad rows/cols zero).
      t_in_pad: [1, p_pad] volley (padding/silent >= cfg.t_max).
      interpret: run under the Pallas interpreter — pass the value from
        ``repro.core.backend.pallas_interpret()``; do not hardcode.
      stream: stochastic STDP only — ``(key, volley)``, the [2] i32 stream
        key and the global volley index.

    Returns:
      (w_new [p_pad, q_pad], y [1, q_pad] post-WTA winner times, float).
    """
    operands = design_operands(
        jnp.full((1,), cfg.neuron.threshold, jnp.float32),
        jnp.full((1,), cfg.t_max, jnp.float32),
        jnp.full((1,), cfg.q, jnp.float32),
        cfg.stdp.mu_capture,
        cfg.stdp.mu_backoff,
        cfg.stdp.mu_search,
    )
    keys, volley = (None, None) if stream is None else stream
    w_new, y = fused_step_pallas_padded(
        w_pad[None], t_in_pad, operands,
        t_window=cfg.t_max, w_max=cfg.neuron.w_max, wta_k=cfg.wta.k,
        stabilize=cfg.stdp.stabilizer == "half",
        t_blk=t_blk, interpret=interpret,
        keys=None if keys is None else jnp.reshape(keys, (1, 2)),
        volley=volley,
    )
    return w_new[0], y


# ------------------------------------------------------------- fused fit
@functools.partial(
    jax.jit,
    static_argnames=("cfg", "epochs", "lowering", "trace", "t_blk"),
    donate_argnums=(0,),
)
def _fused_fit_scan(
    w: jnp.ndarray,
    xs: jnp.ndarray,
    cfg: ColumnConfig,
    epochs: int,
    lowering: str,
    trace: bool,
    t_blk: int = 128,
    key=None,
):
    """One compiled program for the whole fit: scan(epochs) o scan(volleys).

    ``w`` is donated — the weight buffer is updated in place across the
    entire training run instead of round-tripping per volley.  ``key``
    ([2] i32 stream key) is given exactly for stochastic STDP; volley n of
    epoch e then draws at index e * N + n.
    """
    if lowering == "reference":

        def step(wc, xt, stream):
            # integer_fire mirrors the Pallas lowering (planes need the
            # hardware integer grid) so results agree across lowerings.
            return fused_step_ref(
                wc, xt, cfg.neuron.threshold, cfg.t_max, cfg.neuron.w_max,
                cfg.wta.k, cfg.stdp.mu_capture, cfg.stdp.mu_backoff,
                cfg.stdp.mu_search, cfg.stdp.stabilizer == "half",
                response=cfg.neuron.response, integer_fire=True,
                stream=stream,
            )

    else:

        def step(wc, xt, stream):
            w2, y = fused_step_pallas(
                wc, xt[None], cfg, t_blk=t_blk,
                interpret=lowering == "interpret", stream=stream,
            )
            return w2, y[0, : cfg.q].astype(TIME_DTYPE)

    if key is None:

        def volley(wc, xt):
            w2, y = step(wc, xt, None)
            return w2, (y if trace else None)

        def epoch(wc, _):
            return jax.lax.scan(volley, wc, xs)

        w, ys = jax.lax.scan(epoch, w, None, length=epochs)
        return w, ys

    n = xs.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)

    def volley_s(wc, inp):
        xt, v = inp
        w2, y = step(wc, xt, (key, v))
        return w2, (y if trace else None)

    def epoch_s(wc, e):
        return jax.lax.scan(volley_s, wc, (xs, e * n + idx))

    return jax.lax.scan(
        epoch_s, w, jnp.arange(epochs, dtype=jnp.int32)
    )


# ----------------------------------------------------- padded envelope scan
@functools.partial(
    jax.jit,
    static_argnames=(
        "t_window", "w_max", "wta_k", "stabilize", "response", "epochs",
        "lowering", "t_blk", "v_blk", "plan", "stochastic",
    ),
    donate_argnums=(0,),
)
def fit_scan_padded(
    w,  # [D, p_pad, q_pad]
    xs,  # [N, D, p_pad] volleys (scan axis leading; padding silent >= t_window)
    thresholds,  # [D]
    t_maxes,  # [D]
    q_actives,  # [D]
    t_window: int,
    w_max: int,
    wta_k: int,
    mu_capture: float,
    mu_backoff: float,
    mu_search: float,
    stabilize: bool,
    response: str,
    epochs: int,
    lowering: str = "reference",
    t_blk: int | None = None,
    v_blk: int | None = None,
    plan=None,
    stochastic: bool = False,
    keys=None,  # [D, 2] i32 stream keys (stochastic only)
    v0=0,  # i32 stream index of the first volley (stochastic only)
):
    """All designs x all epochs x all volleys in ONE compiled program.

    The padding-envelope contract: every member design is padded into a
    shared (p_pad, q_pad, t_window) envelope, its per-design threshold /
    effective window / live-neuron count / STDP mus become *traced* scalars
    (runtime SMEM operands under the kernel lowerings, ``vmap``-ed operands
    under the reference lowering), and the fused column step runs over the
    leading design axis.  Callers with the same envelope shapes and static
    hyper-parameters share one compiled trace — this is what lets a
    heterogeneous design sweep (``simulator.cluster_time_series_many``) and
    heterogeneous network layers (``network.fit_greedy``) reuse each
    other's compilations: ONE compilation per envelope shape, never per
    design.

    The scan advances in volley blocks of ``v_blk``: each outer scan step
    folds ``v_blk`` sequential online-STDP volleys in one fused body — one
    kernel invocation with the weights VMEM-resident for the whole block
    (kernel lowerings), one statically-unrolled jnp block sharing
    precomputed input ramps (reference).  Exact online semantics either
    way: results are bit-identical across every ``v_blk`` (enforced by
    ``tests/test_blocked_scan.py``); blocking is a throughput knob, never a
    semantic one.  Tail volleys of the last block are silent-padded and
    masked out of the weight fold by a per-block valid count — exact
    no-ops unconditionally.

    Args:
      lowering: 'mosaic' (TPU Mosaic kernel), 'interpret' (Pallas
        interpreter, validation only) or 'reference' (pure jnp).  Callers
        should pass ``repro.core.backend.padded_lowering(response)`` rather
        than hardcoding a host assumption; the kernel lowerings support RNL
        only (``check_fusable``).  All lowerings are bit-identical on
        integer weight grids.
      t_blk: kernel time-block length (kernel lowerings only); None takes
        the plan's choice (or the lane-aligned 128 default).
      v_blk: volleys advanced per scan step; None takes the plan's
        choice, falling back to the central constants policy
        ``repro.core.backend.volley_block(lowering, n, d=D)`` —
        envelope-aware, so small-D batches get a slimmer unrolled
        reference block (cheap traces) than large-D ones.
      plan: an optional ``repro.roofline.costmodel.ExecutionPlan`` (a
        frozen, hashable static) supplying defaults for unset
        ``v_blk``/``t_blk``.  Callers that dispatch through
        ``backend.fit_padded`` never need it (the backend resolves the
        plan to concrete ints before keying its AOT cache); it exists for
        direct jit-path callers — notably the sharded bucketed sweep,
        where GSPMD needs the jit trace.  A plan changes blocking only,
        never results (value-equal plans share one trace).

    Expected-mode STDP with index tie-break WTA needs no PRNG key.
    ``stochastic=True`` (a static flag of the envelope; the expected-mode
    program is untouched by it) selects stochastic STDP: ``keys`` carries
    each design's stream key, sharded and bucketed with its design, and
    volley n of epoch e draws at global index ``v0 + e * N + n`` — the
    same bits ``mode='cycle'`` draws, whatever the envelope, block size,
    bucket or shard.  ``v0`` (an i32 scalar, traced or not) is where
    the stream resumes: a caller that trains one design in several calls
    (the streaming service's re-fits) passes the index the last call
    stopped at, so the calls together draw what one call over the joined
    volleys would.

    ``w`` is donated: the weight buffer stays resident across the whole
    epochs x volleys scan.
    """
    if lowering not in LOWERINGS:
        raise ValueError(f"unknown lowering: {lowering!r}")
    if xs.shape[0] == 0:
        # an empty stream is a caller bug: volley_block would degenerate to
        # a zero-length blocked scan — refuse loudly instead of compiling it
        raise ValueError(
            "fit_scan_padded needs at least one volley (got an empty "
            "stream, N=0)"
        )
    if epochs == 0:
        # zero training passes are well-defined: the weights are returned
        # unchanged (trivially, without building the blocked scan)
        return w
    if plan is not None:
        if v_blk is None:
            v_blk = plan.v_blk
        if t_blk is None:
            t_blk = plan.t_blk
    if t_blk is None:
        t_blk = 128
    if v_blk is None:
        from repro.core import backend  # late: backend imports this module

        v_blk = backend.volley_block(lowering, xs.shape[0], d=w.shape[0])
    if stochastic:
        if keys is None:
            raise ValueError("stochastic STDP needs per-design stream keys")
        keys = keys.astype(jnp.int32)
    # a stable name for the fit's operations in profiles
    with jax.named_scope("fit_scan_padded"):
        if lowering != "reference":
            if response not in fire_responses(lowering):
                raise ValueError(
                    f"the padded kernel lowering supports response "
                    f"{fire_responses(lowering)}, got {response!r}; use "
                    "lowering='reference'"
                )
            return _fit_scan_padded_kernel(
                w, xs, thresholds, t_maxes, q_actives,
                t_window, w_max, wta_k, mu_capture, mu_backoff, mu_search,
                stabilize, epochs, lowering, t_blk, v_blk,
                keys=keys if stochastic else None, v0=v0,
            )

        # [S, v_blk, D, p]
        xsb, n_valid = _pad_volley_blocks(xs, v_blk, t_window)
        kw = dict(
            t_window=t_window, w_max=w_max, wta_k=wta_k,
            mu_capture=mu_capture, mu_backoff=mu_backoff,
            mu_search=mu_search, stabilize=stabilize, response=response,
        )

        def block(wc, inp):  # wc: [D, p, q]; xt_blk: [v_blk, D, p]
            xt_blk, nv = inp[:2]
            # the input-side step transient of the whole block at once — the
            # reference analogue of the kernel's VMEM-resident volley block:
            # only the cumulative weight planes, one GEMM and the plane delays
            # stay inside the sequential (unrolled) loop
            s = _masked_steps(
                xt_blk, t_maxes[None, :, None], t_window
            )  # [v_blk, D, p, T]
            for i in range(v_blk):  # static unroll: one fused XLA body
                valid = i < nv  # tail volleys fold nothing
                vb = inp[2] + i if stochastic else None
                wc = jax.vmap(
                    lambda wd, sd, xd, th, tm, qa, kd: _block_step_ref(
                        wd, sd, xd, th, tm, qa,
                        None if kd is None else (kd, vb), valid=valid, **kw
                    )
                )(wc, s[i], xt_blk[i], thresholds, t_maxes, q_actives,
                  keys if stochastic else None)
            return wc, None

        return _scan_epochs(
            block, w, xsb, n_valid, epochs,
            _block_bases(epochs, xs.shape[0], v_blk, v0) if stochastic
            else None,
        )


def _scan_epochs(block, w, xsb, n_valid, epochs: int, bases=None):
    """Fold ``block`` over the volley blocks of every epoch.  ``bases``
    ([epochs, S] i32, stochastic STDP only) rides as the third block input:
    each block's global index of its first volley."""
    if bases is None:

        def epoch(wc, _):
            return jax.lax.scan(block, wc, (xsb, n_valid))

        return jax.lax.scan(epoch, w, None, length=epochs)[0]

    def epoch_s(wc, vb_row):
        return jax.lax.scan(block, wc, (xsb, n_valid, vb_row))

    return jax.lax.scan(epoch_s, w, bases)[0]


def _block_bases(epochs: int, n: int, v_blk: int, v0=0):
    """[epochs, S] global index of each volley block's first volley:
    ``v0 + e * N + b * v_blk`` (the stochastic stream's volley index)."""
    e = jnp.arange(epochs, dtype=jnp.int32)[:, None]
    b = jnp.arange(-(-n // v_blk), dtype=jnp.int32)[None, :]
    return v0 + e * n + b * v_blk


def _fit_scan_padded_kernel(
    w, xs, thresholds, t_maxes, q_actives,
    t_window, w_max, wta_k, mu_capture, mu_backoff, mu_search,
    stabilize, epochs, lowering, t_blk, v_blk, keys=None, v0=0,
):
    """Kernel-lowering body of ``fit_scan_padded`` (called inside its jit).

    Re-pads the caller's envelope up to the Mosaic tile grid (p to a LANE
    multiple, q to a SUBLANE multiple, t_window to a ``t_blk`` multiple),
    packs the per-design scalars into the runtime SMEM operand array once,
    and scans ``fused_block_pallas_padded`` over epochs x volley blocks —
    each scan step is ONE kernel invocation advancing ``v_blk`` volleys.
    Alignment padding is masked exactly like caller padding: extra synapses
    are silent, extra neurons sit above every ``q_active``.
    """
    d, p_env, q_env = w.shape
    p_pad = _pad_to(p_env, LANE)
    q_pad = _pad_to(q_env, SUBLANE)
    operands = design_operands(
        thresholds, t_maxes, q_actives, mu_capture, mu_backoff, mu_search
    )
    w_k = (
        jnp.zeros((d, p_pad, q_pad), jnp.float32)
        .at[:, :p_env, :q_env]
        .set(w.astype(jnp.float32))
    )
    # alignment rows (and block-tail volleys below) reuse the caller's
    # sentinel convention: any time >= t_window is silent for all designs
    xs_k = _pad_volleys_silent(xs, p_pad, t_window)
    xsb, n_valid = _pad_volley_blocks(xs_k, v_blk, float(t_window))
    xsb = jnp.swapaxes(xsb, 1, 2)  # [S, D, v_blk, p_pad]: design axis leads

    def block(wc, inp):  # wc: [D, p_pad, q_pad]; xt: [D, v_blk, p_pad]
        xt, nv = inp[:2]
        w2 = fused_block_pallas_padded(
            wc, xt, operands, nv.reshape((1,)),
            t_window=t_window, w_max=w_max, wta_k=wta_k,
            stabilize=stabilize, v_blk=v_blk, t_blk=t_blk,
            interpret=lowering == "interpret",
            keys=keys, v_base=None if keys is None else inp[2],
        )
        return w2, None

    w_k = _scan_epochs(
        block, w_k, xsb, n_valid, epochs,
        None if keys is None
        else _block_bases(epochs, xs.shape[0], v_blk, v0),
    )
    return w_k[:, :p_env, :q_env]


def _fire_block_kernel(
    scal_ref,  # [D, N_OPERANDS] f32 SMEM runtime design operands
    t_ref,  # [1, 1, 1, p_pad]   f32 one volley (silent >= design t_max)
    w_ref,  # [1, p_pad, q_pad]  f32 frozen weights
    y_out,  # [1, 1, 1, q_pad]   f32 counts accumulator -> firing times
    *,
    t_blk: int,
    n_planes: int,
    w_max: int,
):
    """Batched fire body, grid = (designs, volleys, time blocks).

    Inference has no sequential dependency, so instead of scanning volleys
    on the host the whole batch rides the kernel grid: ONE ``pallas_call``
    fires every volley of every design (the fire half of ``_fused_kernel``
    with a volley grid axis and no WTA/STDP — assignment only needs raw
    per-neuron firing times).
    """
    _, p_pad, q_pad = w_ref.shape
    d = pl.program_id(0)
    i = pl.program_id(2)
    last = pl.num_programs(2) - 1

    threshold = scal_ref[d, 0]
    t_max = scal_ref[d, 1]
    q_live = scal_ref[d, 2]

    @pl.when(i == 0)
    def _init():
        y_out[...] = jnp.zeros_like(y_out)

    wi = jnp.round(jnp.clip(w_ref[0], 0.0, float(w_max)))
    y_out[0, 0] += _kernel_fire_counts(
        wi, t_ref[0, 0].T, (i * t_blk).astype(jnp.float32), threshold,
        t_max, t_blk=t_blk, n_planes=n_planes,
    )

    @pl.when(i == last)
    def _finalize():
        qi = _lane_iota(q_pad)
        t_fire = jnp.minimum(y_out[0, 0], t_max)
        y_out[0, 0] = jnp.where(qi < q_live, t_fire, t_max)


def _ids_from_times(t_fire, t_maxes, q_actives):
    """Firing times [D, N, q] -> cluster ids [D, N].

    The id of a volley is the earliest-firing neuron's index (index
    tie-break — and therefore independent of ``wta_k``: the k-WTA keeps the
    global minimum for every k >= 1), or the design's live-neuron count
    when no neuron spikes (the 'unclustered' bucket)."""
    tm = t_maxes.astype(jnp.float32)[:, None]
    tf = t_fire.astype(jnp.float32)
    spiked = (tf < tm[..., None]).any(axis=-1)
    idx = jnp.argmin(tf, axis=-1)
    return jnp.where(spiked, idx, q_actives[:, None]).astype(TIME_DTYPE)


@functools.partial(
    jax.jit,
    static_argnames=("t_window", "wta_k", "response", "lowering", "t_blk",
                     "v_blk", "w_max", "plan"),
)
def assign_padded(
    w, xs, thresholds, t_maxes, q_actives,
    t_window: int, wta_k: int, response: str,
    lowering: str = "reference", t_blk: int | None = None,
    v_blk: int | None = None, w_max: int | None = None,
    plan=None,
):
    """Cluster ids for every padded design: [N, D, p_pad] -> [D, N].

    Same envelope contract as ``fit_scan_padded``, but embarrassingly
    parallel: no volley ever depends on another, so volleys are *batched*
    rather than scanned.  Under the kernel lowerings the whole stream rides
    the kernel grid — ONE ``pallas_call`` with grid (designs, volleys, time
    blocks), no host scan at all (``w_max`` is required: the kernel fires
    on the integer weight grid, so auto-selecting it is only a pure
    lowering choice when the weights are on the grid — see
    ``backend.assign_lowering``).  Under the reference lowering volleys are
    fired in vmapped blocks of ``v_blk`` (a ``lax.map`` over blocks bounds
    the dense transient instead of materializing it for the full stream),
    keeping the established float-weight fire semantics bit-for-bit.

    The id of a volley is the winner neuron index, or the design's
    live-neuron count ``q_active`` when no neuron spikes (the 'unclustered'
    bucket); it is independent of ``wta_k`` (the k-WTA keeps the global
    minimum for every k >= 1).

    ``plan`` carries the same optional ``ExecutionPlan`` defaults as
    ``fit_scan_padded`` (unset ``v_blk``/``t_blk`` only; blocking, never
    semantics).
    """
    if lowering not in LOWERINGS:
        raise ValueError(f"unknown lowering: {lowering!r}")
    if xs.shape[0] == 0:
        # same up-front guard as fit_scan_padded: an empty stream has no
        # volleys to assign, and the kernel grid would degenerate
        raise ValueError(
            "assign_padded needs at least one volley (got an empty "
            "stream, N=0)"
        )
    if plan is not None:
        if v_blk is None:
            v_blk = plan.v_blk
        if t_blk is None:
            t_blk = plan.t_blk
    if t_blk is None:
        t_blk = 128
    if v_blk is None:
        from repro.core import backend  # late: backend imports this module

        v_blk = backend.volley_block(lowering, xs.shape[0])
    # a stable name for the assignment's operations in profiles
    with jax.named_scope("assign_padded"):
        n = xs.shape[0]
        if lowering != "reference":
            if response not in fire_responses(lowering):
                raise ValueError(
                    f"the padded kernel lowering supports response "
                    f"{fire_responses(lowering)}, got {response!r}; use "
                    "lowering='reference'"
                )
            if w_max is None:
                raise ValueError(
                    "the kernel assign lowering needs w_max (integer-grid "
                    "weight planes)"
                )
            d, p_env, q_env = w.shape
            p_pad = _pad_to(p_env, LANE)
            q_pad = _pad_to(q_env, SUBLANE)
            t_pad = _pad_to(t_window, t_blk)
            operands = design_operands(
                thresholds, t_maxes, q_actives, 0.0, 0.0, 0.0
            )
            w_k = (
                jnp.zeros((d, p_pad, q_pad), jnp.float32)
                .at[:, :p_env, :q_env]
                .set(w.astype(jnp.float32))
            )
            xs_k = jnp.swapaxes(
                _pad_volleys_silent(xs, p_pad, t_window), 0, 1
            )[:, :, None, :]  # [D, N, 1, p_pad]: unit axis keeps blocks whole
            kern = functools.partial(
                _fire_block_kernel,
                t_blk=t_blk, n_planes=w_max + 1, w_max=w_max,
            )
            t_fire = pl.pallas_call(
                kern,
                grid=(d, n, t_pad // t_blk),
                in_specs=[
                    pl.BlockSpec(memory_space=pltpu.SMEM),
                    pl.BlockSpec(
                        (1, 1, 1, p_pad), lambda di, vi, ti: (di, vi, 0, 0)
                    ),
                    pl.BlockSpec(
                        (1, p_pad, q_pad), lambda di, vi, ti: (di, 0, 0)
                    ),
                ],
                out_specs=pl.BlockSpec(
                    (1, 1, 1, q_pad), lambda di, vi, ti: (di, vi, 0, 0)
                ),
                out_shape=jax.ShapeDtypeStruct((d, n, 1, q_pad), jnp.float32),
                interpret=lowering == "interpret",
                name="assign_fire",
            )(operands, xs_k, w_k)
            return _ids_from_times(
                t_fire[:, :, 0, :q_env], t_maxes, q_actives
            )

        qi = jnp.arange(w.shape[2], dtype=TIME_DTYPE)
        # tail rows are sliced away below, so the valid counts are unused
        xsb, _ = _pad_volley_blocks(xs, v_blk, t_window)  # [S, v_blk, D, p]

        def block(xt_blk):  # [v_blk, D, p] -> [v_blk, D, q]
            def one(wd, xd, th, tm, qa):
                # float-weight dense fire: the established assignment
                # arithmetic, volley for volley (only the batching is new)
                t = fire_dense_ref(
                    wd, xd, th, t_window, t_max=tm, response=response
                )
                return jnp.where(qi < qa, t, tm)

            return jax.vmap(  # volleys in the block
                jax.vmap(one, in_axes=(0, 0, 0, 0, 0)),  # designs
                in_axes=(None, 0, None, None, None),
            )(w, xt_blk, thresholds, t_maxes, q_actives)

        t_all = jax.lax.map(block, xsb)  # [S, v_blk, D, q]
        t_all = t_all.reshape((-1,) + t_all.shape[2:])[:n]  # [N, D, q]
        return _ids_from_times(
            jnp.moveaxis(t_all, 0, 1), t_maxes, q_actives
        )


# -------------------------------------------------- AOT precompilation
# ``jit(...).lower().compile()`` entry points for the padded scans: an
# envelope is fully described by shapes + statics, so its executable can
# be built ahead of the first real operands — a service can pre-compile
# its envelope set at startup, and ``backend.fit_padded`` /
# ``backend.assign_padded`` cache these per envelope so equal-envelope
# buckets share ONE executable across sweep calls and (with
# ``backend.compile_cache``) across processes.  The executables are the
# very programs the jit path would build: bit-identical results, same
# donation (``tests/test_aot_cache.py``).

def _fit_scan_padded_specs(
    d: int, p_pad: int, q_pad: int, n_volleys: int, stochastic: bool = False
):
    """(args, dynamic kwargs) abstract specs mirroring one fit call
    exactly: the mus, and the stream keys and first stream index of a
    stochastic fit."""
    f32 = jnp.float32
    args = (
        jax.ShapeDtypeStruct((d, p_pad, q_pad), f32),          # w
        jax.ShapeDtypeStruct((n_volleys, d, p_pad), TIME_DTYPE),  # xs
        jax.ShapeDtypeStruct((d,), f32),                       # thresholds
        jax.ShapeDtypeStruct((d,), TIME_DTYPE),                # t_maxes
        jax.ShapeDtypeStruct((d,), TIME_DTYPE),                # q_actives
    )
    mus = {
        name: jax.ShapeDtypeStruct((), f32)
        for name in ("mu_capture", "mu_backoff", "mu_search")
    }
    if stochastic:
        mus["keys"] = jax.ShapeDtypeStruct((d, 2), jnp.int32)
        mus["v0"] = jax.ShapeDtypeStruct((), jnp.int32)
    return args, mus


def precompile_fit_scan_padded(
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
    lowering: str = "reference",
    t_blk: int = 128,
    v_blk: int | None = None,
    stochastic: bool = False,
):
    """AOT-compile ``fit_scan_padded`` for one envelope; no operands needed.

    Returns a ``jax.stages.Compiled`` executable.  Call it exactly like
    the dynamic half of the jitted entry point — five positional arrays
    ``(w, xs, thresholds, t_maxes, q_actives)`` matching the spec shapes
    plus the three STDP mus by keyword as f32 scalars, and for a
    ``stochastic`` envelope ``keys`` ([D, 2] i32) and ``v0`` (i32
    scalar) by keyword too (the
    call's args/kwargs pytree must mirror the lowering's) — and it behaves
    bit-for-bit like the jit path, including donating ``w``.
    """
    if v_blk is None:
        from repro.core import backend  # late: backend imports this module

        v_blk = backend.volley_block(lowering, n_volleys, d=d)
    args, dyn = _fit_scan_padded_specs(d, p_pad, q_pad, n_volleys, stochastic)
    statics = {"stochastic": True} if stochastic else {}
    return fit_scan_padded.lower(
        *args,
        t_window=t_window, w_max=w_max, wta_k=wta_k, **dyn,
        stabilize=stabilize, response=response, epochs=epochs,
        lowering=lowering, t_blk=t_blk, v_blk=v_blk, **statics,
    ).compile()


def precompile_assign_padded(
    d: int,
    p_pad: int,
    q_pad: int,
    n_volleys: int,
    *,
    t_window: int,
    wta_k: int,
    response: str,
    lowering: str = "reference",
    t_blk: int = 128,
    v_blk: int | None = None,
    w_max: int | None = None,
):
    """AOT-compile ``assign_padded`` for one envelope.

    Same contract as ``precompile_fit_scan_padded``: the returned
    ``Compiled`` takes the five positional arrays and is bit-identical to
    the jitted assignment (nothing donated).
    """
    if v_blk is None:
        from repro.core import backend  # late: backend imports this module

        v_blk = backend.volley_block(lowering, n_volleys)
    args, _ = _fit_scan_padded_specs(d, p_pad, q_pad, n_volleys)
    return assign_padded.lower(
        *args,
        t_window=t_window, wta_k=wta_k, response=response,
        lowering=lowering, t_blk=t_blk, v_blk=v_blk, w_max=w_max,
    ).compile()


def fit_fused(
    params: dict,
    x: jnp.ndarray,
    cfg: ColumnConfig,
    epochs: int = 8,
    lowering: str = "reference",
    trace: bool = False,
    t_blk: int = 128,
    rng=None,
) -> tuple[dict, jnp.ndarray | None]:
    """Online STDP over [N, p] volleys as ONE jitted, donated scan.

    Weight padding / plane setup happens here, once per fit — never per
    volley.  Returns (params, ys) where ys is [epochs, N, q] winner times
    when ``trace`` else None.  Stochastic STDP needs ``rng``: its stream
    key is ``stdp.stream_key(rng)``, the one the solvers draw from.
    """
    check_fusable(cfg, lowering)
    key = None
    if cfg.stdp.mode == "stochastic":
        if rng is None:
            raise ValueError("stochastic STDP requires a PRNG key")
        key = stdp_lib.stream_key(rng)
    # copy: the scan donates its weight buffer; the caller keeps params.
    w = jnp.array(params["w"], jnp.float32, copy=True)
    if lowering == "reference":
        w_new, ys = _fused_fit_scan(
            w, x, cfg, epochs, lowering, trace, key=key
        )
        return {"w": w_new}, ys

    p_pad = _pad_to(cfg.p, LANE)
    q_pad = _pad_to(cfg.q, SUBLANE)
    t_pad = _pad_to(cfg.t_max, t_blk)
    w_pad = jnp.zeros((p_pad, q_pad), jnp.float32).at[: cfg.p, : cfg.q].set(w)
    xs = _pad_volleys_silent(x, p_pad, 2.0 * t_pad)
    xs = jnp.where(xs >= cfg.t_max, 2.0 * t_pad, xs)
    w_new, ys = _fused_fit_scan(
        w_pad, xs, cfg, epochs, lowering, trace, t_blk, key=key
    )
    return {"w": w_new[: cfg.p, : cfg.q]}, ys
