"""Pallas TPU kernel: fused RNL body-potential + first-crossing detection.

This is the compute hot-spot of the TNN column (in silicon: the bank of
unary ramp units + threshold comparators).  TPU-native adaptation:

  V[b, j, t] = sum_i min(relu(t - t_i), w_ij)
             = sum_i relu(t - t_i)  -  sum_v sum_i 1[w_ij == v] relu(t - t_i - v)

Integer weights (w in {0..w_max}, 3-bit in TNN7) decompose into one-hot
*value planes* ``W_v[i, j]`` for v = 0..w_max; the second term becomes
(w_max + 1) dense (q x p)@(p x B*T) matmuls — MXU work — while the first
term is a cheap column-sum.  (The v = 0 plane is required: it cancels the
base term for zero-weight synapses.)  Because V is nondecreasing in t (ramps
never decay), the firing time equals the COUNT of sub-threshold cycles:

  t_fire[b, j] = sum_t 1[V[b, j, t] < threshold]   (== t_max if never fires)

so the time dimension is a pure reduction: no cross-block "first hit" state,
the grid just accumulates partial counts into the output block.

Layout: the batch tile is folded into the lane dimension next to time —
A[p_pad, B_blk * t_blk] — so every plane matmul is one
(q_pad x p_pad) @ (p_pad x B_blk*t_blk) contraction with p padded to the
128-lane contraction dim and q padded to sublanes.  VMEM budget (defaults
B_blk=8, t_blk=128, p_pad<=2048): A + one transient + planes ~= 10 MB.

Non-spiking synapses (t_in >= t_max) contribute 0 automatically (their ramps
never start inside the window); synapse padding uses t_in = 2*t_max and
zero planes; neuron padding (q_pad > q) produces garbage counts that the
ops.py wrapper slices off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Lane/sublane alignment for TPU tiling.
LANE = 128
SUBLANE = 8


def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _rnl_kernel(
    t_in_ref,  # [B_blk, p_pad]              f32 (no-spike >= t_max)
    planes_ref,  # [n_planes, p_pad, q_pad]  f32 one-hot planes, v = 0..w_max
    out_ref,  # [B_blk, q_pad]               f32 sub-threshold cycle counts
    *,
    t_blk: int,
    n_planes: int,
    threshold: float,
):
    b_blk, p_pad = t_in_ref.shape
    q_pad = planes_ref.shape[2]
    t0 = (pl.program_id(1) * t_blk).astype(jnp.float32)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # A[p, b*t] = relu(t - t_in[b, i]) with (b, t) folded into lanes.
    # Mosaic iotas are integer-only: build the lane index in i32, cast once
    tv = t0 + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, t_blk), 2
    ).astype(jnp.float32)  # [1, 1, t_blk]
    ti = t_in_ref[...].T  # [p_pad, B_blk]
    a = jnp.maximum(tv - ti[:, :, None], 0.0)  # [p, B, t]
    a = a.reshape(p_pad, b_blk * t_blk)

    base = jnp.sum(a, axis=0, keepdims=True)  # [1, B*t]
    acc = jnp.zeros((q_pad, b_blk * t_blk), jnp.float32)
    for v in range(n_planes):  # static unroll: w_max + 1 plane matmuls
        wv = planes_ref[v, :, :]  # [p_pad, q_pad]
        av = a if v == 0 else jnp.maximum(a - float(v), 0.0)
        acc = acc + jax.lax.dot_general(
            wv, av, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [q_pad, B*t]

    vqt = base - acc  # [q_pad, B*t]
    below = (vqt < threshold).astype(jnp.float32)
    counts = below.reshape(q_pad, b_blk, t_blk).sum(axis=2)  # [q_pad, B_blk]
    out_ref[...] += counts.T


def make_weight_planes(w: jnp.ndarray, w_max: int) -> jnp.ndarray:
    """One-hot weight value planes: [p, q] int-valued -> [w_max+1, p, q] f32."""
    wi = jnp.round(w).astype(jnp.int32)
    v = jnp.arange(w_max + 1, dtype=jnp.int32)
    return (wi[None, :, :] == v[:, None, None]).astype(jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("threshold", "t_max", "w_max", "b_blk", "t_blk", "interpret"),
)
def rnl_fire_pallas(
    t_in: jnp.ndarray,
    w: jnp.ndarray,
    threshold: float,
    t_max: int,
    w_max: int,
    b_blk: int = 8,
    t_blk: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused RNL firing-time kernel entry point.

    Args:
      t_in: [B, p] int32 spike times (>= t_max means no spike).
      w: [p, q] integer-valued weights in [0, w_max].
      threshold: firing threshold.
      t_max: window length in cycles.
      w_max: weight ceiling (3-bit TNN7 -> 7).
      b_blk / t_blk: batch tile and time tile (lane-aligned).
      interpret: None (default) defers to the central dispatch policy
        (``repro.core.backend.pallas_interpret()``: Mosaic on TPU,
        interpreter elsewhere); pass an explicit bool only in tests.

    Returns:
      [B, q] int32 firing times (t_max if the neuron never fires).
    """
    if interpret is None:
        from repro.core import backend as backend_lib

        interpret = backend_lib.pallas_interpret()
    B, p = t_in.shape
    q = w.shape[1]
    t_pad = _pad_to(t_max, t_blk)
    b_pad = _pad_to(B, b_blk)
    p_pad = _pad_to(p, LANE)
    q_pad = _pad_to(q, SUBLANE)
    n_planes = w_max + 1

    ti = jnp.full((b_pad, p_pad), 2.0 * t_pad, jnp.float32)
    ti = ti.at[:B, :p].set(t_in.astype(jnp.float32))
    # clamp genuine no-spikes to a value outside every time block
    ti = jnp.where(ti >= t_max, 2.0 * t_pad, ti)

    planes = jnp.zeros((n_planes, p_pad, q_pad), jnp.float32)
    planes = planes.at[:, :p, :q].set(make_weight_planes(w, w_max))

    grid = (b_pad // b_blk, t_pad // t_blk)
    out = pl.pallas_call(
        functools.partial(
            _rnl_kernel, t_blk=t_blk, n_planes=n_planes, threshold=threshold
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((b_blk, p_pad), lambda b, t: (b, 0)),
            pl.BlockSpec((n_planes, p_pad, q_pad), lambda b, t: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((b_blk, q_pad), lambda b, t: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((b_pad, q_pad), jnp.float32),
        interpret=interpret,
    )(ti, planes)

    # padded time blocks beyond t_max count as sub-threshold only if V stays
    # below threshold; we clamp to t_max and slice padding off.
    counts = jnp.minimum(out[:B, :q], float(t_max))
    return counts.astype(jnp.int32)
