"""Device-calibrated cost model driving the fused-path execution policy.

Every performance-critical knob of the fused scans used to be a constant
tuned on one noisy 2-core host: ``backend.volley_block``'s 8/32, the
``t_blk=128`` time-block default, ``ENVELOPE_WASTE_CAP=4.0``, and the
largest-divisor shard policy.  This module replaces the *numbers* with a
*model* while keeping the constants as the documented fallback:

* **DeviceProfile** — the calibration record: peak FLOP/s, HBM/memory
  bandwidth, inter-device link bandwidth, per-dispatch launch overhead,
  per-trace compile cost, and the on-chip footprint bound (VMEM on TPU,
  a cache-resident working-set bound on CPU).  Named default profiles
  ship for TPU v5e (Google Cloud's published peaks) and a generic host
  CPU.
* **calibrate()** — measures the peaks once per host/platform with a
  tiny probe suite (a jitted matmul for FLOP/s, a streaming add for
  bandwidth, a no-op dispatch loop for launch overhead, one fresh
  compile for trace cost) and caches the record on disk next to the
  persistent compilation cache (``backend.compile_cache``), exactly like
  the AOT executable layer: measured once, deserialized forever after.
* **envelope_cost()** — FLOPs/bytes per volley for the *actual* fused
  scan envelope, read from XLA's ``cost_analysis`` on the lowered
  1-volley program when the backend can provide it, with the closed-form
  kernel algebra (the documented MXU plane-matmul count) as fallback.
* **choose_plan()** — enumerates candidate ``(v_blk, t_blk, shards)``
  triples, predicts warm step time for each from the three-term roofline
  (compute, memory, dispatch amortization) plus a trace-cost term for
  the statically-unrolled reference block, discards candidates whose
  transient footprint exceeds the profile's bound, and returns the
  argmin as an ``ExecutionPlan``.

The ONE invariant: a plan changes blocking/sharding/bucketing, never
semantics.  Every candidate the model may pick is bit-identical to every
other (the ``v_blk``/``t_blk``/shard bit-identity contracts pinned in
``tests/test_blocked_scan.py`` and ``docs/kernels.md``), so the model can
be wrong about *speed* but never about *results*.

**No implicit probing**: policy code consults :func:`profile` which
returns the profile explicitly activated in this process (via
``calibrate()``, ``load_profile()`` or ``set_profile()``) — or None, in
which case every policy falls back to the hand-tuned constants.  Library
imports never trigger a probe; benches and launchers opt in with
``load_or_calibrate()``.  See ``docs/costmodel.md`` for the full
contract.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import math
import os
import time
from typing import Optional

# Lane/sublane geometry of the Mosaic kernels (mirrors
# kernels/fused_column.py; duplicated as plain ints so this module never
# imports jax at module scope — policy lookups must stay import-light).
LANE = 128
SUBLANE = 8

CALIBRATION_FILE = "calibration.json"
CALIBRATION_VERSION = 1
# XLA cost_analysis results per envelope, persisted next to the
# calibration record: the ~tens-of-ms trace probe runs once per host per
# envelope, not once per process (a fresh process inside the cold-start
# path would otherwise re-pay it inside the very region being measured)
COSTS_FILE = "envelope_costs.json"
COSTS_VERSION = 1

# Fallback constants — the pre-costmodel hand-tuned policy, still the
# behavior whenever no profile is active (see ``constants_plan``).
CONST_V_BLK_REFERENCE = 8
CONST_V_BLK_KERNEL = 32
CONST_T_BLK = 128
CONST_WASTE_CAP = 4.0


@dataclasses.dataclass(frozen=True)
class DeviceProfile:
    """One host/platform calibration record.

    ``peak_flops``/``hbm_bw``/``link_bw`` are the classic roofline peaks
    (FLOP/s, B/s, B/s per link).  ``dispatch_s`` is the measured overhead
    of dispatching one jitted executable (the cost volley-blocking
    amortizes); ``compile_s`` the cost of one small trace+compile (the
    cost envelope sharing and bounded reference unrolls amortize);
    ``footprint_bytes`` the working-set bound a step's transients must
    respect (VMEM per core on TPU, a cache-resident bound on CPU).
    ``calibrated`` distinguishes measured records from the named
    defaults.
    """

    name: str
    platform: str           # jax.default_backend() at calibration time
    device_kind: str
    peak_flops: float
    hbm_bw: float
    link_bw: float
    dispatch_s: float
    compile_s: float
    footprint_bytes: float
    n_devices: int = 1
    calibrated: bool = False
    # Measured fused-path efficiency: predicted-roofline / measured warm
    # seconds on a small REAL fused-fit probe envelope.  The raw roofline
    # over-counts on hosts where the step's transients stay cache-resident
    # (XLA's 'bytes accessed' assumes every byte hits HBM), so the fused
    # probe anchors absolute predictions to reality; relative ordering of
    # candidates is unaffected (the scalar divides every candidate alike).
    fused_eff: float = 1.0

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["version"] = CALIBRATION_VERSION
        return d

    @staticmethod
    def from_json(d: dict) -> "DeviceProfile":
        d = {k: v for k, v in d.items() if k != "version"}
        return DeviceProfile(**d)


# Default profiles, keyed by the ``device_kind`` JAX reports for the
# device (``jax.devices()[0].device_kind``; a TPU v5e reports
# 'TPU v5 lite').  The v5e record carries Google Cloud's published v5e
# peaks (197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s per ICI link) plus the
# ~16 MB/core VMEM bound; 'cpu' is a deliberately conservative generic
# host (runs that want real numbers calibrate).  Neither is ever *active*
# implicitly — they are reference records for the roofline report, not a
# silent policy input.  A device whose kind is not here has no peaks: see
# ``default_profile``.
TPU_V5E = "TPU v5 lite"
PROFILES: dict[str, DeviceProfile] = {
    TPU_V5E: DeviceProfile(
        name="tpu-v5e", platform="tpu", device_kind=TPU_V5E,
        peak_flops=197e12, hbm_bw=819e9, link_bw=50e9,
        dispatch_s=5e-6, compile_s=2.0, footprint_bytes=16 * 2**20,
    ),
    "cpu": DeviceProfile(
        name="host-cpu", platform="cpu", device_kind="cpu",
        peak_flops=5e10, hbm_bw=1e10, link_bw=1e10,
        dispatch_s=3e-5, compile_s=0.05, footprint_bytes=32 * 2**20,
    ),
}


def default_profile(device_kind: str) -> DeviceProfile:
    """The default profile of ``device_kind``; an unknown device is an
    error, never silently given another device's peaks."""
    try:
        return PROFILES[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} "
            f"(known: {sorted(PROFILES)})"
        ) from None


# ------------------------------------------------------------ activation
# The active profile is process state, set EXPLICITLY (calibrate /
# load_profile / set_profile) — policy functions read it, never populate
# it, so tests and libraries stay hermetic by default.
_ACTIVE: Optional[DeviceProfile] = None


def profile() -> Optional[DeviceProfile]:
    """The active calibration record, or None (constants fallback)."""
    return _ACTIVE


def set_profile(p: Optional[DeviceProfile]) -> Optional[DeviceProfile]:
    """Activate ``p`` (or deactivate with None).  Returns the previous
    active profile.  Plan lookups are memoized on the active profile, so
    switching invalidates nothing stale — the profile is part of the
    memo key."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = p
    return prev


@contextlib.contextmanager
def override(p: Optional[DeviceProfile]):
    """Temporarily activate ``p`` (None = force the constants fallback).
    The bench head-to-heads use this to time plan-vs-constants on the
    same code path."""
    prev = set_profile(p)
    try:
        yield
    finally:
        set_profile(prev)


def calibration_path() -> Optional[str]:
    """Where the calibration record persists: inside the persistent
    compilation cache directory (``backend.compile_cache``), so every
    compile artifact lives in one place.  None when no cache directory is
    enabled — calibration then lives only in this process."""
    from repro.core import backend as backend_lib

    root = backend_lib.compile_cache_dir()
    if root is None:
        return None
    return os.path.join(root, CALIBRATION_FILE)


def save_profile(p: DeviceProfile, path: Optional[str] = None) -> Optional[str]:
    """Persist ``p`` (atomic write-then-rename, same publish discipline
    as the AOT store).  Returns the path written, or None when no
    persistence root is available."""
    path = path or calibration_path()
    if path is None:
        return None
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(p.to_json(), f, indent=2)
    os.replace(tmp, path)
    return path


def load_profile(path: Optional[str] = None) -> Optional[DeviceProfile]:
    """Load and ACTIVATE a persisted calibration record, if one exists
    and matches this host (platform + device kind + device count — a
    record measured on different silicon is ignored, never wrong).
    Returns the activated profile or None."""
    import jax

    path = path or calibration_path()
    if path is None or not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            d = json.load(f)
        if d.get("version") != CALIBRATION_VERSION:
            return None
        p = DeviceProfile.from_json(d)
    except (OSError, ValueError, TypeError):
        return None
    if (
        p.platform != jax.default_backend()
        or p.device_kind != jax.devices()[0].device_kind
        or p.n_devices != jax.local_device_count()
    ):
        return None
    set_profile(p)
    return p


# ------------------------------------------------------------ probe suite
def _probe_peak_flops() -> float:
    """Peak f32 FLOP/s via a jitted square matmul (min over rounds)."""
    import jax
    import jax.numpy as jnp

    n = 384
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((n, n), jnp.float32)
    jax.block_until_ready(f(a, a))
    best = math.inf
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(f(a, a))
        best = min(best, time.perf_counter() - t0)
    return 2.0 * n**3 / max(best, 1e-9)


def _probe_hbm_bw() -> float:
    """Streaming bandwidth via a jitted elementwise add over ~64 MB
    (read + write counted)."""
    import jax
    import jax.numpy as jnp

    n = 16 * 2**20  # 16M f32 = 64 MB
    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros((n,), jnp.float32)
    jax.block_until_ready(f(x))
    best = math.inf
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(f(x))
        best = min(best, time.perf_counter() - t0)
    return 2.0 * 4.0 * n / max(best, 1e-9)


def _probe_dispatch_s() -> float:
    """Per-call overhead of dispatching one tiny jitted executable."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros((8,), jnp.float32)
    jax.block_until_ready(f(x))
    best = math.inf
    for _ in range(7):
        t0 = time.perf_counter()
        for _ in range(20):
            f(x)
        jax.block_until_ready(f(x))
        best = min(best, (time.perf_counter() - t0) / 21)
    return best


def _probe_compile_s() -> float:
    """Cost of one small trace+compile (fresh function each round so the
    jit cache cannot answer).  Against a populated persistent cache this
    measures trace+deserialize — which IS the marginal cost a new trace
    pays in that environment, so the number stays honest."""
    import jax
    import jax.numpy as jnp

    x = jnp.zeros((64, 64), jnp.float32)
    times = []
    for i in range(2):
        c = float(i) + 0.5

        def fresh(a, _c=c):
            return (a * _c + _c).sum()

        t0 = time.perf_counter()
        jax.block_until_ready(jax.jit(fresh)(x))
        times.append(time.perf_counter() - t0)
    return min(times)


def _probe_fused_eff(p: DeviceProfile) -> float:
    """Anchor the roofline to a REAL fused fit: run one small reference
    envelope warm and return predicted/measured.  Pinned ``v_blk``/
    ``t_blk`` so the probe never consults the (not yet active) plan
    policy.  A failing probe raises: a calibration that cannot run the
    fused fit it calibrates is broken, and a neutral answer would hide
    that."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import backend as backend_lib
    from repro.core.types import TIME_DTYPE

    d, pp, qp, tw, nb, ep, vb = 2, 64, 8, 64, 32, 1, 2
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.integers(0, tw, (nb, d, pp)), TIME_DTYPE)
    thr = jnp.full((d,), float(pp) / 3, jnp.float32)
    tm = jnp.full((d,), tw, TIME_DTYPE)
    qa = jnp.full((d,), qp - 2, TIME_DTYPE)
    kw = dict(
        t_window=tw, w_max=7, wta_k=1, mu_capture=2.0, mu_backoff=1.0,
        mu_search=1.0, stabilize=False, response="rnl", epochs=ep,
        lowering="reference", t_blk=CONST_T_BLK, v_blk=vb,
    )

    def run():
        w = jnp.asarray(rng.integers(0, 8, (d, pp, qp)), jnp.float32)
        t0 = time.perf_counter()
        jax.block_until_ready(backend_lib.fit_padded(w, xs, thr, tm, qa, **kw))
        return time.perf_counter() - t0

    run()  # compile
    measured = min(run() for _ in range(3)) / (nb * ep)
    flops, byts, _ = envelope_cost(
        d, pp, qp, tw, w_max=7, lowering="reference", t_blk=CONST_T_BLK
    )
    predicted = (
        max(flops / p.peak_flops, byts / p.hbm_bw)
        + p.dispatch_s / vb
    )
    return float(min(max(predicted / max(measured, 1e-9), 0.05), 50.0))


def _footprint_bound(platform: str) -> float:
    """On-chip working-set bound for one step's transients: VMEM per
    core on TPU (~16 MB, see the Pallas guide), a cache-resident bound
    elsewhere (the reference block's dense transient should stay near
    LLC-sized or the unrolled body thrashes)."""
    return float(16 * 2**20 if platform == "tpu" else 32 * 2**20)


def calibrate(force: bool = False, persist: bool = True) -> DeviceProfile:
    """Measure this host's peaks, ACTIVATE the record, and persist it
    in the compile cache directory (when one is enabled).

    Idempotent per process: an already-active calibrated profile is
    returned as-is unless ``force``.  The probe suite costs well under a
    second warm; results are cached on disk like the AOT layer so later
    processes ``load_or_calibrate()`` in milliseconds.
    """
    import jax

    if _ACTIVE is not None and _ACTIVE.calibrated and not force:
        return _ACTIVE
    platform = jax.default_backend()
    kind = jax.devices()[0].device_kind
    p = DeviceProfile(
        name=f"calibrated-{platform}",
        platform=platform,
        device_kind=kind,
        peak_flops=_probe_peak_flops(),
        hbm_bw=_probe_hbm_bw(),
        # no interconnect probe: a TPU takes its kind's published link
        link_bw=default_profile(kind).link_bw if platform == "tpu" else 1e10,
        dispatch_s=_probe_dispatch_s(),
        compile_s=_probe_compile_s(),
        footprint_bytes=_footprint_bound(platform),
        n_devices=jax.local_device_count(),
        calibrated=True,
    )
    p = dataclasses.replace(p, fused_eff=_probe_fused_eff(p))
    set_profile(p)
    if persist:
        save_profile(p)
    return p


def load_or_calibrate() -> DeviceProfile:
    """The launcher entry point: reuse a persisted record when one
    matches this host, probe (and persist) otherwise."""
    return load_profile() or calibrate()


# --------------------------------------------------------- envelope cost
@functools.lru_cache(maxsize=256)
def analytic_volley_cost(
    d: int, p_pad: int, q_pad: int, t_window: int, w_max: int
) -> tuple[float, float]:
    """Closed-form (flops, bytes) per volley of the fused step.

    FLOPs: the one-hot plane matmuls of the kernel algebra —
    ``2 * (w_max+1) * p * q * t`` per design per volley (the documented
    MXU count every bench row reports) plus the O(p*q) WTA/STDP tail.
    Bytes: weights read+written, the volley row, and the dense
    plane/step transients the reference body materializes.
    """
    flops = d * (2.0 * (w_max + 1) * p_pad * q_pad * t_window
                 + 6.0 * p_pad * q_pad)
    byts = 4.0 * d * (
        2.0 * p_pad * q_pad      # w in + out
        + p_pad                  # volley
        + p_pad * t_window       # masked-step transient
        + q_pad * t_window       # plane-response transient
    )
    return flops, byts


def xla_volley_cost(
    d: int, p_pad: int, q_pad: int, t_window: int,
    *, w_max: int, response: str, lowering: str,
    t_blk: int, epochs: int = 1,
) -> Optional[tuple[float, float]]:
    """(flops, bytes) per volley from XLA ``cost_analysis`` of the
    ACTUAL fused-scan envelope, lowered with ``v_blk=1`` over a single
    volley (tracing one block body is cheap; the totals scale linearly
    in volleys, which the caller applies).  None when the backend cannot
    answer (older jaxlib, instrumented entry point) — callers fall back
    to the closed form."""
    import jax
    from repro.kernels import fused_column

    if not hasattr(fused_column.fit_scan_padded, "lower"):
        return None
    try:
        w = jax.ShapeDtypeStruct((d, p_pad, q_pad), "float32")
        from repro.core.types import TIME_DTYPE

        xs = jax.ShapeDtypeStruct((1, d, p_pad), TIME_DTYPE)
        vec = jax.ShapeDtypeStruct((d,), TIME_DTYPE)
        thr = jax.ShapeDtypeStruct((d,), "float32")
        mu = jax.ShapeDtypeStruct((), "float32")
        lowered = fused_column.fit_scan_padded.lower(
            w, xs, thr, vec, vec,
            mu_capture=mu, mu_backoff=mu, mu_search=mu,
            t_window=t_window, w_max=w_max, wta_k=1, stabilize=False,
            response=response, epochs=1, lowering=lowering,
            t_blk=t_blk, v_blk=1,
        )
        cost = lowered.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost.get("flops", 0.0))
        byts = float(cost.get("bytes accessed", 0.0))
        if flops <= 0.0:
            return None
        return flops, byts
    except Exception:
        return None


# in-process view of the persisted cost store: (path, mapping) — reloaded
# when the cache directory changes, merged-and-republished on new probes
_disk_costs: tuple = (None, None)


def _costs_path() -> Optional[str]:
    root_cal = calibration_path()
    if root_cal is None:
        return None
    return os.path.join(os.path.dirname(root_cal), COSTS_FILE)


def _load_disk_costs(path: str) -> dict:
    """Read the persisted envelope-cost map (empty on any mismatch —
    jaxlib upgrades change ``cost_analysis`` totals, so entries key on
    the jax version and a stale file is ignored, never wrong)."""
    import jax

    try:
        with open(path) as f:
            rec = json.load(f)
        if (rec.get("version") == COSTS_VERSION
                and rec.get("jax") == jax.__version__):
            return dict(rec.get("costs", {}))
    except (OSError, ValueError):
        pass
    return {}


def _publish_disk_costs(path: str, costs: dict) -> None:
    import jax

    merged = _load_disk_costs(path)  # merge concurrent writers' probes
    merged.update(costs)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(
                {"version": COSTS_VERSION, "jax": jax.__version__,
                 "costs": merged}, f,
            )
        os.replace(tmp, path)
    except OSError:
        pass  # persistence is an optimization, never fatal


@functools.lru_cache(maxsize=256)
def envelope_cost(
    d: int, p_pad: int, q_pad: int, t_window: int,
    *, w_max: int, response: str = "rnl", lowering: str = "reference",
    t_blk: int = CONST_T_BLK, use_xla: bool = True,
) -> tuple[float, float, str]:
    """(flops, bytes, source) per volley for one fit envelope: XLA
    ``cost_analysis`` of the real lowered program when available
    (source='xla'), the closed-form kernel algebra otherwise
    (source='analytic').  Memoized twice — in-process (one trace per
    envelope per process) and on disk next to the calibration record
    (one trace per envelope per host: the probe costs tens of ms, which
    a fresh process would otherwise re-pay inside its own cold start)."""
    global _disk_costs
    if use_xla:
        key = (f"{d}x{p_pad}x{q_pad}x{t_window}"
               f":w{w_max}:{response}:{lowering}:t{t_blk}")
        path = _costs_path()
        if path is not None and _disk_costs[0] != path:
            _disk_costs = (path, _load_disk_costs(path))
        cached = (
            _disk_costs[1].get(key)
            if path is not None and _disk_costs[0] == path else None
        )
        if cached is not None:
            return float(cached[0]), float(cached[1]), "xla"
        got = xla_volley_cost(
            d, p_pad, q_pad, t_window, w_max=w_max, response=response,
            lowering=lowering, t_blk=t_blk,
        )
        if got is not None:
            if path is not None:
                _disk_costs[1][key] = [got[0], got[1]]
                _publish_disk_costs(path, _disk_costs[1])
            return got[0], got[1], "xla"
    flops, byts = analytic_volley_cost(d, p_pad, q_pad, t_window, w_max)
    return flops, byts, "analytic"


# -------------------------------------------------------- plan + chooser
@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """One execution policy decision for a padded fused scan.

    Carries every knob the policy seams used to hard-code — the volley
    block, the kernel time block, the design-axis shard count, the
    envelope waste cap in force — plus the prediction that chose them,
    so every consumer (bench rows, DSE journal meta, serve stats) can
    record *why* the knobs are what they are.  Frozen and hashable: a
    plan rides through ``jit`` static args and memo keys untouched.

    Contract (property-tested in ``tests/test_costmodel.py``): ``1 <=
    v_blk <= n_volleys``; ``t_blk`` is lane-aligned (a positive multiple
    of 128); ``shards`` divides ``d``; ``waste_cap >= 1``.  A plan NEVER
    changes semantics — every legal plan is bit-identical to every
    other.
    """

    kind: str               # 'fit' | 'assign'
    lowering: str
    d: int
    n_volleys: int
    v_blk: int
    t_blk: int
    shards: int
    waste_cap: float
    predicted_step_s: float  # predicted warm seconds per volley
    source: str              # 'costmodel' | 'constants'
    profile: str             # profile name ('' when constants)

    def meta(self) -> dict:
        """JSON-ready metadata record (bench rows, journal, stats)."""
        return {
            "kind": self.kind,
            "lowering": self.lowering,
            "v_blk": self.v_blk,
            "t_blk": self.t_blk,
            "shards": self.shards,
            "waste_cap": self.waste_cap,
            "predicted_step_us": self.predicted_step_s * 1e6,
            "source": self.source,
            "profile": self.profile,
        }


def _const_v_blk(lowering: str, n_volleys: int, d: Optional[int]) -> int:
    """The hand-tuned fallback block policy (mirrors the documented
    history in ``backend.volley_block``)."""
    base = (
        CONST_V_BLK_REFERENCE if lowering == "reference"
        else CONST_V_BLK_KERNEL
    )
    if d is not None and lowering == "reference":
        base = min(base, max(2, 2 * int(d)))
    return max(1, min(base, int(n_volleys)))


def _const_shards(d: int) -> int:
    import jax

    n_dev = jax.local_device_count()
    k = min(int(d), n_dev)
    while k > 1 and d % k:
        k -= 1
    return max(k, 1)


def constants_plan(
    kind: str, lowering: str, d: int, n_volleys: int,
    p_pad: int = 0, q_pad: int = 0, t_window: int = 0,
) -> ExecutionPlan:
    """The documented fallback when no calibration exists: exactly the
    pre-costmodel constants, packaged as a plan so consumers see ONE
    shape either way (``source='constants'`` says which policy ran)."""
    return ExecutionPlan(
        kind=kind, lowering=lowering, d=d, n_volleys=max(int(n_volleys), 1),
        v_blk=_const_v_blk(lowering, n_volleys, d if kind == "fit" else None),
        t_blk=CONST_T_BLK,
        shards=_const_shards(d),
        waste_cap=CONST_WASTE_CAP,
        predicted_step_s=0.0,
        source="constants",
        profile="",
    )


def step_footprint_bytes(
    lowering: str, d: int, p_pad: int, q_pad: int, t_window: int,
    v_blk: int, t_blk: int,
) -> float:
    """Transient working set of ONE blocked step under a candidate
    (v_blk, t_blk).

    Reference lowering: the statically-unrolled block shares one dense
    ``[v_blk, d, p, t]`` masked-step transient plus the weight planes —
    the buffer that must stay cache-resident for the unroll to win.
    Kernel lowerings: the per-grid-step VMEM residency — weight planes,
    the volley block, and one (q x t_blk) + (p x t_blk) response tile.
    """
    if lowering == "reference":
        return 4.0 * (
            v_blk * d * p_pad * t_window     # masked-step transient
            + 2.0 * d * p_pad * q_pad        # weights in/out
            + v_blk * d * q_pad * t_window / max(t_window, 1)  # winners
        )
    t_eff = min(t_blk, max(t_window, 1))
    return 4.0 * (
        2.0 * p_pad * q_pad                    # w + its plane decomposition
        + v_blk * p_pad                        # volley block (SMEM-ish)
        + (p_pad + q_pad) * t_eff              # response tiles
    )


def _candidate_v_blks(lowering: str, n_volleys: int) -> list[int]:
    """Volley-block candidates: powers of two from 2 up to the
    lowering's constants base (8 reference / 32 kernel), clamped to the
    stream.

    Never 1 unless the stream itself is — a block of 1 forfeits all
    per-step amortization for nothing (measured ~7% warm loss on the
    tracked sweep geometry), so the model doesn't get to pick it.  Never
    above the constants base either: the measured warm cliff past the
    base (the unrolled reference body regresses beyond ~8 on the bench
    hosts) is a code-size effect the roofline cannot see, so the
    hand-tuned cap stays the upper bound and the model arbitrates below
    it.
    """
    cap = CONST_V_BLK_REFERENCE if lowering == "reference" else CONST_V_BLK_KERNEL
    out = []
    v = 2
    while v <= min(n_volleys, cap):
        out.append(v)
        v *= 2
    if not out:
        out.append(max(1, min(int(n_volleys), cap)))
    return out


def _candidate_t_blks(lowering: str, t_window: int) -> list[int]:
    if lowering == "reference":
        # the reference body has no time blocking — t_blk is carried for
        # key/plan symmetry only, pinned at the lane-aligned default
        return [CONST_T_BLK]
    # kernel lowerings tile time in lane-aligned blocks; offering one
    # larger block lets big windows trade grid steps for VMEM
    cands = [CONST_T_BLK]
    if t_window > CONST_T_BLK:
        cands.append(2 * CONST_T_BLK)
    return cands


def _divisor_shards(d: int, n_dev: int) -> list[int]:
    return [k for k in range(1, min(d, n_dev) + 1) if d % k == 0]


# Two candidates whose predicted warm times differ by less than this
# are a tie — the prediction's resolution, not a real difference (the
# measured warm spread across v_blk 2..8 on the tracked geometry is ~1%).
WARM_TIE_TOL = 0.05


def trace_unroll(kind: str, lowering: str, d: int, v_blk: int) -> float:
    """Relative trace/compile cost proxy of a candidate: the reference
    fit block statically unrolls ``v_blk * d`` copies of the fused body
    into ONE XLA computation (compile time measured ~linear in that
    count), while kernel lowerings fold the block in an in-kernel
    ``fori_loop`` and the assignment fire is one vmapped body — both
    trace a single copy regardless of block size."""
    if kind == "fit" and lowering == "reference":
        return float(v_blk * d)
    return 1.0


def predict_step_s(
    prof: DeviceProfile,
    kind: str,
    lowering: str,
    d: int, p_pad: int, q_pad: int, t_window: int,
    n_volleys: int, epochs: int,
    v_blk: int, t_blk: int, shards: int,
    *, w_max: int = 7, response: str = "rnl",
) -> float:
    """Predicted WARM seconds per volley under a candidate plan.

    Two terms, both per volley:

      max(flops/peak, bytes/bw) / shards     the sharded roofline bound
      + dispatch_s * shards / v_blk          per-step overhead, amortized
                                             over the block, paid per
                                             participating device

    Warm time is THE objective: under the persistent AOT cache
    (``backend.compile_cache``) trace+compile is a once-ever cost, so it
    never belongs in the per-volley prediction — it enters the chooser
    only as the tie-breaker between warm-equivalent candidates (see
    ``trace_unroll`` / ``WARM_TIE_TOL``), which is exactly how the
    hand-tuned constants treated it (v_blk capped for compile growth,
    not warm loss).
    """
    flops, byts, _ = envelope_cost(
        d, p_pad, q_pad, t_window, w_max=w_max, response=response,
        lowering=lowering, t_blk=t_blk,
    )
    roofline_s = max(flops / prof.peak_flops, byts / prof.hbm_bw)
    roofline_s /= max(prof.fused_eff, 1e-6)
    step_s = roofline_s / max(shards, 1)
    step_s += prof.dispatch_s * shards / max(v_blk, 1)
    return step_s


@functools.lru_cache(maxsize=512)
def _choose_plan_cached(
    prof: DeviceProfile,
    kind: str, lowering: str,
    d: int, p_pad: int, q_pad: int, t_window: int,
    n_volleys: int, epochs: int, w_max: int, response: str,
) -> ExecutionPlan:
    import jax

    n_dev = jax.local_device_count()
    cands = []
    for t_blk in _candidate_t_blks(lowering, t_window):
        for v_blk in _candidate_v_blks(lowering, n_volleys):
            admissible = (
                step_footprint_bytes(
                    lowering, d, p_pad, q_pad, t_window, v_blk, t_blk
                ) <= prof.footprint_bytes
            )
            for shards in _divisor_shards(d, n_dev):
                s = predict_step_s(
                    prof, kind, lowering, d, p_pad, q_pad, t_window,
                    n_volleys, epochs, v_blk, t_blk, shards,
                    w_max=w_max, response=response,
                )
                cands.append((admissible, s, v_blk, t_blk, shards))
    # footprint bound first (an inadmissible candidate survives only if
    # nothing fits — then the smallest-footprint one, i.e. the smallest
    # block, limps through); within the admissible set, minimize warm
    # time, then break warm ties (within WARM_TIE_TOL — prediction
    # resolution) toward the cheapest trace, the largest block (launch
    # amortization beyond the model), the default tile, fewest shards.
    if any(a for (a, *_rest) in cands):
        cands = [c for c in cands if c[0]]
    best_s = min(s for (_a, s, *_rest) in cands)
    ties = [c for c in cands if c[1] <= best_s * (1.0 + WARM_TIE_TOL)]
    _a, s, v_blk, t_blk, shards = min(
        ties,
        key=lambda c: (
            trace_unroll(kind, lowering, d, c[2]), -c[2], c[3], c[4]
        ),
    )
    return ExecutionPlan(
        kind=kind, lowering=lowering, d=d, n_volleys=n_volleys,
        v_blk=v_blk, t_blk=t_blk, shards=shards,
        waste_cap=choose_waste_cap(prof, d, p_pad, q_pad, t_window,
                                   n_volleys, epochs, w_max=w_max),
        predicted_step_s=s, source="costmodel", profile=prof.name,
    )


def choose_plan(
    kind: str,
    lowering: str,
    d: int,
    p_pad: int,
    q_pad: int,
    t_window: int,
    n_volleys: int,
    epochs: int = 1,
    *,
    w_max: int = 7,
    response: str = "rnl",
    prof: Optional[DeviceProfile] = None,
) -> ExecutionPlan:
    """The policy front door: an ``ExecutionPlan`` for one padded scan.

    With an active (or explicitly passed) profile, candidates are
    enumerated and the predicted-fastest admissible one wins
    (``source='costmodel'``); with none, the hand-tuned constants are
    returned unchanged (``source='constants'``) — the documented
    fallback, so un-calibrated hosts behave exactly as before this
    module existed.  Deterministic for fixed inputs: a warmed executable
    key and a traffic-time key always agree.
    """
    prof = prof if prof is not None else profile()
    n_volleys = max(int(n_volleys), 1)
    if prof is None:
        return constants_plan(
            kind, lowering, d, n_volleys, p_pad, q_pad, t_window
        )
    return _choose_plan_cached(
        prof, kind, lowering, int(d), int(p_pad), int(q_pad),
        int(t_window), n_volleys, int(max(epochs, 1)), int(w_max),
        response,
    )


def choose_waste_cap(
    prof: Optional[DeviceProfile] = None,
    d: int = 1, p_pad: int = 1, q_pad: int = 1, t_window: int = 1,
    n_volleys: int = 0, epochs: int = 1, *, w_max: int = 7,
) -> float:
    """Envelope waste cap from the roofline: padding waste recurs on
    every volley (cost ~ cap * per-volley envelope seconds * total
    volleys), while sharing an envelope saves ONE trace+compile.  The
    cap where the two break even is ``1 + compile_s / (volley_s *
    total_volleys)``, clamped to [1.5, 8] so degenerate inputs (empty
    streams, enormous envelopes) stay sane.  Falls back to the
    hand-tuned 4.0 without a profile or stream length."""
    prof = prof if prof is not None else profile()
    total = max(int(n_volleys), 0) * max(int(epochs), 1)
    if prof is None or total <= 0:
        return CONST_WASTE_CAP
    flops, byts, _ = envelope_cost(
        max(d, 1), max(p_pad, 1), max(q_pad, 1), max(t_window, 1),
        w_max=w_max, use_xla=False,
    )
    volley_s = max(
        flops / prof.peak_flops, byts / prof.hbm_bw, 1e-12
    ) / max(prof.fused_eff, 1e-6)
    cap = 1.0 + prof.compile_s / (volley_s * total)
    return float(min(max(cap, 1.5), 8.0))


def choose_shards(d: int, volume: Optional[float] = None) -> int:
    """Design-axis shard count.  Without a profile (or a compute-volume
    hint), the classic largest-divisor policy; with both, shard only
    while the per-volley compute saved exceeds the added per-device
    dispatch — tiny buckets stay unsharded instead of paying k dispatches
    to split microseconds of work."""
    prof = profile()
    base = _const_shards(d)
    if prof is None or volume is None:
        return base
    import jax

    n_dev = jax.local_device_count()
    vol_s = 2.0 * float(volume) / prof.peak_flops
    best, best_s = 1, math.inf
    for k in _divisor_shards(d, n_dev):
        s = vol_s / k + prof.dispatch_s * (k - 1)
        if s < best_s:
            best, best_s = k, s
    return best


def plan_is_valid(plan: ExecutionPlan) -> bool:
    """The plan contract, as one predicate (property-tested): clamped
    volley block, lane-aligned time block, shard count dividing the
    design axis, sane waste cap."""
    return (
        1 <= plan.v_blk <= max(plan.n_volleys, 1)
        and plan.t_blk > 0
        and plan.t_blk % LANE == 0
        and plan.shards >= 1
        and plan.d % plan.shards == 0
        and plan.waste_cap >= 1.0
    )


def main(argv=None) -> int:
    """CLI: calibrate this host and persist the record in the compile
    cache directory (``backend.compile_cache()``)."""
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--force", action="store_true",
        help="re-probe even if a persisted record matches this host",
    )
    args = ap.parse_args(argv)
    from repro.core import backend as backend_lib

    backend_lib.compile_cache()
    p = load_profile() if not args.force else None
    if p is None:
        p = calibrate(force=args.force)
    path = calibration_path()
    print(
        f"profile {p.name}: peak={p.peak_flops / 1e9:.1f} GF/s "
        f"bw={p.hbm_bw / 1e9:.1f} GB/s dispatch={p.dispatch_s * 1e6:.1f} us "
        f"compile={p.compile_s * 1e3:.1f} ms "
        f"({'calibrated' if p.calibrated else 'default'}; "
        f"persisted at {path or 'nowhere — no compile cache enabled'})"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
