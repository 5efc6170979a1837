"""Three-term roofline analysis over the dry-run artifacts (§Roofline).

Hardware model: the peaks come from a ``costmodel.DeviceProfile`` — the
*active* device calibration when one is loaded (``costmodel.calibrate`` /
``load_profile``), otherwise the default profile of the device kind in
question (``costmodel.PROFILES``, keyed by ``device_kind``; an unknown
kind is an error).  The dry-run artifacts target a TPU v5e:
    PEAK_FLOPS = 197e12   bf16 FLOP/s
    HBM_BW     = 819e9    B/s
    LINK_BW    = 50e9     B/s per ICI link

Terms, in seconds per step (all quantities are PER DEVICE — XLA's
``cost_analysis`` reports the per-device SPMD module, and the collective
parser counts per-device wire bytes; dividing global totals by chip count
is algebraically identical for balanced SPMD):

    compute_s    = HLO_FLOPs / PEAK_FLOPS
    memory_s     = HLO_bytes / HBM_BW
    collective_s = collective_bytes / LINK_BW

Where the dry-run recorded extrapolated (unrolled 1/2-layer) costs, those
are used — the scanned compile undercounts loop bodies (see launch/hlo.py).

MODEL_FLOPS = 6 * N(_active) * tokens for training (2N fwd + 4N bwd)
and 2 * N(_active) * tokens for inference;
``useful_ratio`` = MODEL_FLOPS / HLO_FLOPS_global catches remat/redundancy
waste.  ``roofline_fraction`` = useful compute time / dominant term — the
MFU the step would achieve if it ran exactly at the binding roofline.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
from typing import Optional

from repro.roofline import costmodel

# The device the dry-run artifacts are compiled for; the module constants
# are its peaks.  Analysis resolves peaks per call through peaks(), which
# prefers the active device calibration.
DRYRUN_DEVICE_KIND = costmodel.TPU_V5E
_DRYRUN_PROFILE = costmodel.default_profile(DRYRUN_DEVICE_KIND)
PEAK_FLOPS = _DRYRUN_PROFILE.peak_flops
HBM_BW = _DRYRUN_PROFILE.hbm_bw
LINK_BW = _DRYRUN_PROFILE.link_bw

RESULTS_DIR = "results/dryrun"


def peaks(
    prof: Optional[costmodel.DeviceProfile] = None,
    device_kind: Optional[str] = None,
) -> tuple[float, float, float, str]:
    """``(peak_flops, hbm_bw, link_bw, profile_name)`` for the analysis.

    Resolution order: explicit ``prof`` argument, then the active
    calibration (``costmodel.profile()``), then the default profile of
    ``device_kind`` (default: the device this process runs on, as
    ``jax.devices()[0].device_kind`` reports it).  A device kind with no
    profile raises ``KeyError``.  A calibrated single-host profile has no
    measured interconnect, so a zero/unset ``link_bw`` takes the default
    profile's interconnect for the same device kind.
    """
    p = prof if prof is not None else costmodel.profile()
    if p is None:
        if device_kind is None:
            import jax

            device_kind = jax.devices()[0].device_kind
        p = costmodel.default_profile(device_kind)
    link = p.link_bw
    if not link or link <= 0:
        link = costmodel.default_profile(p.device_kind).link_bw
    return p.peak_flops, p.hbm_bw, link, p.name


@dataclasses.dataclass
class RooflineRow:
    arch: str
    shape: str
    mesh: str
    status: str
    n_chips: int = 0
    compute_s: float = 0.0
    memory_s: float = 0.0
    memory_min_s: float = 0.0  # fused lower bound (see analyze_cell)
    collective_s: float = 0.0
    dominant: str = ""
    model_flops: float = 0.0
    hlo_flops_global: float = 0.0
    useful_ratio: float = 0.0
    roofline_fraction: float = 0.0
    fraction_fused: float = 0.0
    peak_mem_gb: float = 0.0
    note: str = ""
    # which DeviceProfile supplied the peaks ('tpu-v5e' default, or the
    # host's calibration when one is active)
    profile: str = ""

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def bound_fused_s(self) -> float:
        return max(self.compute_s, self.memory_min_s, self.collective_s)


def load_cells(results_dir: str = RESULTS_DIR) -> list:
    cells = []
    for path in sorted(glob.glob(os.path.join(results_dir, "*.json"))):
        with open(path) as f:
            cells.append(json.load(f))
    return cells


def analyze_cell(cell: dict) -> RooflineRow:
    if cell.get("status") != "ok":
        return RooflineRow(
            arch=cell["arch"], shape=cell["shape"], mesh=cell.get("mesh", "?"),
            status=cell.get("status", "?"), note=cell.get("reason", ""),
        )
    ex = cell.get("extrapolated") or {}
    cost = cell.get("cost_analysis") or {}
    flops = ex.get("flops", cost.get("flops", 0.0))
    byts = ex.get("bytes accessed", cost.get("bytes accessed", 0.0))
    coll = (ex.get("collective_bytes") or cell.get("collective_bytes", {})).get(
        "total", 0.0
    )
    n = cell["n_chips"]
    peak_flops, hbm_bw, link_bw, prof_name = peaks(
        device_kind=DRYRUN_DEVICE_KIND
    )
    compute_s = flops / peak_flops
    memory_s = byts / hbm_bw
    collective_s = coll / link_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    # MODEL_FLOPS (standard convention): training = 6*N_active per token
    # (2N fwd + 4N bwd); inference (prefill/decode) = 2*N_active per token.
    from repro.configs import SHAPES

    sh = SHAPES[cell["shape"]]
    n_active = cell.get("active_params", 0)
    if sh.kind == "train":
        model_flops = 6.0 * n_active * sh.global_batch * sh.seq_len
    elif sh.kind == "prefill":
        model_flops = 2.0 * n_active * sh.global_batch * sh.seq_len
    else:
        model_flops = 2.0 * n_active * sh.global_batch
    hlo_global = flops * n
    useful = model_flops / hlo_global if hlo_global else 0.0
    useful_time = model_flops / (n * peak_flops)
    bound = max(terms.values())
    frac = useful_time / bound if bound else 0.0
    mem = cell.get("memory_analysis", {})
    peak = (
        mem.get("argument_size_in_bytes", 0)
        + mem.get("temp_size_in_bytes", 0)
        + mem.get("output_size_in_bytes", 0)
        - mem.get("alias_size_in_bytes", 0)
    )
    # Fused lower bound on HBM traffic: the HLO 'bytes accessed' proxy
    # reflects this backend's (CPU) fusion decisions and overcounts what a
    # fused TPU program moves.  Minimum = read every argument + write every
    # output once + layer-boundary activation traffic (saved fwd / read
    # bwd / written grads for train; streamed once for serve).
    from repro.configs import get_arch

    try:
        cfg = get_arch(cell["arch"])
        dp = n // 16  # model axis is 16 on both meshes
        tokens_local = sh.global_batch * (
            sh.seq_len if sh.kind != "decode" else 1
        ) / max(dp, 1)
        bound_factor = 3.0 if sh.kind == "train" else 1.0
        boundary = bound_factor * cfg.n_layers * tokens_local * cfg.d_model * 2
    except Exception:
        boundary = 0.0
    min_bytes = (
        mem.get("argument_size_in_bytes", 0)
        + mem.get("output_size_in_bytes", 0)
        + boundary
    )
    memory_min_s = min_bytes / hbm_bw
    bound_fused = max(compute_s, memory_min_s, collective_s)
    frac_fused = useful_time / bound_fused if bound_fused else 0.0
    return RooflineRow(
        arch=cell["arch"], shape=cell["shape"], mesh=cell["mesh"], status="ok",
        n_chips=n, compute_s=compute_s, memory_s=memory_s,
        memory_min_s=memory_min_s,
        collective_s=collective_s, dominant=dominant,
        model_flops=model_flops, hlo_flops_global=hlo_global,
        useful_ratio=useful, roofline_fraction=frac,
        fraction_fused=frac_fused,
        peak_mem_gb=peak / 1e9,
        profile=prof_name,
    )


def analyze_all(results_dir: str = RESULTS_DIR, mesh: Optional[str] = "single") -> list:
    rows = [analyze_cell(c) for c in load_cells(results_dir)]
    if mesh:
        rows = [r for r in rows if r.mesh == mesh or r.status != "ok"]
    return rows


def render_markdown(rows: list) -> str:
    hdr = (
        "| arch | shape | chips | compute_s | memory_s (hlo/min) | "
        "collective_s | dominant | MODEL/HLO | frac (hlo/fused) | "
        "peak GB/dev |\n"
        "|---|---|---|---|---|---|---|---|---|---|\n"
    )
    lines = []
    for r in sorted(rows, key=lambda r: (r.arch, r.shape)):
        if r.status == "skipped":
            lines.append(
                f"| {r.arch} | {r.shape} | — | — | — | — | skipped | — | — | — |"
            )
            continue
        lines.append(
            f"| {r.arch} | {r.shape} | {r.n_chips} | {r.compute_s:.4f} | "
            f"{r.memory_s:.3f} / {r.memory_min_s:.3f} | {r.collective_s:.4f} | "
            f"**{r.dominant}** | {r.useful_ratio:.3f} | "
            f"{r.roofline_fraction:.3f} / {r.fraction_fused:.3f} | "
            f"{r.peak_mem_gb:.1f} |"
        )
    return hdr + "\n".join(lines) + "\n"


def pick_hillclimb_cells(rows: list) -> dict:
    """The three §Perf cells: worst roofline fraction, most collective-bound,
    most representative of the paper's technique (the TNN pillar is separate;
    for the LM pillar we take the largest-scale MoE cell — the arch whose
    silicon-cost-forecasting analogue the paper motivates)."""
    ok = [r for r in rows if r.status == "ok"]
    worst = min(ok, key=lambda r: r.roofline_fraction)
    coll = max(ok, key=lambda r: (r.collective_s / max(r.bound_s, 1e-12)))
    moe = [r for r in ok if r.arch.startswith("kimi")] or ok
    rep = max(moe, key=lambda r: r.model_flops)
    return {"worst_fraction": worst, "most_collective_bound": coll,
            "paper_representative": rep}
