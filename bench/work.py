"""Model work of the TNN column algorithm, computed from design shapes.

The counts are the algorithm's, not any implementation's: they use each
design's own (unpadded) p, q and t_max, never a padded tile, a time block
or the number of weight planes a kernel issues.

* RNL fire: each of the p x q synapses adds its ramp response, clipped at
  its weight (a min and an add), on each of the t_max cycles of the
  window: 2 * p * q * t_max operations per design per volley.
* Expected STDP: one update per synapse per training volley: p * q more.

Bytes are the least traffic one call needs: every spike time read once
(one byte: times are below 256) and every weight read once and, for a
fit, written once (four bytes: the stabilised STDP state is float32).
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
TIME_BYTES = 1
WEIGHT_BYTES = 4


def fire_ops(p: int, q: int, t_max: int) -> int:
    """Operations of one volley's RNL fire on one design."""
    return 2 * p * q * t_max


def stdp_ops(p: int, q: int) -> int:
    """Operations of one volley's STDP update on one design."""
    return p * q


def fit_ops(p: int, q: int, t_max: int, volleys: int) -> int:
    """Operations of ``volleys`` training volleys (fire + STDP)."""
    return volleys * (fire_ops(p, q, t_max) + stdp_ops(p, q))


def assign_ops(p: int, q: int, t_max: int, volleys: int) -> int:
    """Operations of assigning ``volleys`` volleys (fire only)."""
    return volleys * fire_ops(p, q, t_max)


def fit_bytes(p: int, q: int, stream: int) -> int:
    """Least bytes of one fit over a stream of ``stream`` distinct volleys:
    the stream read once, the weights read once and written once."""
    return stream * p * TIME_BYTES + 2 * p * q * WEIGHT_BYTES


def assign_bytes(p: int, q: int, volleys: int) -> int:
    """Least bytes of one assign call: volleys and weights read once, one
    id written per volley."""
    return volleys * (p * TIME_BYTES + 4) + p * q * WEIGHT_BYTES


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``; an unknown device raises."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {path} "
            f"(known: {sorted(table)})"
        )
    return table[device_kind]


def roofline_pct(ops: float, nbytes: float, seconds: float, peak: dict):
    """Share (%) of the roofline a program reached, and its bound.

    The least time is the larger of ops over peak FLOP/s and bytes over
    peak bandwidth; the share is that least time over the measured
    device time.  Returns ``(None, bound)`` when nothing was timed.
    """
    t_ops = ops / peak["bf16_flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_mem else "memory"
    if seconds <= 0 or ops <= 0:
        return None, bound
    return 100.0 * max(t_ops, t_mem) / seconds, bound
