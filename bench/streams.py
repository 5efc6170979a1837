"""Seeded time-series streams: synthetic doubles of the UCR datasets.

The real UCR archive is not available offline, so every cell clusters a
synthetic double of its dataset: the dataset's series length, class
count and size, and a waveform family that fits its modality.  This is a
copy of the generator the program ships (``repro.data.ucr``), kept here
so that the benchmark's inputs cannot change with the program.

A stream's ``meta`` is the ``stream`` entry of a design in a
configuration file: ``length``, ``classes``, ``n`` and ``modality``.
"""
from __future__ import annotations

import zlib

import numpy as np


def _class_prototype(rng: np.random.Generator, L: int, modality: str) -> np.ndarray:
    """Modality-flavoured smooth prototype waveform."""
    t = np.linspace(0, 1, L)
    if modality in ("accelerometer", "motion"):
        proto = np.zeros(L)
        for _ in range(3):
            c, wdt, amp = rng.uniform(0.1, 0.9), rng.uniform(0.03, 0.15), rng.normal(0, 2)
            proto += amp * np.exp(-0.5 * ((t - c) / wdt) ** 2)
        proto += rng.normal(0, 0.5) * t
    elif modality == "ecg":
        proto = np.zeros(L)
        spike_pos = rng.uniform(0.2, 0.8)
        proto += rng.uniform(2, 4) * np.exp(-0.5 * ((t - spike_pos) / 0.02) ** 2)
        proto -= rng.uniform(0.5, 1.5) * np.exp(-0.5 * ((t - spike_pos - 0.05) / 0.03) ** 2)
        proto += 0.3 * np.sin(2 * np.pi * rng.integers(1, 4) * t)
    elif modality in ("fabrication", "spectrograph"):
        proto = np.cumsum(rng.normal(0, 0.15, L))
        for _ in range(2):
            a, b = sorted(rng.uniform(0, 1, 2))
            proto += rng.normal(0, 1.5) * ((t > a) & (t < b))
    elif modality == "optical_rf":
        proto = rng.uniform(0.5, 2) * np.sin(
            2 * np.pi * rng.uniform(2, 8) * t + rng.uniform(0, 2 * np.pi)
        ) * np.exp(-rng.uniform(0, 3) * t)
    else:  # word_outline and default: band-limited random shapes
        proto = np.zeros(L)
        for k in range(1, 6):
            proto += rng.normal(0, 1.0 / k) * np.sin(2 * np.pi * k * t + rng.uniform(0, 6.28))
    return proto


def synthetic(name: str, meta: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(series [N, L] float64, labels [N]) of the double of ``name``.

    ``N`` is ``max(n // classes, 8) * classes``; the same name, meta and
    seed give the same stream on every host.
    """
    rng = np.random.default_rng((zlib.crc32(name.encode()) + int(seed)) % 2**32)
    L, k, n = int(meta["length"]), int(meta["classes"]), int(meta["n"])
    modality = meta["modality"]
    background = _class_prototype(rng, L, modality) * 1.5
    protos = [_class_prototype(rng, L, modality) for _ in range(k)]
    xs, ys = [], []
    per = max(n // k, 8)
    for c in range(k):
        warp = rng.uniform(0.9, 1.1, size=per)
        shift = rng.integers(-L // 20 - 1, L // 20 + 1, size=per)
        for i in range(per):
            tt = np.clip(np.linspace(0, 1, L) * warp[i], 0, 1)
            base = background + np.interp(tt, np.linspace(0, 1, L), protos[c])
            base = np.roll(base, int(shift[i]))
            xs.append(base * rng.uniform(0.7, 1.3) + rng.normal(0, 0.6, L))
            ys.append(c)
    x = np.stack(xs)
    y = np.asarray(ys, np.int64)
    perm = rng.permutation(len(y))
    return x[perm], y[perm]
