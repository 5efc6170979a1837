"""Shared helpers of the benchmark's tests: a tiny copy of the benchmark.

``tiny_checkout(dst)`` copies ``bench/`` and ``BENCHMARK.json`` into
``dst`` and adds, as new files and entries only, a tiny configuration
(two small designs on short streams) and one tiny cell per traffic kind,
listed in every metric of that kind.  The copy's ``bench/run.py`` then
runs them as it runs the real cells.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

for path in (BENCH, os.path.join(REPO, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)



def _limits(traffic: str) -> dict:
    """The limits of a real cell, which the tiny cells are held to."""
    with open(os.path.join(BENCH, "traffic", f"{traffic}.json")) as f:
        return json.load(f)["limits"]


SWEEP_LIMITS = _limits("ws-sweep")
SERVE_LIMITS = _limits("fleet-serve")


def _design(name, p, q, n, classes, modality, t_max=16):
    return {"name": name, "p": p, "q": q, "t_max": t_max,
            "stream": {"length": p, "classes": classes, "n": n, "modality": modality}}


def tiny_config() -> dict:
    with open(os.path.join(BENCH, "configs", "table2-fleet.json")) as f:
        base = json.load(f)
    return dict(base, name="tiny", designs=[
        _design("SonyAIBORobotSurface2", 65, 2, 16, 2, "accelerometer"),
        _design("Beef", 47, 3, 16, 2, "spectrograph"),
    ])


TINY_TRAFFIC = {
    "tiny-sweep": {"kind": "sweep", "q": None, "t_max": [16],
                   "threshold_scales": {"start": 1.0, "stop": 1.25, "num": 2},
                   "epochs": 1, "check_designs": 3, "limits": SWEEP_LIMITS},
    "tiny-serve": {"kind": "serve", "rate_per_s": 40, "zipf_s": 1.0,
                   "popularity": ["SonyAIBORobotSurface2", "Beef"], "batch_size": 4,
                   "refit_every": 8, "refit_window": 4, "refit_epochs": 1,
                   "check_requests": 16, "limits": SERVE_LIMITS},
}


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def tiny_checkout(dst: str) -> str:
    """A copy of the benchmark with the tiny cells added; returns its root."""
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    _write(os.path.join(dst, "bench", "configs", "tiny.json"), tiny_config())
    spec["configs"].append({"name": "tiny", "source": "https://arxiv.org/abs/2412.17977",
                            "file": "bench/configs/tiny.json", "reduced": [], "why": "tests"})
    for cell, traffic in TINY_TRAFFIC.items():
        _write(os.path.join(dst, "bench", "traffic", f"{cell}.json"), traffic)
        spec["workloads"].append({"name": cell, "config": "tiny", "traffic": cell,
                                  "chips": 1, "why": "tests"})
        kind = traffic["kind"]
        for m in spec["end_to_end"] + spec["per_layer"]:
            cells = m.get("workloads")
            if cells and any(traffic_kind(spec, c) == kind for c in cells):
                cells.append(cell)
    _write(os.path.join(dst, "BENCHMARK.json"), spec)
    return dst


def traffic_kind(spec: dict, cell: str) -> str:
    for w in spec["workloads"]:
        if w["name"] == cell:
            path = os.path.join(BENCH, "traffic", f"{w['traffic']}.json")
            if os.path.exists(path):
                with open(path) as f:
                    return json.load(f)["kind"]
            return TINY_TRAFFIC[w["traffic"]]["kind"]
    raise KeyError(cell)


def load_module(stem: str, name: str, bench: str = BENCH):
    """``bench/<stem>.py`` loaded as module ``name``."""
    spec = importlib.util.spec_from_file_location(name, os.path.join(bench, f"{stem}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_run(root: str):
    """The ``run`` module of the checkout at ``root``."""
    return load_module("run", "bench_run_" + str(abs(hash(root))), os.path.join(root, "bench"))


def chip_trace(kind: str) -> str:
    """The recorded chip trace of a tiny run of ``kind``."""
    return os.path.join(DATA, f"{kind}.xplane.pb.gz")
