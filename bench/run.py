#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the checkout's
root: the cell (``workloads``) names a configuration (its ``file``) and a
traffic mix (``bench/traffic/<traffic>.json``), whose ``kind`` names the
generator that drives it (``bench/kinds/<kind>.py``); each per-layer
metric is read by ``bench/metrics/<metric>.py``.  A new cell of an
existing kind, a new configuration or a new per-layer metric is a new
file and a new entry, never an edit here.

A run: imports and data from the seed, a warm-up of the cell's own shapes
(set-up, ``setup_s``), then a window of ``--seconds`` with nothing left to
compile, then the check of what the window produced against the plain
reference (``bench/reference.py``).  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` traces the window with the JAX profiler
and reports the per-layer metrics, the device's busy time and the
breakdown.  The last lines on stderr give each number compared beside its
limit; the last line on stdout is the result, one JSON object.  Without an
accelerator, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.

JAX's persistent compilation cache lives in the checkout's ``.jax_cache``,
so only a checkout's first run of a cell compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, "bench_out", "trace")


class NoChip(RuntimeError):
    """JAX found no accelerator of the expected platform, or too few."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Spec:
    """``BENCHMARK.json`` and the files it names."""

    def __init__(self, root: str = ROOT, bench: str = BENCH):
        self.root = root
        self.bench = bench
        self.data = load_json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return load_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return load_json(os.path.join(self.bench, "traffic", f"{name}.json"))

    def kind(self, name: str):
        return load_module(os.path.join(self.bench, "kinds", f"{name}.py"), f"kinds.{name}")

    def metrics(self, section: str, cell: str) -> list:
        """The section's metrics that the cell reports."""
        return [m for m in self.data[section] if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        path = os.path.join(self.bench, "metrics", f"{metric}.py")
        return load_module(path, "bench_metric_" + metric.replace(".", "_").replace("-", "_"))


def devices(platform: str, chips: int):
    """The first ``chips`` devices, which must be of ``platform``."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from e
    if devs[0].platform != platform:
        raise NoChip(f"expected a {platform} device, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def program(spec: Spec) -> None:
    """Make the program importable and its compile cache persistent: the
    checkout's ``.jax_cache`` (or ``JAX_COMPILATION_CACHE_DIR``), with no
    size limit, so no entry is ever evicted."""
    import jax

    jax.config.update("jax_compilation_cache_max_size", -1)
    src = os.path.join(spec.root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.core import backend

    backend.compile_cache()


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks))


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             platform: str = "tpu", spec: Spec | None = None,
             t_start: float = T_START) -> dict:
    """Run one cell once; returns the result object (the last stdout line)
    with the numbers compared under ``check``."""
    spec = spec or Spec()
    cell = spec.cell(workload)
    config = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    if spec.bench not in sys.path:
        sys.path.insert(0, spec.bench)
    kind = spec.kind(traffic["kind"])

    import jax

    devs = devices(platform, int(cell["chips"]))
    program(spec)
    from repro.testing import count_compiles

    import work

    runner = kind.Cell(config, traffic, seed, int(cell["chips"]))
    runner.setup()
    setup_s = time.perf_counter() - t_start

    trace_dir = os.path.join(TRACE_DIR, workload)
    with count_compiles() as compiles:
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1  # the harness's spans, not the runtime's
            with jax.profiler.trace(trace_dir, profiler_options=opts):
                with jax.profiler.TraceAnnotation("bench.window"):
                    runner.window(seconds)
        else:
            runner.window(seconds)
    device = {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs), "memory_peak_bytes": memory_peak(devs),
    }
    e2e = runner.e2e()
    if hasattr(runner, "lateness"):
        print(runner.lateness(), file=sys.stderr, flush=True)
    runner.release()
    limits = traffic["limits"]
    checks = {name: {"value": value, "limit": limits[name]} for name, value in runner.check()}

    metrics = {}
    result = {}
    if trace:
        summary = load_module(os.path.join(spec.bench, "trace.py"), "bench_trace").reduce(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = dict(runner.layer_context(), trace=summary, compiles=compiles.compiles,
                   peak=work.peaks(devs[0].device_kind))
        for m in spec.metrics("per_layer", workload):
            value = spec.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = summary.breakdown()
    else:
        e2e["setup_s"] = setup_s
        for m in spec.metrics("end_to_end", workload):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return {
        "correct": correct, "attempted": runner.attempted, "failed": runner.failed,
        "metrics": metrics, "device": device, **result, "check": checks,
    }


def main(argv=None, *, platform: str = "tpu") -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                          platform=platform)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["check"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
