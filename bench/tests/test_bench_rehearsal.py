"""``bench/run.py`` rehearsed on the host at a tiny size.

The fused paths are steered to the Pallas interpreter, as they lower to
Mosaic on a TPU, and each traffic kind runs a tiny cell through the same
loop, check and result line as on the chip.  A traced run reads a trace
recorded on the chip in place of the host's (which has no device plane).
``main`` itself refuses to run off a TPU.
"""
import contextlib
import json
import os
import shutil

import pytest

import benchkit
from repro.core import backend

CONTRACT = ["correct", "attempted", "failed", "metrics", "device"]
THROWAWAY = {"name": "throwaway_requests.serve", "unit": "requests", "better": "higher",
             "source": "program_counter", "layer": "front end", "moves": "serve_p50_ms",
             "workloads": ["tiny-serve"]}


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(backend, "pallas_lowering", lambda: "interpret")
    monkeypatch.setattr(backend, "compile_cache", lambda *a, **k: None)


@pytest.fixture
def checkout(tmp_path):
    """The tiny checkout plus a throwaway per-layer metric: new files and
    new entries only."""
    root = benchkit.tiny_checkout(str(tmp_path))
    with open(os.path.join(root, "bench", "metrics", f"{THROWAWAY['name']}.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx['requests'])\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["per_layer"].append(THROWAWAY)
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


@pytest.fixture
def chip_profiler(monkeypatch):
    """``jax.profiler.trace`` that leaves a recorded chip trace of the
    cell's kind where the harness reads it."""
    import jax

    kind = {}

    @contextlib.contextmanager
    def trace(log_dir, **kw):
        yield
        os.makedirs(log_dir, exist_ok=True)
        shutil.copy(benchkit.chip_trace(kind["name"]), os.path.join(log_dir, "t.xplane.pb.gz"))

    monkeypatch.setattr(jax.profiler, "trace", trace)
    # the recorded trace is a v5e's: read its peaks for the host's device
    import work

    v5e = work.peaks("TPU v5 lite")
    monkeypatch.setattr(work, "peaks", lambda device_kind, path=None: v5e)
    return kind


def _main(run, argv, capsys):
    rc = run.main(argv, platform="cpu")
    out, err = capsys.readouterr()
    return rc, out.strip().splitlines(), err.strip().splitlines()


@pytest.mark.parametrize("cell", ["tiny-sweep", "tiny-serve"])
def test_untraced_cell_prints_the_contract_line(cell, checkout, interpret, capsys, monkeypatch):
    run = benchkit.load_run(checkout)
    cache = os.path.join(checkout, ".jax_cache")
    monkeypatch.setattr(run, "CACHE_DIR", cache)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)  # main sets it; restored after
    rc, out, err = _main(run, ["--workload", cell, "--seed", str(2**31 + 5),
                               "--seconds", "0.5", "--trace", "0"], capsys)
    assert rc == 0
    result = json.loads(out[-1])
    assert list(result) == CONTRACT + ["check"]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    spec = run.Spec(root=checkout, bench=os.path.join(checkout, "bench"))
    want = {m["name"] for m in spec.metrics("end_to_end", cell)}
    assert set(result["metrics"]) == want and "setup_s" in want
    for m in result["metrics"].values():
        assert m["value"] > 0 and set(m) == {"value", "unit"}
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    # the numbers compared, each beside its limit, are the last stderr lines
    checks = [line for line in err if line.startswith("check ")]
    assert len(checks) == len(result["check"]) and err[-1].startswith("correct: True")


@pytest.mark.parametrize("cell,kind", [("tiny-sweep", "sweep"), ("tiny-serve", "serve")])
def test_traced_cell_reports_every_per_layer_metric(cell, kind, checkout, interpret,
                                                    chip_profiler):
    chip_profiler["name"] = kind
    run = benchkit.load_run(checkout)
    spec = run.Spec(root=checkout, bench=os.path.join(checkout, "bench"))
    result = run.run_cell(cell, 9, 0.5, True, platform="cpu", spec=spec)
    assert list(result) == CONTRACT + ["breakdown", "check"]
    want = {m["name"] for m in spec.metrics("per_layer", cell)}
    assert set(result["metrics"]) == want
    if kind == "serve":
        assert THROWAWAY["name"] in want
    for name, m in result["metrics"].items():
        if "roofline" in name or "mfu" in name:
            assert 0 < m["value"] <= 100, name
    dev = result["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    bd = result["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10


def test_main_refuses_a_host_without_a_tpu(capsys):
    run = benchkit.load_run(benchkit.REPO)
    rc = run.main(["--workload", "ws-sweep", "--seed", "1", "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc != 0 and out == ""
    assert "expected a tpu device" in err
