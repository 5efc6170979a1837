"""The ``sweep_rule`` kind and the sweep-counter readers, rehearsed on the
host at a tiny size.

A tiny stochastic-STDP configuration runs through ``bench/run.py`` as
``ws-sweep-stochastic`` runs on the chip (the fused paths steered to the
Pallas interpreter), its check against ``bench/reference_stochastic.py``
holds, the control (the reference in bfloat16) fails its limits, and the
two readers of the program's sweep counters read what was counted.
"""
import json
import os
import sys

import pytest

import benchkit
import control
from repro import obs

CELL = "tiny-sweep-rule"


def _limits():
    with open(os.path.join(benchkit.BENCH, "traffic", "ws-sweep-stochastic.json")) as f:
        return json.load(f)["limits"]


def _checkout(dst: str) -> str:
    """The tiny checkout plus a tiny stochastic configuration and its
    ``sweep_rule`` cell, listed in every metric ``ws-sweep-stochastic``
    reports: new files and entries only."""
    root = benchkit.tiny_checkout(dst)
    config = dict(benchkit.tiny_config(), name="tiny-stochastic")
    config["stdp"] = dict(config["stdp"], mode="stochastic")
    traffic = dict(benchkit.TINY_TRAFFIC["tiny-sweep"], kind="sweep_rule",
                   epochs=2, limits=_limits())
    for path, obj in ((("configs", "tiny-stochastic.json"), config),
                      (("traffic", f"{CELL}.json"), traffic)):
        with open(os.path.join(root, "bench", *path), "w") as f:
            json.dump(obj, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-stochastic", "source": "https://arxiv.org/abs/2412.17977",
                            "file": "bench/configs/tiny-stochastic.json", "reduced": [],
                            "why": "tests"})
    spec["workloads"].append({"name": CELL, "config": "tiny-stochastic", "traffic": CELL,
                              "chips": 1, "why": "tests"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "ws-sweep-stochastic" in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return _checkout(str(tmp_path_factory.mktemp("sweep_rule")))


@pytest.fixture
def interpret(monkeypatch):
    from repro.core import backend

    monkeypatch.setattr(backend, "pallas_lowering", lambda: "interpret")
    monkeypatch.setattr(backend, "compile_cache", lambda *a, **k: None)


def test_untraced_stochastic_cell_is_correct(checkout, interpret, capsys, monkeypatch):
    run = benchkit.load_run(checkout)
    cache = os.path.join(checkout, ".jax_cache")
    monkeypatch.setattr(run, "CACHE_DIR", cache)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    rc = run.main(["--workload", CELL, "--seed", str(2**31 + 11), "--seconds", "0.5",
                   "--trace", "0"], platform="cpu")
    out, err = capsys.readouterr()
    assert rc == 0
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["check"]) == {"weight_gap", "rand_index_gap"}
    assert {"setup_s", "column_volleys_per_s"} <= set(result["metrics"])


def test_traced_stochastic_cell_reports_its_metrics(checkout, interpret, monkeypatch):
    import contextlib
    import shutil

    import jax
    import work

    @contextlib.contextmanager
    def trace(log_dir, **kw):
        yield
        os.makedirs(log_dir, exist_ok=True)
        shutil.copy(benchkit.chip_trace("sweep"), os.path.join(log_dir, "t.xplane.pb.gz"))

    monkeypatch.setattr(jax.profiler, "trace", trace)
    v5e = work.peaks("TPU v5 lite")
    monkeypatch.setattr(work, "peaks", lambda device_kind, path=None: v5e)
    run = benchkit.load_run(checkout)
    spec = run.Spec(root=checkout, bench=os.path.join(checkout, "bench"))
    result = run.run_cell(CELL, 9, 0.5, True, platform="cpu", spec=spec)
    assert result["correct"] is True
    want = {m["name"] for m in spec.metrics("per_layer", CELL)}
    assert {"mosaic_assign_share.sweep", "solver_designs.sweep"} <= want
    assert set(result["metrics"]) == want


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_the_control_fails_the_stochastic_limits(seed, checkout, interpret):
    run = benchkit.load_run(checkout)
    spec = run.Spec(root=checkout, bench=os.path.join(checkout, "bench"))
    got = control.readings(CELL, seed, 0.5, platform="cpu", spec=spec)
    limits = _limits()
    assert set(got) == set(limits)
    assert any(v > limits[k] for k, v in got.items()), got


# ----------------------------------------------------------- the readers
def _reader(name):
    return benchkit.load_module(f"metrics/{name}", "bench_metric_" + name.replace(".", "_"))


COUNTS = {"sim.assign_mosaic": 48, "sim.assign_reference": 16, "sim.solver_designs": 2}


@pytest.mark.parametrize("name,counters,want", [
    ("mosaic_assign_share.sweep", COUNTS, 100 * 48 / 64),
    ("mosaic_assign_share.sweep", {}, 0.0),
    ("solver_designs.sweep", COUNTS, 2.0),
    ("solver_designs.sweep", {}, 0.0),
])
def test_counter_readers(name, counters, want, monkeypatch):
    monkeypatch.setattr(obs, "snapshot", lambda: obs.Snapshot.of([], counters))
    assert _reader(name).read({"window_s": 1.0}) == pytest.approx(want)


@pytest.mark.parametrize("name", ["mosaic_assign_share.sweep", "solver_designs.sweep"])
def test_counter_readers_report_nothing_for_a_program_without_them(name, monkeypatch):
    from repro.core import simulator

    monkeypatch.setattr(obs, "snapshot", lambda: obs.Snapshot.of([], {}))
    monkeypatch.delattr(simulator, "SWEEP_COUNTERS")
    assert _reader(name).read({"window_s": 1.0}) is None
    import repro

    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert _reader(name).read({"window_s": 1.0}) is None


# ------------------------------------------------- the scoped fit roofline
def _roofline_ctx(summary):
    import work

    return {"trace": summary, "fit_ops": 3e12, "fit_bytes": 1e9,
            "peak": work.peaks("TPU v5 lite")}


def test_fit_block_roofline_leaves_the_assign_kernel_out():
    """With a Mosaic assign beside the fit, the scoped reader times the fit
    program's kernels alone, where ``fit_roofline.sweep`` times both."""
    trace = benchkit.load_module("trace", "bench_trace_for_roofline")
    dev = trace.Device(name="/device:TPU:0", busy_s=1.0, programs={}, ops={}, gaps=[],
                       kernels={"jit_fit_scan_padded": 0.9, "jit_assign_padded": 0.1})
    ctx = _roofline_ctx(trace.Summary(window_s=2.0, devices=[dev], spans={}))
    scoped = _reader("fit_block_roofline.sweep").read(ctx)
    whole = _reader("fit_roofline.sweep").read(ctx)
    assert scoped == pytest.approx(whole / 0.9)


def test_fit_block_roofline_matches_the_fit_roofline_on_a_recorded_sweep():
    """On the recorded chip sweep only the fit runs a kernel, so the two
    readers agree; a trace without the fit program reads nothing."""
    trace = benchkit.load_module("trace", "bench_trace_for_roofline")
    sweep = trace.reduce(benchkit.chip_trace("sweep"))
    ctx = _roofline_ctx(sweep)
    assert _reader("fit_block_roofline.sweep").read(ctx) == pytest.approx(
        _reader("fit_roofline.sweep").read(ctx), rel=1e-3)
    bare = trace.Summary(window_s=1.0, devices=[], spans={})
    assert _reader("fit_block_roofline.sweep").read(_roofline_ctx(bare)) is None
