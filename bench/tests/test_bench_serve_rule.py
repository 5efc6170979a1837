"""The ``serve_rule`` kind and the serving-counter readers, rehearsed on
the host at a tiny size.

A tiny stochastic-STDP fleet runs through ``bench/run.py`` as
``fleet-serve-stochastic`` runs on the chip (the fused paths steered to
the Pallas interpreter), its check against
``bench/reference_stochastic_serve.py`` holds with counters equal bit for
bit, the control (the reference in bfloat16) fails its limits, and the
two readers of the program's serving counters read what was counted, and
nothing for a program without them.
"""
import contextlib
import json
import os
import shutil
import sys

import pytest

import benchkit
import control
from repro import obs

CELL = "tiny-serve-rule"


def _limits():
    with open(os.path.join(benchkit.BENCH, "traffic", "fleet-serve-stochastic.json")) as f:
        return json.load(f)["limits"]


def _checkout(dst: str) -> str:
    """The tiny checkout plus a tiny stochastic fleet and its ``serve_rule``
    cell, listed in every metric ``fleet-serve-stochastic`` reports: new
    files and entries only."""
    root = benchkit.tiny_checkout(dst)
    config = dict(benchkit.tiny_config(), name="tiny-fleet-stochastic")
    config["stdp"] = dict(config["stdp"], mode="stochastic")
    # the cell's own re-fit window and epochs
    traffic = dict(benchkit.TINY_TRAFFIC["tiny-serve"], kind="serve_rule",
                   refit_window=32, refit_epochs=1, limits=_limits())
    for path, obj in ((("configs", "tiny-fleet-stochastic.json"), config),
                      (("traffic", f"{CELL}.json"), traffic)):
        with open(os.path.join(root, "bench", *path), "w") as f:
            json.dump(obj, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-fleet-stochastic",
                            "source": "https://arxiv.org/abs/2205.07410",
                            "file": "bench/configs/tiny-fleet-stochastic.json",
                            "reduced": [], "why": "tests"})
    spec["workloads"].append({"name": CELL, "config": "tiny-fleet-stochastic",
                              "traffic": CELL, "chips": 1, "why": "tests"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "fleet-serve-stochastic" in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return _checkout(str(tmp_path_factory.mktemp("serve_rule")))


@pytest.fixture
def interpret(monkeypatch):
    from repro.core import backend

    monkeypatch.setattr(backend, "pallas_lowering", lambda: "interpret")
    monkeypatch.setattr(backend, "compile_cache", lambda *a, **k: None)


def test_untraced_stochastic_serve_cell_is_correct(checkout, interpret, capsys, monkeypatch):
    run = benchkit.load_run(checkout)
    cache = os.path.join(checkout, ".jax_cache")
    monkeypatch.setattr(run, "CACHE_DIR", cache)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    rc = run.main(["--workload", CELL, "--seed", str(2**31 + 13), "--seconds", "0.5",
                   "--trace", "0"], platform="cpu")
    out, err = capsys.readouterr()
    assert rc == 0
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    # integer counters and the same stream: the counters agree exactly
    assert result["check"]["weight_gap"]["value"] == 0.0
    assert result["check"]["answer_mismatch"]["value"] == 0.0
    assert {"setup_s", "serve_p50_ms"} <= set(result["metrics"])


def test_the_cell_re_fits_and_draws_from_integer_counters(checkout, interpret):
    """The window commits re-fits, so the check compares trained counters,
    and the kind starts every design on the integer grid."""
    import jax.numpy as jnp
    import numpy as np

    run = benchkit.load_run(checkout)
    spec = run.Spec(root=checkout, bench=os.path.join(checkout, "bench"))
    if spec.bench not in sys.path:
        sys.path.insert(0, spec.bench)
    cell = spec.cell(CELL)
    kind = spec.kind(spec.traffic(cell["traffic"])["kind"])
    runner = kind.Cell(spec.config(cell["config"]), spec.traffic(cell["traffic"]), 7, 1)
    for w in runner.w0.values():
        np.testing.assert_array_equal(w, np.round(w))
        assert w.min() >= 0 and w.max() <= runner.w_max
    runner.prepare_control(0.5)
    ids, weights, trained = runner._reference(jnp.float32)
    assert sum(trained.values()) > 0
    assert runner.compare((runner._ids, runner._weights), (ids, weights)) == [
        ("answer_mismatch", 0.0), ("weight_gap", 0.0)]


def test_traced_stochastic_serve_cell_reports_its_metrics(checkout, interpret, monkeypatch):
    import jax
    import work

    @contextlib.contextmanager
    def trace(log_dir, **kw):
        yield
        os.makedirs(log_dir, exist_ok=True)
        shutil.copy(benchkit.chip_trace("serve"), os.path.join(log_dir, "t.xplane.pb.gz"))

    monkeypatch.setattr(jax.profiler, "trace", trace)
    # the recorded trace stands in for the profiler: let the program count
    monkeypatch.setattr(obs, "enabled", lambda: True)
    obs.reset()
    v5e = work.peaks("TPU v5 lite")
    monkeypatch.setattr(work, "peaks", lambda device_kind, path=None: v5e)
    run = benchkit.load_run(checkout)
    spec = run.Spec(root=checkout, bench=os.path.join(checkout, "bench"))
    result = run.run_cell(CELL, 9, 0.5, True, platform="cpu", spec=spec)
    obs.reset()
    assert result["correct"] is True
    want = {m["name"] for m in spec.metrics("per_layer", CELL)}
    assert {"mosaic_assign_share.serve", "refit_roofline.serve"} <= want
    assert set(result["metrics"]) == want
    # integer counters: every batch's assign took the kernel
    assert result["metrics"]["mosaic_assign_share.serve"]["value"] == 100.0
    assert 0 < result["metrics"]["refit_roofline.serve"]["value"] <= 100


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_the_control_fails_the_stochastic_serve_limits(seed, checkout, interpret):
    run = benchkit.load_run(checkout)
    spec = run.Spec(root=checkout, bench=os.path.join(checkout, "bench"))
    got = control.readings(CELL, seed, 0.5, platform="cpu", spec=spec)
    limits = _limits()
    assert set(got) == set(limits)
    assert any(v > limits[k] for k, v in got.items()), got


# ----------------------------------------------------------- the readers
def _reader(name):
    return benchkit.load_module(f"metrics/{name}", "bench_metric_" + name.replace(".", "_"))


@pytest.mark.parametrize("counters,want", [
    ({"serve.assign_mosaic": 0, "serve.assign_reference": 40}, 0.0),
    ({"serve.assign_mosaic": 40, "serve.assign_reference": 0}, 100.0),
    ({"serve.assign_mosaic": 30, "serve.assign_reference": 10}, 75.0),
    ({}, 0.0),
])
def test_mosaic_assign_share_reader(counters, want, monkeypatch):
    monkeypatch.setattr(obs, "snapshot", lambda: obs.Snapshot.of([], counters))
    assert _reader("mosaic_assign_share.serve").read({"window_s": 1.0}) == pytest.approx(want)


def _roofline_ctx(kernel_s, refit_ops, refit_bytes=0, volleys=64):
    """A traced window's context as the ``serve_rule`` kind gives it:
    the replay counted ``volleys`` re-fit design-volleys."""
    import work

    trace = benchkit.load_module("trace", "bench_trace_for_serve_roofline")
    dev = trace.Device(name="/device:TPU:0", busy_s=1.0, programs={}, ops={}, gaps=[],
                       kernels={"jit_fit_scan_padded": kernel_s, "jit_assign_padded": 0.5})
    return {"trace": trace.Summary(window_s=2.0, devices=[dev], spans={}),
            "refit_ops": refit_ops, "refit_bytes": refit_bytes,
            "refit_design_volleys": volleys, "peak": work.peaks("TPU v5 lite")}


def _program_counted(monkeypatch, volleys):
    counters = {"serve.refit_design_volleys": volleys} if volleys else {}
    monkeypatch.setattr(obs, "snapshot", lambda: obs.Snapshot.of([], counters))


def test_refit_roofline_reader_at_0_100_and_partial(monkeypatch):
    import work

    peak = work.peaks("TPU v5 lite")
    read = _reader("refit_roofline.serve").read
    flops = peak["bf16_flops_per_s"]
    # no re-fit trained: no work, nothing to read
    _program_counted(monkeypatch, 0)
    assert read(_roofline_ctx(0.25, 0, volleys=0)) is None
    _program_counted(monkeypatch, 64)
    # the kernels took exactly the least time of the work: 100%
    assert read(_roofline_ctx(0.25, 0.25 * flops)) == pytest.approx(100.0)
    # four times the least time: 25%; the assign kernel is left out
    assert read(_roofline_ctx(1.0, 0.25 * flops)) == pytest.approx(25.0)
    # bytes that outweigh the operations set the least time
    bw = peak["hbm_bytes_per_s"]
    assert read(_roofline_ctx(1.0, 1.0, 0.5 * bw)) == pytest.approx(50.0)


def test_refit_roofline_reader_needs_the_program_and_the_replay_to_agree(
        monkeypatch, capsys):
    """The work is priced from the replay only when the program trained
    on as many design-volleys as the replay did; a kind whose replay does
    not count them (``serve``) reads nothing."""
    import work

    read = _reader("refit_roofline.serve").read
    flops = work.peaks("TPU v5 lite")["bf16_flops_per_s"]
    _program_counted(monkeypatch, 63)
    assert read(_roofline_ctx(1.0, 0.25 * flops)) is None
    assert "counted 63" in capsys.readouterr().err
    _program_counted(monkeypatch, 64)
    ctx = _roofline_ctx(1.0, 0.25 * flops)
    del ctx["refit_design_volleys"], ctx["refit_bytes"]
    assert read(ctx) is None


@pytest.mark.parametrize("name", ["mosaic_assign_share.serve", "refit_roofline.serve"])
def test_serve_counter_readers_report_nothing_for_a_program_without_them(name, monkeypatch):
    from repro.serve import service

    ctx = _roofline_ctx(1.0, 1e12)
    _program_counted(monkeypatch, 64)
    monkeypatch.delattr(service, "SERVE_COUNTERS")
    assert _reader(name).read(ctx) is None
    import repro

    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert _reader(name).read(ctx) is None
