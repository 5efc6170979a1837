"""Fault-isolated, journaled, resumable exploration (ISSUE 6 acceptance).

The contract under test:
  * **degradation ladder** (`backend.lowering_ladder`): a kernel-path
    failure re-resolves the bucket one rung down (mosaic -> reference)
    and the fallback is BIT-IDENTICAL — a fallback changes the lowering,
    never the semantics; the 'cycle' solver joins a ladder only where
    `backend.cycle_exact` proves it identical;
  * **failure isolation**: a design failing every rung is quarantined as
    a structured `EvalFailure` — alone, never its bucket-mates, whose
    results stay bit-identical to a failure-free sweep; non-finite
    weights and fully-silent designs quarantine post-hoc;
  * **journal + resume** (`dse.journal`): completed buckets are
    published atomically (write-then-rename); a SIGKILLed run resumed
    with `explore(journal=..., resume=True)` re-evaluates only the
    missing candidates and reproduces the uninterrupted frontier
    exactly;
  * **explore meta**: failures/retries/fallbacks/stalls surface in
    `DSEResult.meta`, per-encoder values are recorded for ALL encoder
    groups, and an all-quarantined run yields an empty frontier with a
    diagnostic `best()` error, not an IndexError.

All faults are injected through the shared deterministic harness
(`repro.testing.faults`) at the `fused_column` instrumentation seam —
the same injectors the serving tests and the serve-bench chaos case
use, so every consumer exercises one fault model.
"""
import json
import os
import signal
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import dse
from repro.core import backend, simulator
from repro.core.types import ColumnConfig, STDPConfig
from repro.distributed.straggler import StepMonitor
from repro.kernels import fused_column
from repro.testing import faults


def _cfg(p, q, t_max, scale=1.0):
    c = ColumnConfig(p=p, q=q, t_max=t_max)
    return c.with_threshold(scale * simulator.suggest_threshold(c))


def _grid_cfg(p, q, t_max):
    """A config whose training provably stays on the integer weight grid
    (integer STDP steps, no stabilizer) — the `cycle_exact` regime."""
    c = ColumnConfig(
        p=p, q=q, t_max=t_max,
        stdp=STDPConfig(
            mu_capture=1.0, mu_backoff=1.0, mu_search=1.0, stabilizer="none"
        ),
    )
    return c.with_threshold(simulator.suggest_threshold(c))


def _stream(n=14, length=8, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, length)), rng.integers(0, classes, n)


def _poisoning_patch(monkeypatch, poison_threshold, lowerings=("reference",)):
    """Make `fit_scan_padded` raise whenever the poisoned design's
    threshold rides the batch at one of the given lowerings (shared
    harness injector)."""
    orig = fused_column.fit_scan_padded
    monkeypatch.setattr(
        fused_column, "fit_scan_padded",
        faults.fail_on_threshold(orig, poison_threshold, lowerings),
    )
    return orig


# ------------------------------------------------------- shared harness
def test_injected_context_manager_installs_and_restores():
    """`faults.injected` wraps a fused_column entry point for the block
    and restores the original even when the wrapper raises."""
    orig = fused_column.fit_scan_padded
    with faults.injected(
        "fit_scan_padded", faults.fail_always, detail="down"
    ) as saved:
        assert saved is orig
        assert fused_column.fit_scan_padded is not orig
        with pytest.raises(faults.InjectedFault, match="injected fault"):
            fused_column.fit_scan_padded()
    assert fused_column.fit_scan_padded is orig


def test_slow_call_and_nan_poison_wrappers():
    import time as _time

    calls = []

    def orig(a):
        calls.append(a)
        return np.ones((2, 2), np.float32)

    t0 = _time.perf_counter()
    out = faults.slow_call(orig, 0.02)(1)
    assert _time.perf_counter() - t0 >= 0.02
    assert np.array_equal(out, np.ones((2, 2)))
    poisoned = faults.nan_poison(orig)(2)
    assert np.isnan(poisoned).sum() == 1
    assert calls == [1, 2]


# --------------------------------------------------------- ladder policy
def test_lowering_ladder_policy():
    assert backend.lowering_ladder("mosaic") == ("mosaic", "reference")
    assert backend.lowering_ladder("reference") == ("reference",)
    assert backend.lowering_ladder("cycle") == ("cycle",)
    # the interpreter is never degraded INTO, only out of
    assert backend.lowering_ladder("interpret") == ("interpret", "reference")
    assert backend.lowering_ladder("mosaic", cycle_exact=True) == (
        "mosaic", "reference", "cycle",
    )
    with pytest.raises(ValueError, match="unknown lowering"):
        backend.lowering_ladder("vulkan")
    # the retry bound covers the whole ladder incl. the solver rung
    assert backend.MAX_EVAL_RETRIES >= len(
        backend.lowering_ladder("mosaic", cycle_exact=True)
    )


def test_cycle_exact_policy():
    w_int = jnp.asarray([[3.0, 0.0], [7.0, 2.0]])
    w_float = jnp.asarray([[3.5, 0.0], [7.0, 2.0]])
    default = _cfg(2, 2, 16)  # stabilizer='half': off-grid updates
    grid = _grid_cfg(2, 2, 16)
    assert not backend.cycle_exact(default, w_int)
    assert backend.cycle_exact(grid, w_int)
    assert not backend.cycle_exact(grid, w_float)
    # abstract weights answer False (same probe as assign_lowering)
    seen = []
    jax.eval_shape(
        lambda w: seen.append(backend.cycle_exact(grid, w)) or w,
        jax.ShapeDtypeStruct((2, 2), jnp.float32),
    )
    assert seen == [False]


# --------------------------------------------- kernel failure -> reference
def test_kernel_failure_degrades_to_reference_bit_identically(monkeypatch):
    """Acceptance (a): a kernel that raises on a bucket falls back to the
    reference lowering with bit-identical results, recording the retry."""
    x, y = _stream(seed=1)
    cfgs = [_cfg(8, 2, 16), _cfg(8, 3, 16), _cfg(8, 2, 24)]
    clean = simulator.cluster_time_series_many(x, y, cfgs, epochs=2, seed=3)

    # pretend-TPU: first-choice lowering is the Mosaic kernel, which the
    # injected fault fails; the ladder must land on 'reference'
    monkeypatch.setattr(backend, "padded_lowering", lambda response: "mosaic")
    monkeypatch.setattr(
        fused_column, "fit_scan_padded",
        faults.fail_on_lowering(fused_column.fit_scan_padded, ("mosaic",)),
    )
    # every rung the sweep leaves is loud, naming the rung and its error
    with pytest.warns(RuntimeWarning, match="'mosaic' failed.*injected"):
        res = simulator.cluster_time_series_many(
            x, y, cfgs, epochs=2, seed=3, on_error="isolate"
        )
    for i, (a, b) in enumerate(zip(res, clean)):
        assert isinstance(a, simulator.ClusteringResult)
        assert a.lowering == "reference" and a.retries == 1
        np.testing.assert_array_equal(
            a.assignments, b.assignments,
            err_msg=f"design {i}: fallback changed assignments",
        )
        np.testing.assert_array_equal(
            np.asarray(a.params["w"]), np.asarray(b.params["w"]),
            err_msg=f"design {i}: fallback changed weights",
        )
        assert a.rand_index == b.rand_index


def test_on_error_raise_propagates(monkeypatch):
    """The default mode keeps failing loudly — no silent degradation."""
    x, y = _stream(seed=1)
    cfgs = [_cfg(8, 2, 16)]
    _poisoning_patch(monkeypatch, cfgs[0].neuron.threshold)
    with pytest.raises(RuntimeError, match="injected fault"):
        simulator.cluster_time_series_many(x, y, cfgs, epochs=1, seed=3)
    with pytest.raises(ValueError, match="on_error"):
        simulator.cluster_time_series_many(
            x, y, cfgs, epochs=1, on_error="retry"
        )


# ------------------------------------------------- per-design quarantine
def test_poisoned_design_quarantined_alone(monkeypatch):
    """Acceptance (b): when the fallback fails too, ONLY the poisoned
    design is quarantined; bucket-mates re-run individually and stay
    bit-identical to a failure-free sweep."""
    x, y = _stream(seed=2)
    cfgs = [
        _cfg(8, 2, 16, 0.9), _cfg(8, 2, 16, 1.25),
        _cfg(8, 3, 16, 1.0), _cfg(8, 3, 16, 1.1),
    ]
    clean = simulator.cluster_time_series_many(x, y, cfgs, epochs=2, seed=5)
    poison = cfgs[1].neuron.threshold
    _poisoning_patch(monkeypatch, poison)  # every fused rung fails

    res = simulator.cluster_time_series_many(
        x, y, cfgs, epochs=2, seed=5, on_error="isolate"
    )
    fail = res[1]
    assert isinstance(fail, simulator.EvalFailure)
    assert fail.index == 1 and fail.stage == "fit"
    assert "injected fault" in fail.error
    assert fail.lowerings and fail.retries == len(fail.lowerings)
    # 'cycle' must NOT appear: stabilizer='half' designs are off-grid, so
    # the solver rung would change semantics and is gated out
    assert "cycle" not in fail.lowerings
    for i in (0, 2, 3):
        r = res[i]
        assert isinstance(r, simulator.ClusteringResult), f"design {i}"
        np.testing.assert_array_equal(r.assignments, clean[i].assignments)
        np.testing.assert_array_equal(
            np.asarray(r.params["w"]), np.asarray(clean[i].params["w"])
        )
        assert r.rand_index == clean[i].rand_index


def test_cycle_rung_bit_identical_when_exact(monkeypatch):
    """Integer-grid designs may degrade all the way to the 'cycle'
    solver — and the result is still bit-identical to the fused path."""
    x, y = _stream(seed=3)
    cfgs = [_grid_cfg(8, 2, 16), _grid_cfg(8, 3, 16)]
    rng = np.random.default_rng(11)
    w_init = [
        rng.integers(0, 8, (8, 2)).astype(np.float32),
        rng.integers(0, 8, (8, 3)).astype(np.float32),
    ]
    clean = simulator.cluster_time_series_many(
        x, y, cfgs, epochs=2, w_init=w_init
    )
    orig = fused_column.fit_scan_padded
    monkeypatch.setattr(
        fused_column, "fit_scan_padded",
        faults.fail_always(detail="all fused rungs down"),
    )
    res = simulator.cluster_time_series_many(
        x, y, cfgs, epochs=2, w_init=w_init, on_error="isolate"
    )
    monkeypatch.setattr(fused_column, "fit_scan_padded", orig)
    for i, (a, b) in enumerate(zip(res, clean)):
        assert isinstance(a, simulator.ClusteringResult)
        assert a.lowering == "cycle", f"design {i} should have degraded"
        assert a.retries >= 1
        np.testing.assert_array_equal(a.assignments, b.assignments)
        np.testing.assert_array_equal(
            np.asarray(a.params["w"]), np.asarray(b.params["w"])
        )


# ---------------------------------------------------- degeneracy guards
def test_nan_weights_and_silent_designs_quarantined():
    x, y = _stream(seed=4)
    cfgs = [_cfg(8, 2, 16) for _ in range(3)]
    rng = np.random.default_rng(6)
    w_init = [
        (rng.uniform(0, 7, (8, 2))).astype(np.float32) for _ in range(3)
    ]
    clean = simulator.cluster_time_series_many(
        x, y, cfgs, epochs=1, w_init=[w.copy() for w in w_init]
    )
    w_init[1][3, 1] = np.nan  # poisons design 1's training lane only
    res = simulator.cluster_time_series_many(
        x, y, cfgs, epochs=1, w_init=w_init, on_error="isolate"
    )
    assert isinstance(res[1], simulator.EvalFailure)
    assert res[1].stage == "weights" and "non-finite" in res[1].error
    assert float("nan") != res[1].rand_index  # NaN property, not a crash
    for i in (0, 2):
        assert isinstance(res[i], simulator.ClusteringResult)
        np.testing.assert_array_equal(
            res[i].assignments, clean[i].assignments
        )

    # a threshold no potential can reach -> no spikes -> 'silent'
    cfgs_sil = [_cfg(8, 2, 16), _cfg(8, 2, 16).with_threshold(1e9)]
    res_sil = simulator.cluster_time_series_many(
        x, y, cfgs_sil, epochs=1, on_error="isolate"
    )
    assert isinstance(res_sil[0], simulator.ClusteringResult)
    assert isinstance(res_sil[1], simulator.EvalFailure)
    assert res_sil[1].stage == "silent"


def test_w_init_validation():
    x, y = _stream(seed=5)
    cfgs = [_cfg(8, 2, 16)]
    with pytest.raises(ValueError, match="one array per config"):
        simulator.cluster_time_series_many(x, y, cfgs, w_init=[])
    with pytest.raises(ValueError, match="shape"):
        simulator.cluster_time_series_many(
            x, y, cfgs, w_init=[np.zeros((4, 4), np.float32)]
        )


# ------------------------------------------------------- explore surface
def test_explore_injected_failure_isolates_candidate(monkeypatch):
    """Acceptance: one injected failure in an 8-candidate explore run —
    the other 7 Rand indices are bit-identical to a failure-free run and
    the failed design lands in meta['failures']."""
    x, y = _stream(n=16, seed=7)
    # 8 distinct threshold scales: suggest_threshold depends only on the
    # geometry's input width, so distinct scales give every candidate a
    # unique threshold — the marker the injected fault keys on
    space = dse.DesignSpace(
        q=(2,), t_max=(16,),
        threshold_scale=(0.8, 0.9, 0.95, 1.0, 1.05, 1.1, 1.2, 1.3),
    )
    assert space.size() == 8
    clean = dse.explore(x, y, space, epochs=1, seed=2)
    assert len(clean.points) == 8 and not clean.meta["failures"]

    victim = clean.points[3]
    _poisoning_patch(monkeypatch, victim.cfg.neuron.threshold)
    res = dse.explore(x, y, space, epochs=1, seed=2)
    assert len(res.points) == 7
    assert res.meta["quarantined"] == 1
    (fail,) = res.meta["failures"]
    assert fail["index"] == victim.index and fail["stage"] == "fit"
    assert res.meta["retries"] >= fail["retries"] >= 1
    clean_by_index = {p.index: p for p in clean.points}
    for p in res.points:
        assert p.rand_index == clean_by_index[p.index].rand_index
        np.testing.assert_array_equal(
            np.asarray(p.params["w"]),
            np.asarray(clean_by_index[p.index].params["w"]),
        )
    assert "quarantined" in dse.summarize(res)


def test_explore_meta_per_encoder_group():
    """Satellite: multi-encoder runs record lowering/buckets for EVERY
    encoder group, not just the last one swept."""
    x, y = _stream(n=12, seed=8)
    space = dse.DesignSpace(
        q=(2,), t_max=(16,), encoder=("latency", "onoff")
    )
    res = dse.explore(x, y, space, epochs=1, seed=4)
    assert set(res.meta["lowering"]) == {"latency", "onoff"}
    assert set(res.meta["buckets"]) == {"latency", "onoff"}
    assert all(low for low in res.meta["lowering"].values())
    assert all(b >= 1 for b in res.meta["buckets"].values())


def test_explore_all_quarantined_empty_frontier_contract(monkeypatch):
    """Satellite: an all-quarantined run yields an empty (not raising)
    frontier and a diagnostic best() error — no opaque IndexError."""
    assert dse.pareto_front([]) == []
    x, y = _stream(seed=9)
    space = dse.DesignSpace(q=(2, 3), t_max=(16,))
    monkeypatch.setattr(
        fused_column, "fit_scan_padded",
        faults.fail_always(detail="every evaluation down"),
    )
    res = dse.explore(x, y, space, epochs=1, seed=5)
    assert res.points == [] and res.pareto == []
    assert res.meta["quarantined"] == space.size()
    with pytest.raises(ValueError, match="quarantined"):
        res.best()
    assert "quarantined" in dse.summarize(res)


def test_explore_stall_detection_surfaces_events():
    x, y = _stream(seed=10)
    # two envelope buckets -> two monitored steps; threshold 0 flags any
    # post-warmup bucket as a stall
    space = dse.DesignSpace(q=(2, 3), t_max=(16, 64))
    mon = StepMonitor(threshold=0.0, warmup=1)
    res = dse.explore(x, y, space, epochs=1, seed=6, monitor=mon)
    assert res.meta["stalls"], "post-warmup buckets must flag at threshold 0"
    ev = res.meta["stalls"][0]
    assert ev["duration_s"] > 0 and ev["ratio"] > 0


# ------------------------------------------------------------- journal
def test_candidate_fingerprint_deterministic_and_sensitive():
    cfg = _cfg(8, 2, 16)
    fp = dse.candidate_fingerprint(cfg, "latency", 0, 4)
    assert fp == dse.candidate_fingerprint(cfg, "latency", 0, 4)
    others = {
        dse.candidate_fingerprint(cfg, "onoff", 0, 4),
        dse.candidate_fingerprint(cfg, "latency", 1, 4),
        dse.candidate_fingerprint(cfg, "latency", 0, 5),
        dse.candidate_fingerprint(_cfg(8, 3, 16), "latency", 0, 4),
        dse.candidate_fingerprint(
            _cfg(8, 2, 16, 1.1), "latency", 0, 4
        ),
    }
    assert fp not in others and len(others) == 5


def test_journal_atomic_publish_and_guards(tmp_path):
    path = tmp_path / "run.jsonl"
    jr = dse.Journal(path)
    assert jr.load() == [] and jr.completed() == {}
    assert jr.begin({"seed": 0, "epochs": 1, "search": "grid"}, False) == {}
    jr.append([{"kind": "point", "fp": "aa", "rand_index": 0.5}])
    jr.append([{"kind": "failure", "fp": "bb", "stage": "fit"}])
    assert not os.path.exists(str(path) + ".tmp"), "publish must rename"
    assert set(dse.Journal(path).completed()) == {"aa", "bb"}

    # a fresh run must not clobber completed work
    with pytest.raises(ValueError, match="resume=True"):
        dse.Journal(path).begin(
            {"seed": 0, "epochs": 1, "search": "grid"}, False
        )
    # resuming under a different run configuration is an error
    with pytest.raises(ValueError, match="seed"):
        dse.Journal(path).begin(
            {"seed": 9, "epochs": 1, "search": "grid"}, True
        )
    got = dse.Journal(path).begin(
        {"seed": 0, "epochs": 1, "search": "grid"}, True
    )
    assert set(got) == {"aa", "bb"}

    # defensive read: a torn trailing line (non-atomic filesystem) is
    # skipped, never fatal
    with open(path, "a") as f:
        f.write('{"kind": "point", "fp": "cc", "rand_in')
    assert set(dse.Journal(path).completed()) == {"aa", "bb"}


def test_journal_torn_line_followed_by_valid_record(tmp_path):
    """A torn line with a valid record AFTER it (a non-atomic filesystem
    interleaving appends with a crash) loses only the torn record: later
    valid lines are kept, the header still validates, and resume picks up
    every intact evaluation."""
    path = tmp_path / "run.jsonl"
    meta = {
        "kind": "meta", "version": dse.journal.JOURNAL_VERSION,
        "seed": 0, "epochs": 1, "search": "grid",
    }
    good = {"kind": "point", "fp": "dd", "rand_index": 0.25}
    with open(path, "w") as f:
        f.write(json.dumps(meta) + "\n")
        f.write('{"kind": "point", "fp": "cc", "rand_in\n')  # torn
        f.write(json.dumps(good) + "\n")
    jr = dse.Journal(path)
    assert jr.load() == [meta, good]
    assert jr.completed() == {"dd": good}
    restored = jr.begin(
        {"seed": 0, "epochs": 1, "search": "grid"}, resume=True
    )
    assert set(restored) == {"dd"}
    # appending re-publishes atomically: the torn line is gone for good
    jr.append([{"kind": "point", "fp": "ee", "rand_index": 0.75}])
    assert set(dse.Journal(path).completed()) == {"dd", "ee"}
    raw = open(path).read()
    assert '"cc"' not in raw


def test_explore_resume_with_deleted_compile_cache_dir(
    tmp_path, monkeypatch
):
    """Journaled explorations enable the persistent compilation cache
    placed from outside (``JAX_COMPILATION_CACHE_DIR``).  Resuming with a
    matching meta-header after that directory vanished (cleaned scratch
    space) must repair the directory and reproduce the run, never fail."""
    import shutil

    x, y = _stream(n=10, seed=13)
    space = dse.DesignSpace(q=(2, 3), t_max=(16,))
    path = tmp_path / "dse.jsonl"
    cache_dir = tmp_path / "compile_cache"
    monkeypatch.setenv(backend.CACHE_ENV, str(cache_dir))
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_path = backend._compile_cache_path
    backend._compile_cache_path = None  # a fresh process picks the default
    try:
        full = dse.explore(x, y, space, epochs=1, seed=7, journal=str(path))
        assert backend.compile_cache_dir() == str(cache_dir)
        assert cache_dir.is_dir()
        shutil.rmtree(cache_dir)
        again = dse.explore(
            x, y, space, epochs=1, seed=7, journal=str(path), resume=True
        )
        assert cache_dir.is_dir(), "resume must repair the cache dir"
        assert again.meta["resumed"] == space.size()
        for a, b in zip(full.points, again.points):
            assert a.rand_index == b.rand_index
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        backend._compile_cache_path = prev_path


def test_explore_resume_skips_completed_and_is_bit_identical(tmp_path):
    x, y = _stream(n=12, seed=11)
    space = dse.DesignSpace(q=(2, 3), t_max=(16, 24))
    path = tmp_path / "dse.jsonl"
    full = dse.explore(x, y, space, epochs=1, seed=7, journal=str(path))
    assert full.meta["resumed"] == 0
    again = dse.explore(
        x, y, space, epochs=1, seed=7, journal=str(path), resume=True
    )
    assert again.meta["resumed"] == space.size()
    assert again.seconds < full.seconds  # nothing re-evaluated
    for a, b in zip(full.points, again.points):
        assert a.index == b.index and a.rand_index == b.rand_index
        assert a.area_um2 == b.area_um2 and a.leakage_uw == b.leakage_uw
        np.testing.assert_array_equal(
            np.asarray(a.params["w"]), np.asarray(b.params["w"])
        )
    assert [p.index for p in full.pareto] == [p.index for p in again.pareto]


def test_explore_resume_keeps_quarantine(tmp_path, monkeypatch):
    """A journaled failure stays quarantined on resume — the run never
    re-pays a known-degenerate evaluation."""
    x, y = _stream(seed=12)
    space = dse.DesignSpace(q=(2,), t_max=(16,), threshold_scale=(0.9, 1.2))
    path = tmp_path / "q.jsonl"
    poison = dse.candidate_config(
        space.grid()[1], x.shape[1]
    ).neuron.threshold
    _poisoning_patch(monkeypatch, poison)
    res = dse.explore(x, y, space, epochs=1, seed=8, journal=str(path))
    assert res.meta["quarantined"] == 1
    res2 = dse.explore(
        x, y, space, epochs=1, seed=8, journal=str(path), resume=True
    )
    assert res2.meta["resumed"] == space.size()
    (fail,) = res2.meta["failures"]
    assert fail["restored"] and fail["stage"] == "fit"
    assert [p.rand_index for p in res2.points] == [
        p.rand_index for p in res.points
    ]


def test_explore_sigkill_resume_reproduces_frontier(tmp_path):
    """Acceptance: a journaled explore run SIGKILLed mid-sweep, resumed
    with resume=True, reproduces the uninterrupted frontier exactly —
    losing at most one bucket of work (subprocess; the kill must take
    down a real process, not a pytest frame)."""
    path = tmp_path / "kill.jsonl"
    code = textwrap.dedent(f"""
        import os, signal
        import numpy as np
        from repro import dse

        class KillingJournal(dse.Journal):
            def append(self, records):
                super().append(records)
                os.kill(os.getpid(), signal.SIGKILL)  # die mid-run

        rng = np.random.default_rng(13)
        x = rng.normal(size=(12, 8)); y = rng.integers(0, 3, 12)
        space = dse.DesignSpace(q=(2, 3), t_max=(16, 64))
        dse.explore(x, y, space, epochs=1, seed=9,
                    journal=KillingJournal({str(path)!r}))
        raise SystemExit("unreachable: journal append must have killed us")
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=dict(os.environ, PYTHONPATH="src"),
        timeout=600,
    )
    assert r.returncode == -signal.SIGKILL, (r.returncode, r.stderr[-2000:])
    with open(path) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    n_done = sum(1 for rec in recs if rec["kind"] == "point")
    assert 1 <= n_done < 4, "the kill must land mid-run with partial work"

    rng = np.random.default_rng(13)
    x = rng.normal(size=(12, 8))
    y = rng.integers(0, 3, 12)
    space = dse.DesignSpace(q=(2, 3), t_max=(16, 64))
    resumed = dse.explore(
        x, y, space, epochs=1, seed=9, journal=str(path), resume=True
    )
    assert resumed.meta["resumed"] == n_done
    uninterrupted = dse.explore(x, y, space, epochs=1, seed=9)
    assert len(resumed.points) == len(uninterrupted.points) == 4
    for a, b in zip(uninterrupted.points, resumed.points):
        assert a.index == b.index
        assert a.rand_index == b.rand_index
        assert a.area_um2 == b.area_um2
        np.testing.assert_array_equal(
            np.asarray(a.params["w"]), np.asarray(b.params["w"])
        )
    assert [p.index for p in resumed.pareto] == [
        p.index for p in uninterrupted.pareto
    ]
