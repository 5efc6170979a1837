"""Streaming clustering service — the ISSUE 8 serving pipeline.

Pins the serving contract stage by stage:

* steady state is COMPILE-FREE: after ``warmup()`` an arbitrary traffic
  mix (full batches, partial flushes, online re-fits) performs zero XLA
  compiles, counted at the ``compile_counter`` seam;
* the online re-fit is bit-identical to an offline ``backend.fit_padded``
  resume from the same weights on the same volleys — including ragged
  windows, where the silent-volley no-op carries the proof;
* served assignments are bit-identical to the single-design assignment
  entry (``simulator.assign_time_series``) — the cross-envelope padding
  contract, request by request;
* admission failures raise structured ``RequestRejected`` (no tracing),
  and a poisoned request quarantines ALONE: batch-mates of a failing
  batch re-run against the same executable and answer bit-identically;
* overload control sheds structurally (bounded queues, deadline
  budgets) BEFORE any JAX work, with per-reason counters in ``stats()``;
* a failing or stalling online re-fit degrades the bucket to serving
  from last-good weights — compile-free, request-failure-free — and the
  bucket recovers once re-fits succeed again (faults injected through
  the shared ``repro.testing.faults`` harness).

Durability (snapshot+WAL crash recovery) is pinned separately in
``test_serve_recovery.py``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import backend, encoding, simulator
from repro.core.types import ColumnConfig, TIME_DTYPE
from repro.kernels import fused_column
from repro.serve import (
    ClusteringService,
    RequestRejected,
    ServeFailure,
    ServeResult,
    ServeShed,
)
from repro.testing import faults

P, T_MAX = 12, 16


def _cfg(q=4, t_max=T_MAX, p=P) -> ColumnConfig:
    c = ColumnConfig(p=p, q=q, t_max=t_max)
    return c.with_threshold(simulator.suggest_threshold(c))


def _fleet(n=4) -> dict:
    return {
        f"d{i}": _cfg(q=3 + (i % 2), t_max=T_MAX * (1 + (i // 2) % 2))
        for i in range(n)
    }


def _stream(rng, n):
    return [rng.normal(size=P) for _ in range(n)]


# ------------------------------------------------------------- pipeline
def test_serves_full_and_partial_batches():
    service = ClusteringService(_fleet(2), batch_size=4, refit_every=0)
    service.warmup()
    rng = np.random.default_rng(0)
    handles = [
        service.submit(s, f"d{i % 2}")
        for i, s in enumerate(_stream(rng, 6))
    ]
    # 4 submitted -> one auto-executed batch; 2 still queued
    assert [h.done for h in handles] == [True] * 4 + [False] * 2
    assert service.stats().pending == 2
    # result() on a queued request force-flushes its bucket (silent-padded
    # partial batch, same executable)
    res = handles[-1].result()
    assert isinstance(res, ServeResult)
    assert all(h.done for h in handles)
    stats = service.stats()
    assert stats.served == 6 and stats.pending == 0 and not stats.failed
    for h in handles:
        r = h.result()
        assert 0 <= r.cluster <= service._cfgs[r.design].q
        assert r.latency_s >= 0


def test_results_match_single_design_assignment_entry():
    """Bucket-batched serving answers == the D=1 assignment entry on the
    design's own envelope — the padding contract, request by request."""
    service = ClusteringService(_fleet(4), batch_size=4, refit_every=0,
                                seed=3, waste_cap=2.0)
    service.warmup()
    assert len(service.buckets()) >= 2  # tight cap splits the t_max pairs
    rng = np.random.default_rng(1)
    names = service.designs()
    cases = [(s, names[i % 4]) for i, s in enumerate(_stream(rng, 12))]
    handles = [service.submit(s, d) for s, d in cases]
    service.flush()
    for h, (s, d) in zip(handles, cases):
        expect = simulator.assign_time_series(
            s, service._cfgs[d], {"w": service.weights(d)}
        )
        assert h.result().cluster == int(expect)


@pytest.mark.parametrize("encoder", ["latency", "onoff"])
def test_steady_state_is_compile_free(compile_counter, encoder):
    """The acceptance bar: after warmup, a traffic mix spanning full
    batches, partial flushes and online re-fits performs ZERO XLA
    compiles — one resident executable per (bucket, shape), the jitted
    encode of every design shape included."""
    service = ClusteringService(
        _fleet(4), batch_size=8, refit_every=16, refit_window=16, seed=0,
        waste_cap=2.0,  # two buckets: steady state spans both executables
        encoder=encoder,
    )
    length = P if encoder == "latency" else P // 2
    service.warmup()
    assert compile_counter.compiles > 0  # warmup did the compiling
    base = compile_counter.compiles
    rng = np.random.default_rng(2)
    names = service.designs()
    handles = []
    for r in range(3):
        for s in range(24):
            handles.append(service.submit(
                rng.normal(size=length), names[s % len(names)]
            ))
        service.flush()  # partial batches ride the same executables
    stats = service.stats()
    assert stats.served == len(handles) and not stats.failed
    assert stats.refits >= 1  # re-fits happened inside the window
    assert compile_counter.compiles == base, (
        f"steady state compiled {compile_counter.compiles - base} "
        f"module(s): {compile_counter.names[base:]}"
    )


# -------------------------------------------------------------- re-fit
def test_online_refit_bit_identical_to_offline_resume():
    """Live re-fit == offline ``backend.fit_padded`` resume from the same
    weights on the same volleys (full window: shapes match exactly)."""
    cfg = _cfg()
    service = ClusteringService(
        {"d0": cfg}, batch_size=4, refit_every=8, refit_window=8, seed=7
    )
    service.warmup()
    w0 = service.weights("d0")  # silent warmup re-fit is a weight no-op
    rng = np.random.default_rng(3)
    series = _stream(rng, 8)
    for s in series:
        service.submit(s, "d0")
    assert service.stats().refits == 1

    enc = np.stack([
        np.asarray(encoding.encode(jnp.asarray(s), cfg.t_max))
        for s in series
    ])
    w_off = backend.fit_padded(
        jnp.asarray(w0[None]), jnp.asarray(enc[:, None, :], TIME_DTYPE),
        jnp.asarray([cfg.neuron.threshold], jnp.float32),
        jnp.asarray([cfg.t_max], TIME_DTYPE),
        jnp.asarray([cfg.q], TIME_DTYPE),
        t_window=cfg.t_max, w_max=cfg.neuron.w_max, wta_k=cfg.wta.k,
        mu_capture=cfg.stdp.mu_capture, mu_backoff=cfg.stdp.mu_backoff,
        mu_search=cfg.stdp.mu_search,
        stabilize=cfg.stdp.stabilizer == "half",
        response=cfg.neuron.response, epochs=1,
        lowering=backend.padded_lowering(cfg.neuron.response),
    )
    assert np.array_equal(service.weights("d0"), np.asarray(w_off[0]))


def test_ragged_refit_window_matches_unpadded_resume():
    """A re-fit window only partially filled (6 live volleys, window 8)
    trains bit-identically to an offline resume on the 6 volleys ALONE:
    the silent tail rows are exact weight no-ops above threshold 0."""
    cfg = _cfg()
    service = ClusteringService(
        {"d0": cfg}, batch_size=2, refit_every=6, refit_window=8, seed=11
    )
    service.warmup()
    w0 = service.weights("d0")
    rng = np.random.default_rng(5)
    series = _stream(rng, 6)
    for s in series:
        service.submit(s, "d0")
    assert service.stats().refits == 1

    enc = np.stack([
        np.asarray(encoding.encode(jnp.asarray(s), cfg.t_max))
        for s in series
    ])  # [6, p] — no padding on the offline side
    w_off = backend.fit_padded(
        jnp.asarray(w0[None]), jnp.asarray(enc[:, None, :], TIME_DTYPE),
        jnp.asarray([cfg.neuron.threshold], jnp.float32),
        jnp.asarray([cfg.t_max], TIME_DTYPE),
        jnp.asarray([cfg.q], TIME_DTYPE),
        t_window=cfg.t_max, w_max=cfg.neuron.w_max, wta_k=cfg.wta.k,
        mu_capture=cfg.stdp.mu_capture, mu_backoff=cfg.stdp.mu_backoff,
        mu_search=cfg.stdp.mu_search,
        stabilize=cfg.stdp.stabilizer == "half",
        response=cfg.neuron.response, epochs=1,
        lowering=backend.padded_lowering(cfg.neuron.response),
    )
    assert np.array_equal(service.weights("d0"), np.asarray(w_off[0]))


def test_refit_actually_learns():
    """The live weights move under traffic (the re-fit is not a no-op on
    real volleys) and keep serving afterwards."""
    service = ClusteringService(
        _fleet(1), batch_size=4, refit_every=4, refit_window=4, seed=2
    )
    service.warmup()
    w0 = service.weights("d0")
    rng = np.random.default_rng(9)
    for s in _stream(rng, 4):
        service.submit(s, "d0")
    assert service.stats().refits == 1
    assert not np.array_equal(service.weights("d0"), w0)
    h = service.submit(rng.normal(size=P), "d0")
    assert isinstance(h.result(), ServeResult)


# ----------------------------------------------------------- admission
def test_structured_rejection_without_tracing(compile_counter):
    """Admission failures raise structured RequestRejected BEFORE any JAX
    work — zero compiles, zero traces, and the service keeps serving."""
    service = ClusteringService(_fleet(2), batch_size=4, refit_every=0)
    service.warmup()
    base = compile_counter.compiles
    cases = [
        (np.zeros(P + 3), "d0", "envelope"),       # width fits no bucket
        (np.zeros(P), "nope", "unknown-design"),
        (np.zeros((2, P)), "d0", "shape"),
        (np.full(P, np.nan), "d0", "non-finite"),
    ]
    for series, design, reason in cases:
        with pytest.raises(RequestRejected) as ei:
            service.submit(series, design)
        assert ei.value.reason == reason
        assert ei.value.detail  # human-readable, machine-checkable
    assert compile_counter.compiles == base
    stats = service.stats()
    assert stats.rejected == len(cases)
    # per-reason counters: one rejection each, nothing double-counted
    assert stats.rejections == {
        "envelope": 1, "unknown-design": 1, "shape": 1, "non-finite": 1,
    }
    assert stats.offered == len(cases) and stats.submitted == 0
    h = service.submit(np.random.default_rng(0).normal(size=P), "d0")
    assert isinstance(h.result(), ServeResult)


def test_rejects_incompatible_fleets_at_construction():
    import dataclasses

    # threshold 0: silent-padding would stop being a weight no-op
    with pytest.raises(ValueError, match="threshold"):
        ClusteringService(
            {"bad": ColumnConfig(p=P, q=4, t_max=T_MAX).with_threshold(0.0)}
        )
    # mismatched statics cannot share one compiled program per bucket
    a = _cfg()
    b = dataclasses.replace(
        a, neuron=dataclasses.replace(a.neuron, w_max=a.neuron.w_max + 1)
    )
    with pytest.raises(ValueError, match="statics"):
        ClusteringService({"a": a, "b": b})
    with pytest.raises(ValueError, match="at least one design"):
        ClusteringService({})
    with pytest.raises(ValueError, match="encoder"):
        ClusteringService({"a": a}, encoder="morse")


# ------------------------------------------------------------ quarantine
def test_poisoned_request_quarantines_alone(monkeypatch):
    """A request that detonates the batch executable fails ALONE: every
    batch-mate re-runs against the same executable and answers
    bit-identically to an unpoisoned run."""
    cfg = _cfg()
    service = ClusteringService(
        {"d0": cfg}, batch_size=4, refit_every=0, seed=4
    )
    service.warmup()
    rng = np.random.default_rng(7)
    clean = _stream(rng, 3)
    expect = [
        int(simulator.assign_time_series(
            s, cfg, {"w": service.weights("d0")}
        ))
        for s in clean
    ]
    # the poison: a constant series encodes to an all-(t_max-1) volley —
    # distinctive, and never produced by the clean normal draws above
    poison = np.full(P, 2.5)
    poison_enc = np.asarray(encoding.encode(jnp.asarray(poison), cfg.t_max))

    # the instrumentation seam: backend.assign_padded honors a plain
    # callable in place of the jitted entry point (shared harness)
    monkeypatch.setattr(
        fused_column, "assign_padded",
        faults.fail_on_volley(fused_column.assign_padded, poison_enc),
    )

    handles = [service.submit(s, "d0") for s in clean]
    handles.append(service.submit(poison, "d0"))  # fills + detonates batch
    outcomes = [h.result() for h in handles]
    # batch-mates: bit-identical answers, served despite the poisoned mate
    for got, want in zip(outcomes[:3], expect):
        assert isinstance(got, ServeResult)
        assert got.cluster == want
    # the poison: quarantined as a structured failure
    assert isinstance(outcomes[3], ServeFailure)
    assert outcomes[3].stage == "assign"
    assert "poisoned" in outcomes[3].error
    stats = service.stats()
    assert stats.failed == 1 and stats.isolations == 1
    assert stats.served == 3 and stats.pending == 0


# ------------------------------------------------------ overload control
def test_overload_sheds_structured_with_retry_hint():
    """Beyond ``max_pending`` queued requests, admission sheds with
    ``reason='overloaded'`` and a retry-after hint — before any encode or
    JAX work — and capacity frees up again after a flush."""
    service = ClusteringService(
        _fleet(2), batch_size=8, refit_every=0, max_pending=3
    )
    service.warmup()
    rng = np.random.default_rng(0)
    for _ in range(3):
        service.submit(rng.normal(size=P), "d0")
    with pytest.raises(RequestRejected) as ei:
        service.submit(rng.normal(size=P), "d0")
    assert ei.value.reason == "overloaded"
    assert ei.value.retry_after_s is not None
    service.flush()
    h = service.submit(rng.normal(size=P), "d0")  # capacity is back
    assert isinstance(h.result(), ServeResult)
    stats = service.stats()
    assert stats.rejections == {"overloaded": 1}
    assert stats.offered == 5 and stats.submitted == 4 and stats.served == 4


def test_deadline_budget_sheds_at_dispatch_and_admission():
    """A request whose budget expires while queued is shed at dispatch (a
    ``ServeShed`` outcome, no JAX work); once a batch-time estimate
    exists, a budget below the predicted wait is rejected at admission."""
    service = ClusteringService(_fleet(2), batch_size=4, refit_every=0)
    service.warmup()
    rng = np.random.default_rng(1)
    # pre-traffic the wait estimate is 0, so admission is permissive
    h = service.submit(rng.normal(size=P), "d0", deadline_s=0.005)
    time.sleep(0.02)
    service.flush()
    shed = h.result()
    assert isinstance(shed, ServeShed)
    assert shed.reason == "deadline" and shed.waited_s >= 0.005
    # serve real traffic to establish the batch-time EWMA
    for _ in range(4):
        service.submit(rng.normal(size=P), "d0")
    assert service._batch_ewma is not None
    with pytest.raises(RequestRejected) as ei:
        service.submit(rng.normal(size=P), "d0", deadline_s=1e-12)
    assert ei.value.reason == "deadline"
    assert ei.value.retry_after_s > 0
    stats = service.stats()
    assert stats.shed == 1 and stats.rejections == {"deadline": 1}
    assert stats.served == 4 and not stats.failed


def test_drain_serves_inflight_then_stops_admission():
    service = ClusteringService(_fleet(2), batch_size=4, refit_every=0)
    service.warmup()
    rng = np.random.default_rng(2)
    handles = [service.submit(rng.normal(size=P), "d0") for _ in range(2)]
    assert not any(h.done for h in handles)  # queued behind a partial batch
    service.drain()
    assert all(isinstance(h.result(), ServeResult) for h in handles)
    with pytest.raises(RequestRejected) as ei:
        service.submit(rng.normal(size=P), "d0")
    assert ei.value.reason == "draining"
    stats = service.stats()
    assert stats.served == 2 and stats.pending == 0
    assert stats.rejections == {"draining": 1}


# ------------------------------------------------------- degraded re-fit
def test_refit_outage_degrades_to_last_good_compile_free(
    compile_counter, monkeypatch
):
    """The acceptance bar for degraded mode: with the re-fit path down
    hard, the service keeps answering from last-good weights — zero
    request failures, zero XLA compiles — and recovers (weights learning
    again) once the fault lifts."""
    service = ClusteringService(
        _fleet(2), batch_size=4, refit_every=4, refit_window=4, seed=0
    )
    service.warmup()
    w0 = {d: service.weights(d) for d in service.designs()}
    base = compile_counter.compiles
    rng = np.random.default_rng(3)
    with monkeypatch.context() as m:
        m.setattr(
            fused_column, "fit_scan_padded",
            faults.fail_always(detail="refit executable down"),
        )
        for _ in range(12):  # 3 re-fit windows under the outage
            service.submit(rng.normal(size=P), "d0")
            service.submit(rng.normal(size=P), "d1")
        service.flush()
    mid = service.stats()
    assert mid.served == 24 and not mid.failed  # every request answered
    assert mid.degraded == 1 and mid.refit_failures >= 1
    assert mid.refits == 0 and mid.recoveries == 0
    assert compile_counter.compiles == base  # no compile under the outage
    for d in service.designs():
        assert np.array_equal(service.weights(d), w0[d])  # last-good held

    # fault lifted: the backoff cooldown expires, a window commits, the
    # bucket recovers, and the weights move again
    for _ in range(16):
        service.submit(rng.normal(size=P), "d0")
        service.submit(rng.normal(size=P), "d1")
    service.flush()
    stats = service.stats()
    assert stats.recoveries == 1 and stats.degraded == 0
    assert stats.refits >= 1 and not stats.failed
    assert any(
        not np.array_equal(service.weights(d), w0[d])
        for d in service.designs()
    )
    assert compile_counter.compiles == base  # recovery reused executables


def test_nan_poisoned_refit_is_never_committed(monkeypatch):
    """A re-fit that 'succeeds' with NaN weights is rejected by the
    finite-weights guard — the live weights stay finite and last-good."""
    service = ClusteringService(
        _fleet(1), batch_size=4, refit_every=4, refit_window=4, seed=1
    )
    service.warmup()
    w0 = service.weights("d0")
    rng = np.random.default_rng(4)
    with monkeypatch.context() as m:
        m.setattr(
            fused_column, "fit_scan_padded",
            faults.nan_poison(fused_column.fit_scan_padded),
        )
        for s in _stream(rng, 4):
            service.submit(s, "d0")
    stats = service.stats()
    assert stats.refit_failures == 1 and stats.degraded == 1
    assert stats.refits == 0 and not stats.failed
    assert np.array_equal(service.weights("d0"), w0)
    assert np.isfinite(service.weights("d0")).all()


def test_refit_watchdog_discards_stalled_attempt(monkeypatch):
    """An attempt exceeding ``refit_budget_s`` is discarded as a stall
    (its result thrown away) even though it returned fine weights."""
    service = ClusteringService(
        _fleet(1), batch_size=4, refit_every=4, refit_window=4, seed=2,
        refit_budget_s=0.01,
    )
    service.warmup()
    w0 = service.weights("d0")
    rng = np.random.default_rng(5)
    with monkeypatch.context() as m:
        m.setattr(
            fused_column, "fit_scan_padded",
            faults.slow_call(fused_column.fit_scan_padded, 0.05),
        )
        for s in _stream(rng, 4):
            service.submit(s, "d0")
    stats = service.stats()
    assert stats.refit_stalls >= 1 and stats.refit_failures == 1
    assert stats.degraded == 1 and not stats.failed
    assert np.array_equal(service.weights("d0"), w0)


def test_refit_step_down_is_loud_and_counted(monkeypatch):
    """A re-fit that leaves its first-choice lowering warns with the error,
    counts the failed rung and records the rung it landed on — a kernel
    the device refuses never passes as a quiet fallback."""
    monkeypatch.setattr(backend, "padded_lowering", lambda r: "interpret")
    monkeypatch.setattr(backend, "assign_lowering", lambda r, w: "reference")
    service = ClusteringService(
        _fleet(1), batch_size=4, refit_every=4, refit_window=4, seed=2,
    )
    service.warmup()
    rng = np.random.default_rng(5)
    with monkeypatch.context() as m:
        m.setattr(
            fused_column, "fit_scan_padded",
            faults.fail_on_lowering(
                fused_column.fit_scan_padded, ("interpret",)
            ),
        )
        with pytest.warns(RuntimeWarning, match="'interpret' failed"):
            for s in _stream(rng, 4):
                service.submit(s, "d0")
    stats = service.stats()
    assert stats.refits == 1 and stats.refit_retries == 1
    assert stats.refit_failures == 0 and not stats.degraded
    (b,) = service.buckets()
    assert b["fit_lowering"] == "interpret"
    assert b["last_fit_lowering"] == "reference"


# ------------------------------------------------- seams used by serving
def test_pad_stream_silent_seam():
    xs = np.arange(12, dtype=np.int32).reshape(2, 2, 3)
    out = fused_column.pad_stream_silent(xs, 5, 99)
    assert out.shape == (5, 2, 3) and isinstance(out, np.ndarray)
    assert np.array_equal(out[:2], xs) and (out[2:] == 99).all()
    assert fused_column.pad_stream_silent(xs, 2, 99) is xs  # no-op path
    j = fused_column.pad_stream_silent(jnp.asarray(xs), 4, 7)
    assert j.shape == (4, 2, 3) and bool((np.asarray(j)[2:] == 7).all())
    with pytest.raises(ValueError, match="exceeds"):
        fused_column.pad_stream_silent(xs, 1, 99)


def test_warm_front_doors_make_dispatch_compile_free(compile_counter):
    """backend.warm_fit_padded / warm_assign_padded compile an envelope's
    executables with NO operands; the later operand-carrying front-door
    calls are then dispatch-only (key identity by construction)."""
    cfg = _cfg()
    kw = dict(
        t_window=cfg.t_max, wta_k=cfg.wta.k,
        response=cfg.neuron.response, lowering="reference",
    )
    assert backend.warm_assign_padded(
        1, cfg.p, cfg.q, 4, w_max=cfg.neuron.w_max, **kw
    ) in (False, True)
    assert backend.warm_assign_padded(  # second warm: already resident
        1, cfg.p, cfg.q, 4, w_max=cfg.neuron.w_max, **kw
    ) is True
    # operands built BEFORE the baseline: eager zeros/asarray ops compile
    # tiny modules of their own the first time a shape appears in-process,
    # and those are not what this test pins
    w0 = jnp.zeros((1, cfg.p, cfg.q))
    xs4 = jnp.zeros((4, 1, cfg.p), TIME_DTYPE)
    xs8 = jnp.zeros((8, 1, cfg.p), TIME_DTYPE)
    thr = jnp.asarray([cfg.neuron.threshold], jnp.float32)
    t_maxes = jnp.asarray([cfg.t_max], TIME_DTYPE)
    q_actives = jnp.asarray([cfg.q], TIME_DTYPE)
    base = compile_counter.compiles
    ids = backend.assign_padded(
        w0, xs4, thr, t_maxes, q_actives, w_max=cfg.neuron.w_max, **kw
    )
    assert ids.shape == (1, 4)
    assert compile_counter.compiles == base  # dispatch-only

    assert backend.warm_fit_padded(
        1, cfg.p, cfg.q, 8, t_window=cfg.t_max, w_max=cfg.neuron.w_max,
        wta_k=cfg.wta.k, stabilize=False, response=cfg.neuron.response,
        epochs=1, lowering="reference",
    ) in (False, True)
    base = compile_counter.compiles
    w = backend.fit_padded(
        w0, xs8, thr, t_maxes, q_actives,
        t_window=cfg.t_max, w_max=cfg.neuron.w_max, wta_k=cfg.wta.k,
        mu_capture=cfg.stdp.mu_capture, mu_backoff=cfg.stdp.mu_backoff,
        mu_search=cfg.stdp.mu_search, stabilize=False,
        response=cfg.neuron.response, epochs=1, lowering="reference",
    )
    assert w.shape == (1, cfg.p, cfg.q)
    assert compile_counter.compiles == base  # dispatch-only


def test_assign_time_series_single_and_micro_batch():
    cfg = _cfg()
    rng = np.random.default_rng(6)
    params = {"w": rng.integers(0, cfg.neuron.w_max + 1, (cfg.p, cfg.q))}
    batch = rng.normal(size=(5, P))
    ids = simulator.assign_time_series(batch, cfg, params)
    assert ids.shape == (5,)
    assert ((0 <= ids) & (ids <= cfg.q)).all()
    for i in range(5):
        one = simulator.assign_time_series(batch[i], cfg, params)
        assert int(one) == int(ids[i])  # micro-batch == single requests


# ---------------------------------------------------------------- spans
def _children(snap, span):
    return [s.name for s in snap.spans if s.parent == span.id]


def test_traced_requests_give_the_span_tree(monkeypatch, tmp_path):
    """Under the profiler every request is one ``serve.submit`` holding
    its admission and encode; each batch one ``serve.execute`` holding
    assembly, assign and completion; each re-fit one ``serve.refit``
    holding its window, fit rung, check and commit.  Untraced, nothing
    is recorded."""
    monkeypatch.setattr(backend, "padded_lowering", lambda r: "interpret")
    service = ClusteringService(
        _fleet(2), batch_size=4, refit_every=4, refit_window=4
    )
    service.warmup()
    rng = np.random.default_rng(3)
    obs.reset()
    for i, s in enumerate(_stream(rng, 6)):
        service.submit(s, f"d{i % 2}")
    service.flush()
    assert obs.snapshot().spans == ()

    before = service.stats()
    with jax.profiler.trace(str(tmp_path)):
        for i, s in enumerate(_stream(rng, 10)):
            service.submit(s, f"d{i % 2}")
        service.flush()
    after = service.stats()
    snap = obs.snapshot()
    obs.reset()

    submits = [s for s in snap.spans if s.name == "serve.submit"]
    assert [s.attrs["request"] for s in submits] == list(
        range(before.submitted, after.submitted)
    )
    for s in submits:
        assert s.parent == 0 and s.attrs["bucket"] == 0
        kids = _children(snap, s)
        assert kids[:2] == ["serve.admit", "serve.encode"]
        assert kids[2:] in ([], ["serve.execute"])
    executes = [s for s in snap.spans if s.name == "serve.execute"]
    assert len(executes) == after.batches - before.batches == 3
    flush, = [s for s in snap.spans if s.name == "serve.flush"]
    parents = {s.id: s.name for s in snap.spans}
    assert sorted(parents[s.parent] for s in executes) == [
        "serve.flush", "serve.submit", "serve.submit"
    ]
    for s in executes:
        kids = _children(snap, s)
        assert kids[:3] == ["serve.batch_xs", "serve.assign", "serve.complete"]
        assert kids[3:] in ([], ["serve.refit"])
    refits = [s for s in snap.spans if s.name == "serve.refit"]
    assert len(refits) == after.refits - before.refits >= 1
    for s in refits:
        assert _children(snap, s) == [
            "serve.refit_xs", "serve.fit", "serve.refit_check", "serve.commit"
        ]
    assert {s.attrs["lowering"] for s in snap.spans
            if s.name == "serve.fit"} == {"interpret"}
    assert {s.attrs["rule"] for s in snap.spans
            if s.name == "serve.fit"} == {"expected"}
    # float weights keep every batch on the reference assign; each re-fit
    # counts the live volleys its designs trained on: 3 + 3 (two served
    # before the window, four in it), then 2 + 2
    assert snap.counters == {
        "serve.rows_live": 10, "serve.rows_slots": 12,
        "serve.assign_reference": 3, "serve.refit_design_volleys": 10,
    }

