"""Cost-model contract tests (ISSUE 10).

Four guarantees:

  * the ExecutionPlan CONTRACT holds for every plan the chooser can emit
    (property-tested over randomized envelopes and synthetic devices):
    clamped ``v_blk``, lane-aligned ``t_blk``, ``shards`` dividing the
    design axis, sane waste cap;
  * the constants FALLBACK is exact — with no active profile every policy
    seam resolves to precisely the pre-costmodel hand-tuned constants
    (``backend.volley_block``, ``t_blk=128``, ``ENVELOPE_WASTE_CAP``);
  * a plan NEVER changes semantics — plan-chosen blocking and the
    constants blocking train bit-identical weights on both tracked bench
    geometries (blocking is a schedule, not math);
  * calibration records round-trip through disk and never activate on a
    mismatched host.

Tests never activate a profile implicitly: the autouse fixture restores
the active-profile state and keeps the cost terms analytic (the XLA
cost-analysis probe would trace+compile one real envelope per distinct
property-test shape).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import backend, simulator
from repro.core.types import ColumnConfig, NeuronConfig, TIME_DTYPE
from repro.roofline import costmodel

import jax.numpy as jnp


@pytest.fixture(autouse=True)
def _hermetic_costmodel(monkeypatch):
    """Restore the active profile after every test and keep the cost
    terms analytic — the XLA probe would compile one throwaway module
    per distinct property-example shape for numbers no contract here
    depends on."""
    prev = costmodel.profile()
    monkeypatch.setattr(
        costmodel, "envelope_cost",
        functools.partial(costmodel.envelope_cost.__wrapped__, use_xla=False)
        if hasattr(costmodel.envelope_cost, "__wrapped__")
        else functools.partial(costmodel.envelope_cost, use_xla=False),
    )
    costmodel._choose_plan_cached.cache_clear()
    yield
    costmodel.set_profile(prev)
    costmodel._choose_plan_cached.cache_clear()


def _synth_profile(**kw) -> costmodel.DeviceProfile:
    base = dict(
        name="synth", platform="cpu", device_kind="synth",
        peak_flops=5e10, hbm_bw=1e10, link_bw=1e10,
        dispatch_s=3e-5, compile_s=0.05, footprint_bytes=32 * 2**20,
        calibrated=True,
    )
    base.update(kw)
    return costmodel.DeviceProfile(**base)


# ------------------------------------------------------ plan contract
@settings(max_examples=60, deadline=None)
@given(
    d=st.integers(1, 16),
    p=st.integers(1, 512),
    q=st.integers(1, 64),
    t=st.integers(1, 512),
    n=st.integers(1, 1024),
    epochs=st.integers(1, 8),
    kind=st.sampled_from(["fit", "assign"]),
    lowering=st.sampled_from(["reference", "mosaic", "interpret"]),
    peak=st.floats(1e9, 1e15),
    bw=st.floats(1e8, 1e13),
    dispatch=st.floats(1e-7, 1e-3),
    compile_s=st.floats(1e-3, 10.0),
    footprint=st.floats(1e4, 1e9),
)
def test_any_plan_is_valid(
    d, p, q, t, n, epochs, kind, lowering, peak, bw, dispatch, compile_s,
    footprint,
):
    prof = _synth_profile(
        peak_flops=peak, hbm_bw=bw, link_bw=bw, dispatch_s=dispatch,
        compile_s=compile_s, footprint_bytes=footprint,
    )
    plan = costmodel.choose_plan(
        kind, lowering, d, p, q, t, n, epochs, prof=prof
    )
    assert costmodel.plan_is_valid(plan), plan
    assert plan.source == "costmodel"
    assert plan.profile == prof.name
    # the chooser never exceeds the hand-tuned upper bound: the warm
    # cliff past the constants base is a code-size effect outside the
    # roofline's sight
    cap = (
        costmodel.CONST_V_BLK_REFERENCE if lowering == "reference"
        else costmodel.CONST_V_BLK_KERNEL
    )
    assert plan.v_blk <= max(cap, 1)
    assert 1.5 <= plan.waste_cap <= 8.0
    # constants fallback obeys the same contract on the same inputs
    cplan = costmodel.constants_plan(kind, lowering, d, n, p, q, t)
    assert costmodel.plan_is_valid(cplan), cplan
    assert cplan.source == "constants"


def test_plan_is_hashable_and_deterministic():
    prof = _synth_profile()
    a = costmodel.choose_plan("fit", "reference", 4, 96, 10, 64, 64, 4,
                              prof=prof)
    b = costmodel.choose_plan("fit", "reference", 4, 96, 10, 64, 64, 4,
                              prof=prof)
    assert a == b and hash(a) == hash(b)
    assert {a: "plan"}[b] == "plan"  # usable as a jit static / memo key


# ------------------------------------------------- constants fallback
def test_constants_fallback_matches_legacy_policy():
    """With no active profile, every seam resolves to exactly the
    pre-costmodel constants."""
    assert costmodel.profile() is None or costmodel.set_profile(None) or True
    costmodel.set_profile(None)
    for lowering in ("reference", "mosaic"):
        for n in (1, 7, 64):
            for d in (1, 3, 4):
                plan = backend.execution_plan(
                    "fit", lowering, d, 96, 10, 64, n, 4
                )
                assert plan.source == "constants"
                assert plan.v_blk == backend.volley_block(lowering, n, d=d)
                assert plan.t_blk == backend.DEFAULT_T_BLK == 128
                assert plan.waste_cap == backend.ENVELOPE_WASTE_CAP
                assert plan.shards == backend.design_shards(d)
            aplan = backend.execution_plan(
                "assign", lowering, 4, 96, 10, 64, n, 1
            )
            # assign blocking historically ignored d (no unroll cap)
            assert aplan.v_blk == backend.volley_block(lowering, n)
    assert costmodel.choose_waste_cap() == backend.ENVELOPE_WASTE_CAP
    assert costmodel.choose_shards(4) == backend.design_shards(4)


def test_envelope_buckets_default_cap_unchanged():
    costmodel.set_profile(None)
    shapes = [(96, 2, 32), (96, 2, 32), (96, 10, 64), (96, 10, 64)]
    base = backend.envelope_buckets(shapes)
    hinted = backend.envelope_buckets(shapes, n_volleys=64, epochs=4)
    assert hinted == base  # no profile: the hint must not change policy


def test_waste_cap_with_profile_is_clamped_and_breaks_even():
    prof = _synth_profile()
    # a short stream cannot amortize a compile: the cap opens up (more
    # sharing); a long stream can: the cap tightens toward 1.5
    short = costmodel.choose_waste_cap(prof, 4, 96, 10, 64, n_volleys=1)
    long = costmodel.choose_waste_cap(
        prof, 4, 96, 10, 64, n_volleys=200_000, epochs=8
    )
    assert 1.5 <= long <= short <= 8.0


# ------------------------------------------------------- bit identity
# the two tracked bench geometries: the heterogeneous design sweep and
# the 2-layer network's fused layers (see benchmarks/train_bench.py)
_GEOMETRIES = (
    # (d, p, q_pad, t_window, q_actives, t_maxes)
    (4, 96, 10, 64, (5, 5, 10, 10), (32, 64, 32, 64)),   # sweep4x96p
    (4, 96, 8, 64, (8, 8, 8, 8), (64, 64, 64, 64)),      # net layer 0
    (1, 32, 5, 64, (5,), (64,)),                          # net layer 1
)


@pytest.mark.parametrize("geom", _GEOMETRIES)
def test_plan_blocking_is_bit_identical_to_constants(geom):
    d, p, q_pad, t_window, q_actives, t_maxes = geom
    B, epochs = 24, 2
    rng = np.random.default_rng(7)
    w0 = np.asarray(rng.integers(0, 8, (d, p, q_pad)), np.float32)
    xs = jnp.asarray(rng.integers(0, 32, (B, d, p)), TIME_DTYPE)
    thresholds = jnp.full((d,), p * 7 / 8.0, jnp.float32)
    tm = jnp.asarray(t_maxes, TIME_DTYPE)
    qa = jnp.asarray(q_actives, TIME_DTYPE)
    lowering = backend.padded_lowering("rnl")

    def fit():
        return np.asarray(backend.fit_padded(
            jnp.asarray(w0), xs, thresholds, tm, qa,
            t_window=t_window, w_max=7, wta_k=1,
            mu_capture=0.5, mu_backoff=-0.5, mu_search=0.1,
            stabilize=True, response="rnl", epochs=epochs,
            lowering=lowering,
        ))

    with costmodel.override(None):
        w_const = fit()
        const_plan = backend.execution_plan(
            "fit", lowering, d, p, q_pad, t_window, B, epochs
        )
    # low dispatch overhead puts the candidate blocks within the warm
    # tie tolerance, so the tie-break picks the cheapest trace (v_blk=2)
    # — a genuinely different schedule than the constants' 8 when d > 1
    prof = _synth_profile(dispatch_s=5e-6)
    with costmodel.override(prof):
        plan = backend.execution_plan(
            "fit", lowering, d, p, q_pad, t_window, B, epochs
        )
        w_plan = fit()
    assert plan.source == "costmodel"
    assert const_plan.source == "constants"
    # the schedules genuinely differ on at least the sweep geometry —
    # equality would make this test vacuous there
    if d > 1:
        assert plan.v_blk != const_plan.v_blk
    np.testing.assert_array_equal(w_plan, w_const)


# ------------------------------------------------------- persistence
def test_peaks_are_keyed_by_device_kind():
    """Peaks come from the profile of the device kind JAX reports; a
    device with no profile is an error, never another device's peaks."""
    from repro.roofline import analysis

    v5e = costmodel.default_profile("TPU v5 lite")
    assert (v5e.peak_flops, v5e.hbm_bw) == (197e12, 819e9)
    assert analysis.peaks(device_kind="TPU v5 lite")[:2] == (197e12, 819e9)
    with costmodel.override(None):  # no calibration: the host's own kind
        assert analysis.peaks()[3] == costmodel.default_profile(
            jax.devices()[0].device_kind
        ).name
        with pytest.raises(KeyError, match="TPU v99"):
            analysis.peaks(device_kind="TPU v99")


def test_calibration_round_trip(tmp_path):
    path = str(tmp_path / "calibration.json")
    prof = _synth_profile(
        platform=jax.default_backend(),
        device_kind=jax.devices()[0].device_kind,
        n_devices=jax.local_device_count(),
    )
    assert costmodel.save_profile(prof, path) == path
    costmodel.set_profile(None)
    got = costmodel.load_profile(path)
    assert got == prof
    assert costmodel.profile() == prof  # load ACTIVATES


def test_calibration_rejects_mismatched_host(tmp_path):
    path = str(tmp_path / "calibration.json")
    alien = _synth_profile(platform="tpu", device_kind="TPU v99")
    costmodel.save_profile(alien, path)
    costmodel.set_profile(None)
    assert costmodel.load_profile(path) is None
    assert costmodel.profile() is None


def test_calibration_rejects_unknown_version(tmp_path):
    path = str(tmp_path / "calibration.json")
    prof = _synth_profile(
        platform=jax.default_backend(),
        device_kind=jax.devices()[0].device_kind,
        n_devices=jax.local_device_count(),
    )
    d = prof.to_json()
    d["version"] = costmodel.CALIBRATION_VERSION + 1
    import json

    (tmp_path / "calibration.json").write_text(json.dumps(d))
    assert costmodel.load_profile(path) is None


# -------------------------------------------------- consumer threading
def test_sweep_records_plan_metadata():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, 24))
    cfgs = []
    for q in (2, 3):
        c = ColumnConfig(p=24, q=q, t_max=16)
        cfgs.append(c.with_threshold(simulator.suggest_threshold(c)))
    res = simulator.cluster_time_series_many(x, None, cfgs, epochs=1)
    for r in res:
        assert r.plan is not None
        assert r.plan["kind"] == "fit"
        assert r.plan["source"] in ("constants", "costmodel")
        assert r.plan["v_blk"] >= 1


def test_service_surfaces_plans():
    from repro.serve.service import ClusteringService

    c = ColumnConfig(p=8, q=2, t_max=16)
    c = c.with_threshold(simulator.suggest_threshold(c))
    svc = ClusteringService({"d0": c}, batch_size=2, refit_every=4,
                           refit_window=4)
    stats = svc.stats()
    assert len(stats.plans) == len(svc.buckets())
    asg_meta, fit_meta = stats.plans[0]
    assert asg_meta["kind"] == "assign"
    assert fit_meta["kind"] == "fit"
    for b in svc.buckets():
        assert b["assign_plan"]["source"] in ("constants", "costmodel")
