"""Fused online-STDP training benchmark — the ISSUE 1/2/4 perf trajectory.

Times the fused single-scan training path (one jitted, donated lax.scan over
epochs x volleys, fused fire+WTA+STDP body) against the legacy per-epoch
loop, on paper column geometries, a padded heterogeneous design sweep (the
ISSUE 3 tentpole: ONE ``fit_scan_padded`` program with runtime design
operands vs one fused fit per design) AND a multi-layer network (the ISSUE 2
tentpole: ``network.fit_greedy`` as one jitted padded scan per layer vs the
untraced per-epoch Python loop it replaced).  Since ISSUE 4 the padded
cases run the volley-blocked scan (``v_blk`` volleys per step, one kernel
invocation / one unrolled reference body per block) and report BOTH warm
and cold numbers — the blocked path must win warm throughput, not just the
compile cliff, and ``main`` prints a REGRESSION flag whenever a fused case
reports warm speedup below the ``WARM_REGRESSION_MIN`` floor and a
COLD-REGRESSION flag whenever cold speedup
falls below the tracked ``COLD_REGRESSION_MIN`` floor.  Since ISSUE 5 a
bucketed heterogeneous sweep case (``sweepbkt*``) times the envelope-
bucketed front-end against the same sweep forced into one global envelope,
and every padded case records its bucket/shard metadata.

Cold numbers are first calls in this one process, measured against the
persistent compilation cache ``backend.compile_cache()`` places
(``JAX_COMPILATION_CACHE_DIR`` when set, the checkout's ``.jax_cache``
otherwise); each padded row records the state that cache was in when the
run started (``compile_cache`` column, via ``common.cache_state``), so a
cold row means "compile" only when it says ``fresh``.  Every case runs in
this process: an accelerator belongs to one process at a time, so the
bench starts no child that would need it.  ``--check`` validates the
committed floors for CI without re-running the bench.

Since ISSUE 10 ``main`` activates the device calibration
(``costmodel.load_or_calibrate``, persisted in the compile cache directory
so later runs resolve identical plans) and every padded case records
its resolved ``ExecutionPlan`` plus a plan-vs-constants warm head-to-head
(``plan_vs_const_speedup``, PLAN-REGRESSION below ``PLAN_REGRESSION_MIN``)
and the cost model's predicted-vs-measured step-time ratio.  Emits
``BENCH_train.json`` (us/volley + MXU
FLOPs of the fused kernel algebra) so the perf trajectory — including the
reference-vs-kernel gap on the padded path (the 'lowering' column) — is
tracked PR over PR; later PRs append comparable numbers.

MXU FLOPs count the one-hot plane matmuls of the fused Pallas kernel
(2 * (w_max+1) * p * q * t_max per volley) — the work the TPU lowering puts
on the systolic array; off-TPU the reference lowering does the same algebra
on the VPU-equivalent.
"""
from __future__ import annotations

import argparse
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import cache_state, emit, time_call, time_cold, time_pair
from repro.core import backend, column, network, simulator
from repro.roofline import costmodel
from repro.core.types import (
    ColumnConfig, LayerConfig, NetworkConfig, NeuronConfig, TIME_DTYPE,
)
from repro.kernels import fused_column

# (name, B volleys, p, q, t_max) — Beef-shaped default plus small/large cols
CASES = [
    ("col65x2", 64, 65, 2, 64),
    ("col470x5", 120, 470, 5, 64),
    ("col152x2", 64, 152, 2, 100),
]
EPOCHS = 4

# Tracked cold-regression threshold (ISSUE 5 CI satellite): the padded
# fused paths knowingly trade some cold (first-call, compile-inclusive)
# time for warm throughput — the blocked trace is bigger than the legacy
# per-design/per-epoch ones, and the one-trace-vs-D-traces cliff only wins
# back with design count.  A cold_speedup below this floor is a LOUD
# COLD-REGRESSION flag in ``main``, not a silent JSON column: net96-4x8-1x5
# shipped at 0.33x unflagged before the flag existed.  Raise the floor as
# cold compiles improve; lowering it needs a recorded justification here.
COLD_REGRESSION_MIN = 0.5

# Warm floor for the tracked padded cases.  Not 1.0: sweep4x96p's fused
# and legacy sides are within measurement parity on fast hosts — a clean
# worktree of the PRE-AOT seed commit (3257c6a) measures 0.974x on the
# same host/day that the AOT build measures 0.97-0.98x, and the AOT
# dispatcher itself benches at parity with a direct jit call — so a 1.0
# floor flags host drift, not code regressions.  0.95 still catches any
# real dispatch-overhead regression (a 50us/call slip on this geometry
# is ~0.92x).  Raising it back requires a control measurement like the
# one above.
WARM_REGRESSION_MIN = 0.95

# Plan-vs-constants floor (ISSUE 10): every tracked padded case runs a
# warm head-to-head between the cost-model-chosen blocking (the active
# device calibration) and the hand-tuned constants it replaced
# (``costmodel.override(None)`` forces the fallback).  The plan side must
# hold >= this fraction of the constants' warm throughput — the cost
# model is allowed to trade within measurement parity (this host is warm-
# flat across v_blk 2..8) for its cold-compile wins, never to lose real
# warm throughput.  Without a calibration both sides resolve identically
# and the ratio is ~1.0 by construction.
PLAN_REGRESSION_MIN = 0.95

def run() -> list:
    rows = []
    rng = np.random.default_rng(0)
    for name, B, p, q, t_max in CASES:
        cfg = ColumnConfig(
            p=p, q=q, t_max=t_max,
            neuron=NeuronConfig(threshold=p * 7 / 8.0),
        )
        params = {
            "w": jnp.asarray(rng.integers(0, 8, (p, q)), jnp.float32)
        }
        x = jnp.asarray(rng.integers(0, t_max, (B, p)), jnp.int32)

        def fused():
            jax.block_until_ready(
                column.fit(params, x, cfg, epochs=EPOCHS)["w"]
            )

        def legacy():
            pr = params
            for _ in range(EPOCHS):
                pr, _ = column.train_step(pr, x, cfg, update="batch")
            jax.block_until_ready(pr["w"])

        us_fused = time_call(fused)
        us_legacy = time_call(legacy)
        volleys = EPOCHS * B
        mxu_flops = 2 * (cfg.neuron.w_max + 1) * p * q * t_max
        rows.append({
            "case": name,
            "backend": backend.resolve("auto", cfg, training=True),
            "lowering": backend.pallas_lowering(),
            "fused_us_per_volley": us_fused / volleys,
            "legacy_us_per_volley": us_legacy / volleys,
            "speedup": us_legacy / max(us_fused, 1e-9),
            "mxu_flops_per_volley": mxu_flops,
        })
    return rows


# ------------------------------------------------------- padded design sweep
SWEEP_B = 64  # volleys per epoch
# heterogeneous candidates sharing one envelope: (q, t_max) per design,
# p pinned by the stream as in simulator.cluster_time_series_many
SWEEP_P = 96
SWEEP_DESIGNS = [(5, 32), (5, 64), (10, 32), (10, 64)]


def run_sweep(cache: str | None = None) -> dict:
    """Padded heterogeneous design sweep: ONE fit_scan_padded program
    (runtime design operands, one trace for the whole batch) vs the legacy
    per-design loop (one fused fit per design, D separate compilations).
    The reference-vs-kernel gap on this path is tracked by the 'lowering'
    column: 'reference' off-TPU, 'mosaic' on TPU."""
    rng = np.random.default_rng(2)
    d = len(SWEEP_DESIGNS)
    cfgs = [
        ColumnConfig(
            p=SWEEP_P, q=q, t_max=t_max,
            neuron=NeuronConfig(threshold=SWEEP_P * 7 / 8.0),
        )
        for q, t_max in SWEEP_DESIGNS
    ]
    c0 = cfgs[0]
    q_pad = max(c.q for c in cfgs)
    t_window = max(c.t_max for c in cfgs)
    lowering = backend.padded_lowering(c0.neuron.response)
    # the ExecutionPlan this case's fit will resolve to: the cost model's
    # choice under the active calibration, the volley_block/128 constants
    # otherwise (same resolution fit_padded performs internally)
    plan = backend.execution_plan(
        "fit", lowering, d, SWEEP_P, q_pad, t_window, SWEEP_B, EPOCHS,
        w_max=c0.neuron.w_max, response=c0.neuron.response,
    )

    w0 = np.zeros((d, SWEEP_P, q_pad), np.float32)
    for i, c in enumerate(cfgs):
        w0[i, :, : c.q] = rng.integers(0, 8, (SWEEP_P, c.q))
    x = rng.integers(0, min(c.t_max for c in cfgs), (SWEEP_B, SWEEP_P))
    xs = jnp.asarray(
        np.broadcast_to(x[:, None, :], (SWEEP_B, d, SWEEP_P)), TIME_DTYPE
    )
    thresholds = jnp.asarray([c.neuron.threshold for c in cfgs], jnp.float32)
    t_maxes = jnp.asarray([c.t_max for c in cfgs], TIME_DTYPE)
    q_actives = jnp.asarray([c.q for c in cfgs], TIME_DTYPE)

    def padded():
        # the AOT front door (backend.fit_padded) is the production entry
        # point — simulator and network route through it — so the bench
        # measures it too: same jitted program warm, and cold it reaps the
        # serialized-executable layer a populated cache dir provides
        w = backend.fit_padded(
            jnp.asarray(w0), xs, thresholds, t_maxes, q_actives,
            t_window=t_window, w_max=c0.neuron.w_max, wta_k=c0.wta.k,
            mu_capture=c0.stdp.mu_capture, mu_backoff=c0.stdp.mu_backoff,
            mu_search=c0.stdp.mu_search,
            stabilize=c0.stdp.stabilizer == "half",
            response=c0.neuron.response, epochs=EPOCHS, lowering=lowering,
        )
        jax.block_until_ready(w)

    def legacy():
        # per-design fused fits on the SAME engine the padded path uses
        # (kernel on TPU, reference off-TPU): D traces, no shared envelope,
        # so the row isolates one-trace-vs-D-traces + padding waste.
        xj = jnp.asarray(x, TIME_DTYPE)
        for i, c in enumerate(cfgs):
            p2, _ = fused_column.fit_fused(
                {"w": jnp.asarray(w0[i, :, : c.q])}, xj, c, epochs=EPOCHS,
                lowering=lowering,
            )
            jax.block_until_ready(p2["w"])

    # cold first calls: the padded program compiles ONE trace for the whole
    # heterogeneous batch (runtime design operands), the legacy loop one
    # trace per design — the compilation cliff this path removes.  The
    # cache label is sampled by ``main`` BEFORE anything runs (operand
    # setup already writes tiny dtype-cast modules into a fresh dir, so a
    # per-row sample would always read 'populated'): these numbers only
    # mean "compile" when it says the run STARTED fresh or uncached.
    if cache is None:
        cache = cache_state(backend.compile_cache_dir())
    volleys = EPOCHS * SWEEP_B * d
    cold_padded_us = time_cold(padded)
    cold_legacy_us = time_cold(legacy)

    # alternating rounds: the warm fused-vs-legacy ratio is the ISSUE 4
    # acceptance bar, so neither side may soak up host drift alone
    us_padded, us_legacy = time_pair(padded, legacy)

    # plan-vs-constants head-to-head (ISSUE 10 acceptance bar): the SAME
    # entry point, once under the active calibration and once with the
    # cost model suppressed so the constants fallback resolves
    def padded_const():
        with costmodel.override(None):
            padded()

    us_plan, us_const = time_pair(padded, padded_const)
    mxu_flops = sum(
        2 * (c.neuron.w_max + 1) * c.p * c.q * c.t_max for c in cfgs
    ) // d
    return {
        "case": f"sweep{d}x{SWEEP_P}p",
        "backend": "pallas",
        "lowering": lowering,
        "v_blk": plan.v_blk,
        "plan": plan.meta(),
        "plan_us_per_volley": us_plan / volleys,
        "const_us_per_volley": us_const / volleys,
        "plan_vs_const_speedup": us_const / max(us_plan, 1e-9),
        # predicted vs measured per SCAN volley (one volley spans all d
        # designs — the unit predicted_step_s is defined in)
        "predicted_measured_ratio": (
            plan.predicted_step_s * 1e6
            / max(us_plan / (EPOCHS * SWEEP_B), 1e-9)
            if plan.predicted_step_s else None
        ),
        "compile_cache": cache,
        "buckets": 1,  # one shared envelope: these designs fit the cap
        # this case drives fit_scan_padded directly — sharding happens in
        # the simulator front-end only (see sweepbkt), so this row is 1
        "shards": 1,
        "fused_us_per_volley": us_padded / volleys,
        "legacy_us_per_volley": us_legacy / volleys,
        "speedup": us_legacy / max(us_padded, 1e-9),
        "cold_us_per_volley": cold_padded_us / volleys,
        "cold_legacy_us_per_volley": cold_legacy_us / volleys,
        "cold_speedup": cold_legacy_us / max(cold_padded_us, 1e-9),
        "traces": 1,
        "legacy_traces": d,
        "mxu_flops_per_volley": mxu_flops,
    }


# ------------------------------------------------------ bucketed sweep (DSE)
BKT_B = 64  # volleys per epoch
BKT_P = 96
# heterogeneous candidates a DSE pass actually produces: two tiny read-out
# sized designs next to two big ones — a single global envelope makes the
# small designs pay (10*64)/(2*32) = 10x padding compute on every volley,
# so the central waste cap splits them into two buckets
BKT_DESIGNS = [(2, 32), (2, 32), (10, 64), (10, 64)]


def run_bucketed_sweep(cache: str | None = None) -> dict:
    """Envelope-bucketed heterogeneous sweep (the ISSUE 5 tentpole) vs the
    same sweep forced into one global envelope (waste_cap=inf — the
    pre-bucketing behavior).  Both sides run the full simulator front-end
    (encode + blocked fit + batched assign), so the row measures what a
    DSE pass actually pays; 'buckets'/'shards' record how the bucketed
    side executed."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(BKT_B, BKT_P))
    cfgs = []
    for q, t_max in BKT_DESIGNS:
        c = ColumnConfig(p=BKT_P, q=q, t_max=t_max)
        cfgs.append(c.with_threshold(simulator.suggest_threshold(c)))
    d = len(cfgs)

    def bucketed():
        simulator.cluster_time_series_many(x, None, cfgs, epochs=EPOCHS)

    def global_env():
        simulator.cluster_time_series_many(
            x, None, cfgs, epochs=EPOCHS, waste_cap=float("inf")
        )

    # cold first calls: bucketing compiles one trace per distinct bucket
    # envelope (2 here) vs the global envelope's single bigger trace
    if cache is None:
        cache = cache_state(backend.compile_cache_dir())
    volleys = EPOCHS * BKT_B * d
    cold_bkt_us = time_cold(bucketed)
    cold_glb_us = time_cold(global_env)

    us_bkt, us_glb = time_pair(bucketed, global_env)

    # plan-vs-constants head-to-head through the full front-end: the
    # simulator resolves its buckets' plans internally, so the constants
    # side just suppresses the cost model for the duration
    def bucketed_const():
        with costmodel.override(None):
            bucketed()

    us_plan, us_const = time_pair(bucketed, bucketed_const)
    res = simulator.cluster_time_series_many(x, None, cfgs, epochs=EPOCHS)
    lowering = res[0].lowering
    plan_meta = res[0].plan
    mxu_flops = sum(
        2 * (c.neuron.w_max + 1) * c.p * c.q * c.t_max for c in cfgs
    ) // d
    return {
        "case": f"sweepbkt{d}x{BKT_P}p",
        "backend": "pallas",
        "lowering": lowering,
        # the first bucket's resolved block size — under the constants
        # fallback both 2-design buckets get the d-aware reference cap
        # (v_blk=4, not the homogeneous-sweep 8); a calibration may choose
        # differently, and the full choice is in 'plan'
        "v_blk": (
            plan_meta["v_blk"] if plan_meta
            else backend.volley_block(lowering, BKT_B, d=2)
        ),
        "plan": plan_meta,
        "plan_us_per_volley": us_plan / volleys,
        "const_us_per_volley": us_const / volleys,
        "plan_vs_const_speedup": us_const / max(us_plan, 1e-9),
        # fit-only prediction vs END-TO-END measurement (encode + fit +
        # assign): an upper-bound sanity ratio, not a fit-time error
        "predicted_measured_ratio": (
            plan_meta["predicted_step_us"]
            / max(us_plan / (EPOCHS * BKT_B), 1e-9)
            if plan_meta and plan_meta.get("predicted_step_us") else None
        ),
        "compile_cache": cache,
        "buckets": res[0].buckets,
        "shards": max(r.shards for r in res),
        # fused = bucketed, legacy = single global envelope
        "fused_us_per_volley": us_bkt / volleys,
        "legacy_us_per_volley": us_glb / volleys,
        "speedup": us_glb / max(us_bkt, 1e-9),
        "cold_us_per_volley": cold_bkt_us / volleys,
        "cold_legacy_us_per_volley": cold_glb_us / volleys,
        "cold_speedup": cold_glb_us / max(cold_bkt_us, 1e-9),
        "traces": res[0].buckets,
        "legacy_traces": 1,
        "mxu_flops_per_volley": mxu_flops,
    }


# ---------------------------------------------------- multi-layer network
NET_B = 64  # volleys per epoch


def _net_cfg() -> NetworkConfig:
    """2-layer NSPU: 4 fully-connected 96x8 columns feeding one 32x5."""

    def col(p, q, t_max=64):
        return ColumnConfig(
            p=p, q=q, t_max=t_max, neuron=NeuronConfig(threshold=p * 7 / 8.0)
        )

    return NetworkConfig(layers=(
        LayerConfig(columns=4, column=col(96, 8)),
        LayerConfig(columns=1, column=col(32, 5)),
    ), name="bench2layer")


def run_network(cache: str | None = None) -> dict:
    """Fused per-layer scans (network.fit_greedy) vs the legacy untraced
    per-epoch Python loop they replaced (one vmapped train_step per epoch)."""
    net = _net_cfg()
    rng = np.random.default_rng(1)
    in_width = network.in_width(net)
    params = [
        {
            "w": jnp.asarray(
                rng.integers(
                    0, 8, (l.columns, l.column.p, l.column.q)
                ),
                jnp.float32,
            )
        }
        for l in net.layers
    ]
    x = jnp.asarray(
        rng.integers(0, net.layers[0].column.t_max, (NET_B, in_width)),
        jnp.int32,
    )

    def fused():
        trained = network.fit_greedy(params, x, net, epochs=EPOCHS)
        jax.block_until_ready(trained[-1]["w"])

    def legacy():
        # the pre-fusion fit_greedy: Python epochs loop, per-epoch dispatch
        h = x
        key = jax.random.key(0)
        for lp, layer in zip(params, net.layers):
            c = layer.columns
            hc = jnp.broadcast_to(
                h[..., None, :], h.shape[:-1] + (c, h.shape[-1])
            )
            w = lp["w"]
            for _ in range(EPOCHS):
                key, sub = jax.random.split(key)
                keys = jax.random.split(sub, c)

                def one(wi, xi, ki):
                    p2, _ = column.train_step(
                        {"w": wi}, xi, layer.column, rng=ki
                    )
                    return p2["w"]

                w = jax.vmap(one, in_axes=(0, -2, 0))(w, hc, keys)
            h = network._apply_layer({"w": w}, h, layer, "auto")
        jax.block_until_ready(h)

    # cold first calls: the compile cliff of the blocked per-layer scans vs
    # the legacy per-epoch dispatch loop
    if cache is None:
        cache = cache_state(backend.compile_cache_dir())
    volleys = EPOCHS * NET_B
    cold_fused_us = time_cold(fused)
    cold_legacy_us = time_cold(legacy)

    # alternating rounds, same rationale as run_sweep
    us_fused, us_legacy = time_pair(fused, legacy)

    # plan-vs-constants head-to-head on the fused side only (the
    # constants fallback resolves when the cost model is suppressed)
    def fused_const():
        with costmodel.override(None):
            fused()

    us_plan, us_const = time_pair(fused, fused_const)
    # one more (warm) training pass to capture the per-layer plans the
    # timed runs resolved to
    layer_plans: list = []
    network.fit_greedy(params, x, net, epochs=EPOCHS, plan_sink=layer_plans)
    mxu_flops = sum(
        l.columns * 2 * (l.column.neuron.w_max + 1)
        * l.column.p * l.column.q * l.column.t_max
        for l in net.layers
    )
    lowering = backend.padded_lowering(net.layers[0].column.neuron.response)
    return {
        "case": "net96-4x8-1x5",
        "backend": backend.resolve(
            "auto", net.layers[0].column, training=True
        ),
        # the padded per-layer scan lowers through backend.padded_lowering:
        # Mosaic kernel on TPU (runtime design operands), reference off-TPU
        "lowering": lowering,
        # per-layer resolved block sizes — under the constants fallback
        # the d-aware reference cap unrolls 8 volleys for the 4-column
        # layer but only 2 for the single-column read-out layer
        "v_blk": (
            [p["v_blk"] for p in layer_plans] if layer_plans
            else [
                backend.volley_block(lowering, NET_B, d=l.columns)
                for l in net.layers
            ]
        ),
        "plan": {"layers": layer_plans},
        "plan_us_per_volley": us_plan / volleys,
        "const_us_per_volley": us_const / volleys,
        "plan_vs_const_speedup": us_const / max(us_plan, 1e-9),
        # per-layer fit predictions sum to a per-volley bound for the
        # whole greedy pass; measured includes the layer handoffs
        "predicted_measured_ratio": (
            sum(p["predicted_step_us"] for p in layer_plans)
            / max(us_plan / (EPOCHS * NET_B), 1e-9)
            if layer_plans
            and all(p.get("predicted_step_us") for p in layer_plans)
            else None
        ),
        "compile_cache": cache,
        # per-layer envelopes: both layers get their own bucket (the 96x8
        # and 32x5 columns are outside the waste cap of each other);
        # network layer training does not shard its columns axis, so 1
        "buckets": len(set(network._fused_envelopes(list(net.layers)))),
        "shards": 1,
        "fused_us_per_volley": us_fused / volleys,
        "legacy_us_per_volley": us_legacy / volleys,
        "speedup": us_legacy / max(us_fused, 1e-9),
        "cold_us_per_volley": cold_fused_us / volleys,
        "cold_legacy_us_per_volley": cold_legacy_us / volleys,
        "cold_speedup": cold_legacy_us / max(cold_fused_us, 1e-9),
        "mxu_flops_per_volley": mxu_flops,
    }


# the padded cases whose floors CI tracks (``--check``)
TRACKED_COLD_CASES = ("sweep4x96p", "sweepbkt4x96p", "net96-4x8-1x5")


def check() -> int:
    """Validate the committed ``BENCH_train.json`` floors (CI smoke):
    every tracked padded case must hold warm speedup >=
    WARM_REGRESSION_MIN and fresh-cache cold speedup >=
    COLD_REGRESSION_MIN.  Returns a nonzero exit status on any miss so
    the workflow step fails loudly."""
    path = pathlib.Path("BENCH_train.json")
    rows = {r["case"]: r for r in json.loads(path.read_text())}
    failed = 0
    for case in TRACKED_COLD_CASES:
        r = rows.get(case)
        if r is None:
            print(f"CHECK-FAIL: tracked case {case} missing from {path}")
            failed = 1
            continue
        floors = [
            ("warm speedup", r.get("speedup"), WARM_REGRESSION_MIN),
            ("cold speedup (fresh cache)", r.get("cold_speedup"),
             COLD_REGRESSION_MIN),
            ("plan-vs-constants warm speedup",
             r.get("plan_vs_const_speedup"), PLAN_REGRESSION_MIN),
        ]
        for label, val, floor in floors:
            if val is None or val < floor:
                print(
                    f"CHECK-FAIL: {case} {label} "
                    f"{'missing' if val is None else f'{val:.2f}x'} "
                    f"< {floor}x floor"
                )
                failed = 1
    if not failed:
        print(f"train bench floors OK for {', '.join(TRACKED_COLD_CASES)}")
    return failed


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--check", action="store_true",
        help="validate the committed BENCH_train.json floors and exit",
    )
    args = ap.parse_args(argv)
    if args.check:
        raise SystemExit(check())
    cache_dir = backend.compile_cache()
    # sample the label ONCE, before anything compiles: it describes the
    # state the run started from, which is what makes cold rows honest
    cache = cache_state(cache_dir)
    # activate the device calibration AFTER the cache dir is resolved: the
    # calibration persists NEXT TO the compile cache (calibration.json), so
    # later runs load the SAME profile -> resolve the SAME plans -> hit the
    # SAME AOT keys.  A fresh dir calibrates once, before any timed region.
    try:
        prof = costmodel.load_or_calibrate()
        print(
            f"device calibration: {prof.name} "
            f"(peak={prof.peak_flops:.3g} FLOP/s, bw={prof.hbm_bw:.3g} B/s, "
            f"fused_eff={prof.fused_eff:.2f})"
        )
    except Exception as e:  # constants fallback is always available
        print(f"device calibration unavailable ({e!r}); constants fallback")
    if cache_dir:
        print(f"persistent compile cache: {cache_dir} ({cache})")
    rows = run()
    rows.append(run_sweep(cache=cache))
    rows.append(run_bucketed_sweep(cache=cache))
    rows.append(run_network(cache=cache))
    print("\n# Fused online-STDP training vs legacy per-epoch loop")
    print("| case | backend | fused us/volley | legacy us/volley | speedup | MXU flops/volley |")
    print("|---|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['case']} | {r['backend']}/{r['lowering']} | "
              f"{r['fused_us_per_volley']:.1f} | {r['legacy_us_per_volley']:.1f} | "
              f"{r['speedup']:.2f}x | {r['mxu_flops_per_volley']:.2e} |")
    out = pathlib.Path("BENCH_train.json")
    out.write_text(json.dumps(rows, indent=2) + "\n")
    print(f"wrote {out.resolve()}")
    for r in rows:
        emit(f"train/{r['case']}", r["fused_us_per_volley"],
             f"speedup={r['speedup']:.2f}x flops={r['mxu_flops_per_volley']:.2e}")
    # warm throughput is the ISSUE 4 acceptance bar: a fused case that only
    # wins the compile cliff is a regression, and says so loudly
    for r in rows:
        if r["speedup"] < WARM_REGRESSION_MIN:
            print(
                f"REGRESSION: {r['case']} warm fused speedup "
                f"{r['speedup']:.2f}x < {WARM_REGRESSION_MIN}x floor vs legacy "
                f"({r['fused_us_per_volley']:.1f} vs "
                f"{r['legacy_us_per_volley']:.1f} us/volley, "
                f"lowering={r['lowering']})"
            )
    # cold (first-call, compile-inclusive) time is tracked too: the fused
    # paths may trade SOME cold time for warm throughput, but below the
    # tracked floor the compile cliff is a real usability regression and
    # must be loud, not a silent JSON column
    for r in rows:
        cold = r.get("cold_speedup")
        if cold is not None and cold < COLD_REGRESSION_MIN:
            print(
                f"COLD-REGRESSION: {r['case']} cold fused speedup "
                f"{cold:.2f}x < {COLD_REGRESSION_MIN}x floor vs legacy "
                f"({r['cold_us_per_volley']:.1f} vs "
                f"{r['cold_legacy_us_per_volley']:.1f} us/volley cold, "
                f"lowering={r['lowering']}, "
                f"compile_cache={r.get('compile_cache', 'off')})"
            )
    # the cost model may only ever trade within warm parity: a plan that
    # loses real warm throughput against the constants it replaced is a
    # regression in the one metric the chooser optimizes
    for r in rows:
        pvc = r.get("plan_vs_const_speedup")
        if pvc is not None and pvc < PLAN_REGRESSION_MIN:
            print(
                f"PLAN-REGRESSION: {r['case']} plan-vs-constants warm "
                f"speedup {pvc:.2f}x < {PLAN_REGRESSION_MIN}x floor "
                f"({r['plan_us_per_volley']:.1f} vs "
                f"{r['const_us_per_volley']:.1f} us/volley, "
                f"plan={r.get('plan')})"
            )


if __name__ == "__main__":
    main()
