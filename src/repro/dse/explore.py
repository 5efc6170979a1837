"""Design-space exploration: bucketed sweep -> silicon forecast -> Pareto.

This is the paper's headline loop closed end to end: grid or random
search over (q, t_max, threshold, encoder) runs through the functional
simulator's envelope-bucketed, device-sharded design sweep
(``simulator.cluster_time_series_many``), each design's clustering
quality is paired with forecasted post-layout area/leakage from its
synapse count (``repro.hwgen.forecast`` — the TNN7 regression by
default), and the result is a Pareto frontier of Rand index vs silicon
cost — no hardware flow run required.

Exploration is built for *long* runs: evaluations are fault-isolated by
default (one degenerate candidate is quarantined as an ``EvalFailure``
record in ``meta['failures']`` instead of aborting the sweep, with
kernel-path failures retried down the central lowering-degradation
ladder), per-bucket wall times are watched for stalls
(``distributed.straggler.StepMonitor``), and passing ``journal=`` makes
every completed bucket durable so ``resume=True`` after a kill
re-evaluates only the missing candidates — bit-identical to an
uninterrupted run.  See ``docs/dse.md``.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Optional, Union

import jax
import numpy as np

from repro import obs
from repro.core import backend as backend_lib
from repro.core import column as column_lib
from repro.core import simulator
from repro.core import stdp as stdp_lib
from repro.distributed.straggler import StepMonitor
from repro.dse import journal as journal_lib
from repro.dse.pareto import DesignPoint, pareto_front
from repro.dse.space import Candidate, DesignSpace, candidate_config
from repro.roofline import costmodel

# Counters ``explore`` records (``repro.obs.count``, while a profiler
# runs): per encoder group, the candidates whose initial weights (and
# stream keys) were drawn, and the programs that drew them (one per
# candidate shape).
INIT_DESIGNS = "dse.init_designs"
INIT_RUNS = "dse.init_runs"
DSE_COUNTERS = (INIT_DESIGNS, INIT_RUNS)


@dataclasses.dataclass
class DSEResult:
    """Outcome of one exploration run.

    ``points`` holds every *scored* candidate in explore order (a
    quarantined candidate has no point); ``pareto`` the nondominated
    subset (Rand index up, forecasted area and leakage down),
    cheapest-area first.  ``meta`` records how the sweep executed,
    per encoder group where applicable:

      * ``'buckets'`` / ``'lowering'`` — dicts keyed by encoder: the
        bucket count and the comma-joined lowerings that actually ran
        for that group (every group, not just the last one).
      * ``'failures'`` — one dict per quarantined candidate (index,
        encoder, stage, error, lowerings attempted, retries); empty on
        a clean run.  ``'quarantined'`` is its length.
      * ``'retries'`` / ``'fallbacks'`` — total failed ladder-rung
        attempts across the run, and how many scored designs ran on a
        degraded lowering.
      * ``'stalls'`` — straggler events (bucket wall-time outliers)
        flagged by the step monitor.
      * ``'resumed'`` — candidates restored from the journal instead of
        re-evaluated (0 without ``resume=True``).
    """

    points: list[DesignPoint]
    pareto: list[DesignPoint]
    seconds: float
    meta: dict

    def best(self) -> DesignPoint:
        """Highest Rand index per forecasted area — the NSPU design
        objective the example sweeps optimize.

        Raises a diagnostic ``ValueError`` when the frontier is empty:
        either nothing was scored (all candidates quarantined — the
        error says how many and points at ``meta['failures']``) or the
        stream was unlabeled (NaN Rand indices rank nothing).
        """
        if not self.pareto:
            quarantined = len(self.meta.get("failures", ()))
            detail = (
                f"{quarantined} candidate(s) quarantined — see "
                "DSEResult.meta['failures']"
                if quarantined
                else "was the stream labeled? NaN Rand indices rank nothing"
            )
            raise ValueError(
                f"empty Pareto frontier: {len(self.points)} of "
                f"{len(self.points) + quarantined} candidate(s) scored; "
                + detail
            )
        return max(self.pareto, key=lambda p: p.rand_index / p.area_um2)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _draw(init_key, stream_root, idxs, cfg):
    """Initial weights [k, p, q] of candidates ``idxs`` [k] of one shape,
    ``init_params`` of ``fold_in(init_key, i)``, and under stochastic STDP
    their stream keys [k, 2], ``stream_key`` of ``fold_in(stream_root, i)``
    (None otherwise): per candidate exactly the draws made one by one."""

    def one(i):
        w = column_lib.init_params(jax.random.fold_in(init_key, i), cfg)["w"]
        if cfg.stdp.mode != "stochastic":
            return w, None
        return w, stdp_lib.stream_key(jax.random.fold_in(stream_root, i))

    return jax.vmap(one)(idxs)


def _draw_inits(init_key, stream_root, idxs, cfgs):
    """The initial weights of candidates ``idxs`` (configs ``cfgs``) and,
    under stochastic STDP, their stream keys (else None), as host arrays in
    ``idxs`` order: one ``_draw`` program per candidate shape, one fetch.

    The program is keyed on the config with threshold and window zeroed,
    which the draw never reads, so candidates that differ only there share
    it."""
    groups: dict = {}
    for j, c in enumerate(cfgs):
        key = dataclasses.replace(c.with_threshold(0.0), t_max=0)
        groups.setdefault(key, []).append(j)
    drawn = jax.device_get([
        _draw(
            init_key, stream_root,
            np.asarray([idxs[j] for j in js], np.int32), cfg,
        )
        for cfg, js in groups.items()
    ])
    obs.count(INIT_DESIGNS, len(idxs))
    obs.count(INIT_RUNS, len(groups))
    w_init: list = [None] * len(idxs)
    keys: list = [None] * len(idxs)
    for js, (w, k) in zip(groups.values(), drawn):
        for r, j in enumerate(js):
            w_init[j] = w[r]
            keys[j] = None if k is None else k[r]
    return w_init, None if keys[0] is None else keys


def explore(
    series: np.ndarray,
    labels: Optional[np.ndarray],
    space: DesignSpace,
    epochs: int = 4,
    search: str = "grid",
    budget: Optional[int] = None,
    seed: int = 0,
    forecaster=None,
    waste_cap: Optional[float] = None,
    max_bucket: Optional[int] = None,
    on_error: str = "isolate",
    journal: Union[str, journal_lib.Journal, None] = None,
    resume: bool = False,
    monitor: Optional[StepMonitor] = None,
) -> DSEResult:
    """Explore a column design space over one stream, silicon-forecasted.

    Args:
      series: [N, L] real-valued stream (N >= 1; an empty stream raises).
      labels: [N] ground-truth classes; required — the Pareto frontier
        ranks on the Rand index, which needs labels.
      space: the axes to search (see ``DesignSpace``).
      epochs: STDP passes per design.
      search: 'grid' (the full cross product) or 'random' (``budget``
        uniform draws from it, deterministic per ``seed``).
      budget: candidate cap; required for 'random', optional for 'grid'
        (truncates the deterministic grid order).
      seed: feeds candidate sampling, per-design weight init and, for a
        stochastic-STDP space, each design's stream key, so equal seeds
        reproduce the exploration exactly.  Init weights and stream keys
        are keyed by (seed, candidate index) — never by sweep position —
        so results are invariant to grouping, bucketing, sharding and
        resume subsets.
      forecaster: any object with ``area_um2(synapses)`` /
        ``leakage_uw(synapses)`` — ``hwgen.forecast.PaperForecaster``
        (TNN7 regression) by default; pass a refit
        ``hwgen.forecast.Forecaster`` to use an accumulated design
        database instead.
      waste_cap / max_bucket: envelope-bucketing knobs forwarded to
        ``cluster_time_series_many`` (None defers to central policy).
      on_error: 'isolate' (default) quarantines failing candidates as
        ``EvalFailure`` records in ``meta['failures']`` and keeps
        sweeping, retrying kernel-path failures down the lowering
        ladder; 'raise' propagates the first failure (debugging).
      journal: path (or ``Journal``) to an append-only evaluation
        journal; every completed bucket is published atomically, so a
        killed run loses at most one bucket.  An existing journal
        requires ``resume=True``.  Journaled runs also enable the
        persistent compilation cache (``backend.compile_cache()``:
        ``JAX_COMPILATION_CACHE_DIR`` when set, the checkout's
        ``.jax_cache`` otherwise), so resumed and repeated runs compile
        zero envelope traces.
      resume: skip candidates already in the journal (scored *and*
        quarantined); the resumed run's frontier is bit-identical to an
        uninterrupted one.
      monitor: optional ``StepMonitor`` override for stall detection
        (a fresh one per run by default); its events land in
        ``meta['stalls']``.

    Candidates sharing an encoder sweep together (the encoder pins the
    input width); within each encoder group the sweep is envelope-bucketed
    and design-sharded by the central backend policy.

    Returns a ``DSEResult`` whose ``pareto`` pairs each surviving design's
    Rand index with its forecasted area/leakage.
    """
    if labels is None:
        raise ValueError(
            "explore ranks designs on the Rand index; labels are required"
        )
    if forecaster is None:
        from repro.hwgen.forecast import PaperForecaster

        forecaster = PaperForecaster()

    if search == "grid":
        candidates = space.grid()
        if budget is not None:
            candidates = candidates[: int(budget)]
    elif search == "random":
        if budget is None:
            raise ValueError("search='random' needs a candidate budget")
        candidates = space.sample(budget, seed=seed)
    else:
        raise ValueError(f"unknown search: {search!r} (grid | random)")

    with obs.span("dse.explore", candidates=len(candidates)):
        series = np.asarray(series)
        n_cand = len(candidates)
        cfgs_all = [
            candidate_config(c, series.shape[1], space.stdp)
            for c in candidates
        ]
        fps = [
            journal_lib.candidate_fingerprint(cfg, c.encoder, seed, epochs)
            for cfg, c in zip(cfgs_all, candidates)
        ]

        jr = journal
        if jr is not None and not isinstance(jr, journal_lib.Journal):
            jr = journal_lib.Journal(jr)
        restored: dict = {}
        if jr is not None:
            restored = jr.begin(
                journal_lib.run_meta(seed, epochs, search, space.stdp),
                resume=resume,
            )
            # journaled runs are the long-lived ones: enable the persistent
            # compilation cache (``backend.default_cache_dir()``), so a resumed
            # (or merely repeated) exploration re-pays ZERO envelope compiles.
            # A deleted cache dir is recreated (re-enabling the default repairs
            # it, even mid-process); a cache a test enabled explicitly wins.
            if backend_lib.compile_cache_dir() in (
                None, os.path.abspath(backend_lib.default_cache_dir())
            ):
                backend_lib.compile_cache()
            # a device calibration saved next to the cache (costmodel.calibrate
            # once per host) upgrades every policy seam below from the
            # hand-tuned constants to the roofline plan.  Disk-load only —
            # exploration never probes the device itself, so an uncalibrated
            # host just keeps the constants fallback.
            try:
                costmodel.load_profile()
            except Exception:
                pass
        mon = monitor if monitor is not None else StepMonitor(
            threshold=4.0, warmup=3
        )

        points: list[Optional[DesignPoint]] = [None] * n_cand
        failures: list[dict] = []
        resumed = 0
        pending: list[int] = []
        for i, (cand, cfg, fp) in enumerate(zip(candidates, cfgs_all, fps)):
            rec = restored.get(fp)
            if rec is None:
                pending.append(i)
                continue
            resumed += 1
            if rec["kind"] == "point":
                points[i] = DesignPoint(
                    index=i,
                    cfg=cfg,
                    encoder=cand.encoder,
                    rand_index=float(rec["rand_index"]),
                    synapses=int(rec["synapses"]),
                    area_um2=float(rec["area_um2"]),
                    leakage_uw=float(rec["leakage_uw"]),
                    params={"w": np.asarray(rec["w"], np.float32)},
                    lowering=rec.get("lowering", ""),
                    buckets=int(rec.get("buckets", 1)),
                    shards=int(rec.get("shards", 1)),
                    fingerprint=fp,
                    retries=int(rec.get("retries", 0)),
                    plan=rec.get("plan"),
                )
            else:
                failures.append(
                    {
                        "index": i,
                        "encoder": cand.encoder,
                        "stage": rec.get("stage", ""),
                        "error": rec.get("error", ""),
                        "lowerings": list(rec.get("lowerings", ())),
                        "retries": int(rec.get("retries", 0)),
                        "restored": True,
                    }
                )

        # init weights (and stochastic STDP's stream keys) keyed per
        # CANDIDATE index (fold_in), not per sweep position: a resumed
        # partial sweep hands every design the same init and stream the
        # full sweep would have, so resume is bit-identical
        stream_root, init_key = jax.random.split(jax.random.key(seed))

        t0 = time.perf_counter()
        for encoder in dict.fromkeys(candidates[i].encoder for i in pending):
            idxs = [i for i in pending if candidates[i].encoder == encoder]
            cfgs = [cfgs_all[i] for i in idxs]
            with obs.span("dse.init"):
                w_init, stream_keys = _draw_inits(
                    init_key, stream_root, idxs, cfgs
                )

            def on_bucket(local_idxs, results, idxs=idxs, encoder=encoder):
                with obs.span("dse.record"):
                    recs = []
                    for li, r in zip(local_idxs, results):
                        gi = idxs[li]
                        if isinstance(r, simulator.EvalFailure):
                            f = {
                                "index": gi,
                                "encoder": encoder,
                                "stage": r.stage,
                                "error": r.error,
                                "lowerings": list(r.lowerings),
                                "retries": r.retries,
                            }
                            failures.append({**f, "restored": False})
                            recs.append(
                                {"kind": "failure", "fp": fps[gi], **f}
                            )
                            continue
                        syn = cfgs_all[gi].synapse_count
                        p = DesignPoint(
                            index=gi,
                            cfg=cfgs_all[gi],
                            encoder=encoder,
                            rand_index=r.rand_index,
                            synapses=syn,
                            area_um2=float(forecaster.area_um2(syn)),
                            leakage_uw=float(forecaster.leakage_uw(syn)),
                            params=r.params,
                            lowering=r.lowering,
                            buckets=r.buckets,
                            shards=r.shards,
                            fingerprint=fps[gi],
                            retries=r.retries,
                            plan=r.plan,
                        )
                        points[gi] = p
                        recs.append(
                            {
                                "kind": "point",
                                "fp": fps[gi],
                                "index": gi,
                                "encoder": encoder,
                                "cand": dataclasses.asdict(candidates[gi]),
                                "rand_index": p.rand_index,
                                "synapses": p.synapses,
                                "area_um2": p.area_um2,
                                "leakage_uw": p.leakage_uw,
                                "lowering": p.lowering,
                                "buckets": p.buckets,
                                "shards": p.shards,
                                "retries": p.retries,
                                "plan": p.plan,
                                "w": np.asarray(
                                    r.params["w"], np.float32
                                ).tolist(),
                            }
                        )
                    if jr is not None:
                        jr.append(recs)

            simulator.cluster_time_series_many(
                series, labels, cfgs, epochs=epochs, seed=seed,
                encoder=encoder, waste_cap=waste_cap, max_bucket=max_bucket,
                on_error=on_error, w_init=w_init, bucket_callback=on_bucket,
                monitor=mon, stream_keys=stream_keys,
            )
        seconds = time.perf_counter() - t0

        done = [p for p in points if p is not None]
        encoders = list(dict.fromkeys(c.encoder for c in candidates))
        lowering_by_encoder = {
            e: ",".join(
                sorted({
                    p.lowering for p in done if p.encoder == e and p.lowering
                })
            )
            for e in encoders
            if any(p.encoder == e for p in done)
        }
        buckets_by_encoder = {
            e: max(p.buckets for p in done if p.encoder == e)
            for e in encoders
            if any(p.encoder == e for p in done)
        }
        with obs.span("dse.pareto"):
            pareto = pareto_front(done)
        return DSEResult(
            points=done,
            pareto=pareto,
            seconds=seconds,
            meta={
                "search": search,
                "candidates": len(done),
                "buckets": buckets_by_encoder,
                "shards": max((p.shards for p in done), default=1),
                "lowering": lowering_by_encoder,
                "epochs": epochs,
                "seed": seed,
                "on_error": on_error,
                "failures": failures,
                "quarantined": len(failures),
                "retries": (
                    sum(p.retries for p in done)
                    + sum(f["retries"] for f in failures)
                ),
                "fallbacks": sum(1 for p in done if p.retries > 0),
                "stalls": [dataclasses.asdict(ev) for ev in mon.events],
                "resumed": resumed,
                "journal": jr.path if jr is not None else None,
                # '' = constants fallback; otherwise the calibrated
                # DeviceProfile whose cost model chose every bucket's blocking
                "profile": getattr(costmodel.profile(), "name", ""),
            },
        )


def summarize(result: DSEResult) -> str:
    """Human-readable frontier table (the example prints this)."""
    meta = result.meta
    lines = [
        f"{len(result.points)} designs explored in {result.seconds:.2f}s "
        f"(buckets={meta['buckets']}, shards={meta['shards']}, "
        f"lowering={meta['lowering']})",
    ]
    if meta.get("quarantined"):
        by_stage: dict[str, int] = {}
        for f in meta["failures"]:
            by_stage[f["stage"]] = by_stage.get(f["stage"], 0) + 1
        lines.append(
            f"{meta['quarantined']} candidate(s) quarantined "
            f"({', '.join(f'{k}: {v}' for k, v in sorted(by_stage.items()))})"
            " — see meta['failures']"
        )
    if meta.get("resumed"):
        lines.append(f"{meta['resumed']} candidate(s) restored from journal")
    if meta.get("stalls"):
        lines.append(f"{len(meta['stalls'])} stalled bucket(s) flagged")
    lines.append("Pareto frontier (Rand index vs forecasted TNN area/leakage):")
    for p in result.pareto:
        lines.append(
            f"  enc={p.encoder:7s} q={p.cfg.q:3d} t_max={p.cfg.t_max:4d} "
            f"th={p.cfg.neuron.threshold:7.1f}  RI={p.rand_index:.3f}  "
            f"syn={p.synapses:6d}  area={p.area_um2:9.0f} um^2  "
            f"leak={p.leakage_uw:7.2f} uW"
        )
    return "\n".join(lines)
