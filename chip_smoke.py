#!/usr/bin/env python3
"""Smoke run of the TNN system's main paths on a TPU.

    python chip_smoke.py            # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4  # four chips: the sharded design sweep

Phases, all in this one process (an accelerator belongs to one process at
a time), on the synthetic doubles of the paper's Table II datasets
(``repro.data.ucr``) with a fixed seed, at the paper's column widths:

  (a) device — platform, ``device_kind`` and device count; anything but a
      TPU fails here (there is no CPU fallback);
  (b) single column — ``simulator.cluster_time_series(mode='pallas')`` on
      Lightning2 (637x2) and WordSynonyms (270x25), plus an integer-grid
      variant whose fused fit and assignments must be bit-identical to the
      cycle-accurate solver;
  (c) design sweep — ``simulator.cluster_time_series_many`` over the seven
      Table II designs (threshold variants share each design's stream);
      the integer-grid variant must be bit-identical to the reference
      lowering of ``backend.fit_padded`` / ``assign_padded`` on this chip;
  (d) service — a ``ClusteringService`` over the seven designs serves a
      few hundred requests with online re-fits; every answer must equal
      ``simulator.assign_time_series`` on the weights live at the time.

With ``--chips 4`` only the sharded sweep runs: four designs per bucket
sharded over the four devices, checked bit-identical to the same sweep on
one device.  Any step down from the expected lowering, any retry and any
failure fails the run.

Each phase prints its wall time, compilation included: a smoke timing,
not a benchmark.  Compiles go to the persistent cache
(``backend.compile_cache()``: ``JAX_COMPILATION_CACHE_DIR`` when set, the
checkout's ``.jax_cache`` otherwise), so a second run compiles nothing.
On success the last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
on failure the run exits nonzero without printing it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

TABLE2 = (
    "SonyAIBORobotSurface2", "ECG200", "Wafer", "ToeSegmentation2",
    "Lightning2", "Beef", "WordSynonyms",
)
SEED = 0
T_MAX = 64


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _timed(label: str, fn, *args, **kwargs):
    """Run one phase; print its smoke timing and its compile count."""
    from repro.testing import count_compiles

    t0 = time.perf_counter()
    with count_compiles() as compiles:
        out = fn(*args, **kwargs)
    dt = time.perf_counter() - t0
    print(
        f"{label}: {dt:.3f} s wall, {compiles.compiles} XLA compiles "
        "(smoke timing incl. compilation; not a benchmark)",
        flush=True,
    )
    return out


def integer_grid(cfg):
    """The design with unstabilized, integer-step STDP: trained from
    integer weights, its weights never leave the integer grid, so every
    lowering and the cycle-accurate solver must agree bit for bit."""
    from repro.core.types import STDPConfig

    return dataclasses.replace(cfg, stdp=STDPConfig(
        mu_capture=1.0, mu_backoff=1.0, mu_search=1.0, stabilizer="none",
    ))


def _integer_weights(rng, cfg):
    import numpy as np

    return rng.integers(0, cfg.neuron.w_max + 1, (cfg.p, cfg.q)).astype(
        np.float32
    )


def _data(name: str, n_series):
    from repro.data import ucr

    ds = ucr.load(name, seed=SEED)
    n = len(ds.y) if n_series is None else n_series
    return ds.x[:n], ds.y[:n]


# ------------------------------------------------------------ (a) device
def phase_device(platform: str = "tpu") -> dict:
    import jax

    devs = jax.devices()
    info = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    print(
        f"(a) device: platform={info['platform']} "
        f"device_kind={info['kind']!r} count={info['count']}",
        flush=True,
    )
    check(
        info["platform"] == platform,
        f"expected a {platform} device, JAX found {info['platform']!r}",
    )
    return info


# ----------------------------------------------------- (b) single column
def phase_single(
    lowering: str,
    datasets=("Lightning2", "WordSynonyms"),
    *,
    n_series=None,
    epochs: int = 4,
    t_max: int = T_MAX,
) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.tnn_columns import column_config
    from repro.core import column, encoding, simulator

    for name in datasets:
        x, y = _data(name, n_series)
        cfg = column_config(name, t_max)
        res = simulator.cluster_time_series(
            x, y, cfg, epochs=epochs, mode="pallas", seed=SEED
        )
        w = np.asarray(res.params["w"])
        check(res.lowering == lowering,
              f"{name}: trained on {res.lowering!r}, expected {lowering!r}")
        check(res.assignments.shape == (len(y),), f"{name}: assignment shape")
        check(np.isfinite(w).all() and w.shape == (cfg.p, cfg.q),
              f"{name}: trained weights not finite [{cfg.p}, {cfg.q}]")
        check(((res.assignments >= 0) & (res.assignments <= cfg.q)).all(),
              f"{name}: cluster id out of range")

        icfg = integer_grid(cfg)
        volleys = encoding.encode(jnp.asarray(x), t_max)
        w0 = _integer_weights(np.random.default_rng(SEED), icfg)
        fused = column.fit({"w": w0}, volleys, icfg, epochs, mode="pallas")
        cycle = column.fit({"w": w0}, volleys, icfg, epochs, mode="cycle")
        a_fused = column.cluster_assignments(fused, volleys, icfg, "pallas")
        a_cycle = column.cluster_assignments(cycle, volleys, icfg, "cycle")
        check(np.array_equal(np.asarray(fused["w"]), np.asarray(cycle["w"])),
              f"{name}: integer-grid fused weights differ from mode='cycle'")
        check(np.array_equal(np.asarray(a_fused), np.asarray(a_cycle)),
              f"{name}: integer-grid assignments differ from mode='cycle'")
        print(
            f"    {name} {cfg.p}x{cfg.q}: lowering={res.lowering} "
            f"rand_index={res.rand_index:.4f} clusters="
            f"{len(np.unique(res.assignments))}; integer grid == cycle",
            flush=True,
        )


# ------------------------------------------------------ (c) design sweep
def _sweep_designs(name: str, scales, t_max: int):
    from repro.configs.tnn_columns import column_config

    base = column_config(name, t_max)
    return [base.with_threshold(base.neuron.threshold * s) for s in scales]


def _padded_operands(cfgs, x, w_init, t_max):
    """The operands ``cluster_time_series_many`` pads one same-shape
    bucket into: volleys [N, D, p], weights [D, p, q] and the per-design
    scalars."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import encoding
    from repro.core.types import TIME_DTYPE

    v = np.asarray(encoding.encode(jnp.asarray(x), t_max))
    xs = jnp.asarray(np.repeat(v[:, None, :], len(cfgs), axis=1))
    w0 = jnp.asarray(np.stack(w_init))
    th = jnp.asarray([c.neuron.threshold for c in cfgs], jnp.float32)
    tm = jnp.asarray([c.t_max for c in cfgs], TIME_DTYPE)
    qa = jnp.asarray([c.q for c in cfgs], TIME_DTYPE)
    return w0, xs, th, tm, qa


def _one_device_sweep(cfgs, x, w_init, t_max, epochs, fit_lowering):
    """The same sweep as one unsharded bucket on the default device:
    ``backend.fit_padded`` + ``assign_padded`` (the sweep's own
    single-device path).  Returns (weights [D, p, q], ids [D, N])."""
    import numpy as np

    from repro.core import backend

    c0 = cfgs[0]
    w0, xs, th, tm, qa = _padded_operands(cfgs, x, w_init, t_max)
    w = backend.fit_padded(
        w0, xs, th, tm, qa, t_window=t_max, w_max=c0.neuron.w_max,
        wta_k=c0.wta.k, mu_capture=c0.stdp.mu_capture,
        mu_backoff=c0.stdp.mu_backoff, mu_search=c0.stdp.mu_search,
        stabilize=c0.stdp.stabilizer == "half", response=c0.neuron.response,
        epochs=epochs, lowering=fit_lowering,
    )
    asg_lowering = (
        "reference" if fit_lowering == "reference"
        else backend.assign_lowering(c0.neuron.response, w)
    )
    ids = backend.assign_padded(
        w, xs, th, tm, qa, t_window=t_max, wta_k=c0.wta.k,
        response=c0.neuron.response, lowering=asg_lowering,
        w_max=c0.neuron.w_max,
    )
    return np.asarray(w), np.asarray(ids)


def _run_sweep(cfgs, x, y, epochs, lowering, shards, w_init=None):
    """One ``cluster_time_series_many`` call that must stay on
    ``lowering`` with no retry, no failure and ``shards`` devices per
    bucket."""
    from repro.core import simulator

    res = simulator.cluster_time_series_many(
        x, y, cfgs, epochs=epochs, seed=SEED, on_error="raise",
        w_init=w_init,
    )
    for r in res:
        check(isinstance(r, simulator.ClusteringResult), f"failed: {r}")
        check(r.lowering == lowering,
              f"sweep bucket ran {r.lowering!r}, expected {lowering!r}")
        check(r.retries == 0, f"sweep bucket retried {r.retries} times")
        check(r.shards == shards,
              f"sweep bucket sharded {r.shards} ways, expected {shards}")
    return res


def _same(res, w, ids, what: str) -> None:
    import numpy as np

    for j, r in enumerate(res):
        check(np.array_equal(np.asarray(r.params["w"]), w[j]),
              f"{what}: weights of design {j} differ")
        check(np.array_equal(np.asarray(r.assignments), ids[j]),
              f"{what}: assignments of design {j} differ")


def phase_sweep(
    lowering: str,
    datasets=TABLE2,
    *,
    scales=(0.75, 1.0, 1.25, 1.5),
    n_series=None,
    epochs: int = 2,
    t_max: int = T_MAX,
) -> None:
    import numpy as np

    buckets = 0
    for name in datasets:
        x, y = _data(name, n_series)
        cfgs = _sweep_designs(name, scales, t_max)
        res = _run_sweep(cfgs, x, y, epochs, lowering, shards=1)
        buckets += res[0].buckets

        icfgs = [integer_grid(c) for c in cfgs]
        rng = np.random.default_rng(SEED)
        w_init = [_integer_weights(rng, c) for c in icfgs]
        ires = _run_sweep(icfgs, x, y, epochs, lowering, shards=1,
                          w_init=w_init)
        w_ref, ids_ref = _one_device_sweep(
            icfgs, x, w_init, t_max, epochs, "reference"
        )
        _same(ires, w_ref, ids_ref, f"{name} integer grid vs reference")
        print(
            f"    {name} {cfgs[0].p}x{cfgs[0].q} x{len(cfgs)} designs: "
            f"lowering={lowering} retries=0; integer grid == reference "
            f"(best rand_index {max(r.rand_index for r in res):.4f})",
            flush=True,
        )
    print(f"    {buckets} buckets, all on {lowering}", flush=True)


def phase_sharded(
    lowering: str,
    datasets=("Lightning2", "WordSynonyms"),
    *,
    shards: int = 4,
    scales=(0.75, 1.0, 1.25, 1.5),
    n_series=None,
    epochs: int = 2,
    t_max: int = T_MAX,
) -> None:
    import numpy as np

    for name in datasets:
        x, y = _data(name, n_series)
        cfgs = _sweep_designs(name, scales, t_max)
        rng = np.random.default_rng(SEED)
        for label, variant in (("default", cfgs),
                               ("integer grid", [integer_grid(c) for c in cfgs])):
            w_init = [_integer_weights(rng, c) for c in variant]
            res = _run_sweep(variant, x, y, epochs, lowering, shards=shards,
                             w_init=w_init)
            w1, ids1 = _one_device_sweep(
                variant, x, w_init, t_max, epochs, lowering
            )
            _same(res, w1, ids1, f"{name} {label}: {shards} shards vs 1 device")
            print(
                f"    {name} {cfgs[0].p}x{cfgs[0].q} {label}: "
                f"{len(variant)} designs over {shards} devices == one device",
                flush=True,
            )


# ---------------------------------------------------------- (d) service
def phase_service(
    lowering: str,
    datasets=TABLE2,
    *,
    requests: int = 336,
    batch_size: int = 8,
    refit_every: int = 16,
    refit_window: int = 16,
    t_max: int = T_MAX,
) -> None:
    import numpy as np

    from repro.configs.tnn_columns import column_config
    from repro.core import simulator
    from repro.serve import ClusteringService, ServeResult

    designs = {n: column_config(n, t_max) for n in datasets}
    rng = np.random.default_rng(SEED)
    # integer initial weights: the batched assign starts on the kernel and
    # must leave it for the reference body once stabilized re-fits move
    # the weights off the integer grid
    weights = {n: _integer_weights(rng, c) for n, c in designs.items()}
    svc = ClusteringService(
        designs, batch_size=batch_size, refit_every=refit_every,
        refit_window=refit_window, seed=SEED, weights=weights,
    )
    warm = svc.warmup()
    print(f"    warmup: {warm['buckets']} buckets in "
          f"{warm['seconds']:.3f} s; asg_lowering "
          f"{[b['asg_lowering'] for b in svc.buckets()]}", flush=True)
    data = {n: _data(n, None)[0] for n in datasets}
    names = list(datasets)
    asked = []
    for i in range(requests):
        name = names[i % len(names)]
        x = data[name][(i // len(names)) % len(data[name])]
        # the weights this request is answered with: a bucket's weights
        # change only by a re-fit right after one of its batches runs, and
        # a queued request rides the next batch, so they are the weights
        # live when it is admitted
        live = svc.weights(name)
        asked.append((name, x, live, svc.submit(x, name)))
    svc.flush()

    st = svc.stats()
    check(st.served == st.submitted == requests,
          f"served {st.served} of {st.submitted} submitted ({requests})")
    check(st.failed == 0 and st.isolations == 0,
          f"{st.failed} failed requests, {st.isolations} isolated batches")
    check(st.refit_failures == 0 and st.refit_retries == 0,
          f"{st.refit_failures} failed re-fits, {st.refit_retries} retries")
    check(st.degraded == 0, f"{st.degraded} degraded buckets")
    for b in svc.buckets():
        print(
            f"    bucket {b['envelope']} {list(b['designs'])}: "
            f"fit_lowering={b['fit_lowering']} "
            f"last_fit_lowering={b['last_fit_lowering']} "
            f"asg_lowering={b['asg_lowering']}",
            flush=True,
        )
        check(b["fit_lowering"] == lowering
              and b["last_fit_lowering"] == lowering,
              f"bucket {b['designs']} re-fit off {lowering}")
    check(st.refits >= len(svc.buckets()),
          f"{st.refits} re-fits for {len(svc.buckets())} buckets")

    for name, x, live, handle in asked:
        out = handle.result()
        check(isinstance(out, ServeResult), f"request {handle.id}: {out}")
        want = simulator.assign_time_series(x, designs[name], {"w": live})
        check(out.cluster == int(want),
              f"request {handle.id} ({name}): served {out.cluster}, "
              f"assign_time_series says {int(want)}")
    print(f"    {st.served} served, {st.refits} re-fits, "
          f"answers == assign_time_series", flush=True)


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1 (default): phases (a)-(d) on one chip; 4: only the sharded "
             "design sweep across four chips",
    )
    args = ap.parse_args(argv)
    try:
        device = phase_device("tpu")
        check(device["count"] >= args.chips,
              f"--chips {args.chips} but JAX sees {device['count']} devices")
        from repro.core import backend

        cache = backend.compile_cache()
        check(cache is not None, "no usable compile cache directory")
        state = "populated" if os.listdir(cache) else "empty"
        print(f"compile cache: {cache} ({state})", flush=True)
        t0 = time.perf_counter()
        if args.chips == 4:
            _timed("sharded sweep (4 chips)", phase_sharded, "mosaic",
                   shards=4)
        else:
            _timed("(b) single column", phase_single, "mosaic")
            _timed("(c) design sweep", phase_sweep, "mosaic")
            _timed("(d) service", phase_service, "mosaic")
        print(f"total: {time.perf_counter() - t0:.3f} s wall "
              "(smoke timing; not a benchmark)", flush=True)
    except SmokeFailure as e:
        print(f"SMOKE FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
