"""Traffic kind ``sweep``: design-space campaigns, back to back.

A cell of this kind runs ``repro.dse.explore`` (grid search, the
program's exploration front end) once per design of its configuration,
each over that design's own seeded stream, and calls that one sweep.  The
window repeats whole sweeps, each with its own exploration seed drawn
from the cell's seed, until ``--seconds`` have passed; the rate counts
every sweep finished and all the time they took.

Traffic file keys (``bench/traffic/<name>.json``):

* ``kind``: ``"sweep"``;
* ``q``: neuron counts to sweep, or null for each design's own ``q``;
* ``t_max``: temporal windows to sweep;
* ``threshold_scales``: ``{"start", "stop", "num"}``, evenly spaced
  multiples of the suggested threshold;
* ``epochs``: STDP passes per candidate;
* ``check_designs``: candidates of one window sweep compared with the
  reference.

One column-volley is one volley through one candidate column: a sweep
does ``candidates x N x (epochs + 1)`` of them per design (the fit's
epochs and one assign pass).
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

import reference
import streams
import work


@dataclasses.dataclass
class Design:
    name: str
    p: int
    q_list: tuple
    t_max: tuple
    scales: tuple
    x: np.ndarray
    y: np.ndarray

    def candidates(self):
        """(q, t_max, scale) in the program's grid order: q, then t_max,
        then threshold scale."""
        return [(q, t, s) for q in self.q_list for t in self.t_max for s in self.scales]


def scales(spec: dict) -> tuple:
    """The threshold scales of a traffic file, evenly spaced."""
    return tuple(float(s) for s in np.linspace(spec["start"], spec["stop"], int(spec["num"])))


WARMUP_REP = 2**20  # the warm-up sweep's seed index, past any window's sweeps


def rep_seed(seed: int, rep: int) -> int:
    """The exploration seed of the window's ``rep``-th sweep: 31 bits, so
    every consumer of a seed takes it."""
    return int(np.random.SeedSequence([int(seed), rep]).generate_state(1)[0] % (2**31 - 1))


class Cell:
    """One sweep cell: set-up, window, end-to-end numbers, check."""

    def __init__(self, config: dict, traffic: dict, seed: int, chips: int):
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.chips = chips
        self.epochs = int(traffic["epochs"])
        self.w_max = int(config["neuron"]["w_max"])
        self.designs = []
        for d in config["designs"]:
            x, y = streams.synthetic(d["name"], d["stream"], self.seed)
            self.designs.append(Design(
                name=d["name"], p=int(d["p"]),
                q_list=tuple(traffic["q"] or [d["q"]]),
                t_max=tuple(traffic["t_max"]),
                scales=scales(traffic["threshold_scales"]), x=x, y=y,
            ))
        self.sweeps: list = []  # per sweep: (explore seed, [DSEResult per design])
        self.elapsed = 0.0

    # ------------------------------------------------------------ program
    def _sweep(self, explore_seed: int):
        import jax
        from repro.dse.explore import explore
        from repro.dse.space import DesignSpace

        out = []
        with jax.profiler.TraceAnnotation("bench.explore"):
            for d in self.designs:
                space = DesignSpace(q=d.q_list, t_max=d.t_max, threshold_scale=d.scales)
                out.append(explore(d.x, d.y, space, epochs=self.epochs, seed=explore_seed))
        return out

    def setup(self) -> None:
        """Warm up: one sweep of the window's own shapes (its results are
        discarded)."""
        self._sweep(rep_seed(self.seed, WARMUP_REP))

    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        rep = 0
        while True:
            s = rep_seed(self.seed, rep)
            self.sweeps.append((s, self._sweep(s)))
            rep += 1
            if time.perf_counter() - t0 >= seconds:
                break
        self.elapsed = time.perf_counter() - t0

    # ------------------------------------------------------------ numbers
    def column_volleys(self) -> int:
        """Column-volleys of one sweep."""
        return sum(
            len(d.candidates()) * len(d.x) * (self.epochs + 1) for d in self.designs
        )

    @property
    def attempted(self) -> int:
        return len(self.sweeps) * sum(len(d.candidates()) for d in self.designs)

    @property
    def failed(self) -> int:
        """Candidates the program quarantined instead of scoring."""
        return self._failed

    def e2e(self) -> dict:
        return {"column_volleys_per_s": len(self.sweeps) * self.column_volleys() / self.elapsed}

    def work(self) -> dict:
        """Model operations and least bytes of the window, by program."""
        fit_ops = fit_b = asg_ops = asg_b = 0
        for d in self.designs:
            n = len(d.x)
            for q, t, _ in d.candidates():
                fit_ops += work.fit_ops(d.p, q, t, n * self.epochs)
                fit_b += work.fit_bytes(d.p, q, n)
                asg_ops += work.assign_ops(d.p, q, t, n)
                asg_b += work.assign_bytes(d.p, q, n)
        k = len(self.sweeps)
        return {"fit_ops": k * fit_ops, "fit_bytes": k * fit_b,
                "assign_ops": k * asg_ops, "assign_bytes": k * asg_b}

    def layer_context(self) -> dict:
        return {"window_s": self.elapsed, "chips": self.chips, "sweeps": len(self.sweeps), **self.work()}

    # --------------------------------------------------------------- check
    def release(self) -> None:
        """Keep, of the window's results, only what the check compares."""
        self._failed = sum(
            len(r.meta["failures"]) for _, results in self.sweeps for r in results
        )
        self._sample = self._draw_sample()
        kept = []
        for si, di, ci in self._sample:
            s, results = self.sweeps[si]
            point = {p.index: p for p in results[di].points}.get(ci)
            kept.append(None if point is None else (
                np.asarray(point.params["w"]), float(point.rand_index)))
        self._kept = kept
        self.sweeps = [(s, None) for s, _ in self.sweeps]

    def _draw_sample(self):
        rng = np.random.default_rng([self.seed, 1])
        si = int(rng.integers(len(self.sweeps)))
        pool = [(si, di, ci) for di, d in enumerate(self.designs)
                for ci in range(len(d.candidates()))]
        k = min(int(self.traffic["check_designs"]), len(pool))
        return [pool[i] for i in sorted(rng.choice(len(pool), k, replace=False))]

    def reference_outputs(self, dtype):
        """The reference's (weights, Rand index) of each sampled candidate."""
        import jax.numpy as jnp

        stats = reference.statics(self.config)
        out = []
        for si, di, ci in self._sample:
            d = self.designs[di]
            q, t, scale = d.candidates()[ci]
            thr = scale * reference.suggested_threshold(d.p, self.w_max)
            xs = reference.encode(d.x, t)
            w0 = reference.explore_init(self.sweeps[si][0], ci, d.p, q, self.w_max)
            w = reference.fit(w0, xs, jnp.float32(thr), t_max=t, epochs=self.epochs,
                              statics=stats, dtype=dtype)
            ids = reference.assign(w, xs, jnp.float32(thr), t_max=t, dtype=dtype)
            out.append((np.asarray(w, np.float32), reference.rand_index(d.y, np.asarray(ids))))
        return out

    def compare(self, got, want) -> list:
        """Numbers compared: the widest weight gap (in units of w_max) and
        the widest Rand-index gap over the sampled candidates; a candidate
        the program did not score reads as a gap of 1."""
        wgap = rgap = 0.0
        for g, (w_ref, ri_ref) in zip(got, want):
            if g is None:
                wgap = rgap = 1.0
                continue
            w, ri = g
            wgap = max(wgap, float(np.max(np.abs(w - w_ref))) / self.w_max)
            rgap = max(rgap, abs(ri - ri_ref))
        return [("weight_gap", wgap), ("rand_index_gap", rgap)]

    def prepare_control(self, seconds: float) -> None:
        """Sample the candidates of one sweep for the control, which needs
        no window: it trains them itself."""
        self.sweeps = [(rep_seed(self.seed, 0), None)]
        self._sample = self._draw_sample()

    def check(self) -> list:
        import jax.numpy as jnp

        return self.compare(self._kept, self.reference_outputs(jnp.float32))

