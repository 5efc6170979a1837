"""Traffic kind ``serve``: open-loop requests to the clustering service.

A cell of this kind serves a fleet of column designs with
``repro.serve.ClusteringService`` and offers it requests on a fixed,
seeded schedule, whatever the service does (open loop).  The loop is
work-conserving and single-threaded: it submits every request that is
due, flushes the partial batches when none is due, and otherwise waits
for the next due time.  Each request is timed from when it was due to
when the call that answered it returned.

Every seed offers the same work in another order: ``rate_per_s *
seconds`` requests, whose gaps are one fixed set of exponential quantiles
shuffled, and whose designs are fixed Zipf counts over the popularity
ranking, shuffled.  Each request is a series drawn from its design's
stream.

Traffic file keys (``bench/traffic/<name>.json``):

* ``kind``: ``"serve"``;
* ``rate_per_s``: offered load;
* ``zipf_s`` and ``popularity``: the design names, most popular first;
* ``batch_size``, ``refit_every``, ``refit_window``, ``refit_epochs``:
  the service's settings;
* ``check_requests``: answered requests compared with the reference.
"""
from __future__ import annotations

import time

import numpy as np

import reference
import work

SLOW_CALL_S = 0.05  # a call this long holds some ten requests behind it


def zipf_counts(n: int, ranks: int, s: float) -> list:
    """Requests per rank: ``n`` split by weights ``1 / rank**s``, largest
    remainders rounded up."""
    w = np.array([1.0 / (k + 1) ** s for k in range(ranks)])
    share = n * w / w.sum()
    counts = np.floor(share).astype(int)
    for i in np.argsort(-(share - counts))[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def schedule(seed: int, rate: float, seconds: float, names: list, zipf_s: float,
             sizes: dict):
    """(due times [n], design per request [n], series index per request [n])."""
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([int(seed), 3])
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = gaps[rng.permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    designs = np.repeat(np.arange(len(names)), zipf_counts(n, len(names), zipf_s))
    designs = designs[rng.permutation(n)]
    series = np.array([rng.integers(sizes[names[d]]) for d in designs])
    return due, [names[d] for d in designs], series


def span_monitor():
    """A ``StepMonitor`` (the service's stage-timing seam) that also sums
    the wall time of each stage label."""
    from repro.distributed.straggler import StepMonitor

    class SpanTotals(StepMonitor):
        def __init__(self):
            super().__init__(threshold=4.0, warmup=3)  # the service's default
            self.totals: dict = {}
            self.counts: dict = {}
            self._open = None

        def start(self, label: str = "") -> None:
            self._open = (label, time.perf_counter())
            super().start(label)

        def stop(self):
            label, t0 = self._open
            self.totals[label] = self.totals.get(label, 0.0) + time.perf_counter() - t0
            self.counts[label] = self.counts.get(label, 0) + 1
            return super().stop()

    return SpanTotals()


class Cell:
    """One serving cell: set-up, open-loop window, numbers, check."""

    def __init__(self, config: dict, traffic: dict, seed: int, chips: int):
        import streams

        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.chips = chips
        self.w_max = int(config["neuron"]["w_max"])
        self.designs = {d["name"]: d for d in config["designs"]}
        self.streams = {
            n: streams.synthetic(n, d["stream"], self.seed)[0] for n, d in self.designs.items()
        }
        rng = np.random.default_rng([self.seed, 2])
        self.w0 = {
            n: rng.uniform(0.0, self.w_max, (d["p"], d["q"])).astype(np.float32)
            for n, d in self.designs.items()
        }
        self.events: list = []

    def threshold(self, name: str) -> float:
        return reference.suggested_threshold(self.designs[name]["p"], self.w_max)

    def setup(self) -> None:
        from repro.core.types import ColumnConfig, NeuronConfig, STDPConfig, WTAConfig
        from repro.serve import ClusteringService

        c = self.config
        cfgs = {
            n: ColumnConfig(
                p=d["p"], q=d["q"], t_max=d["t_max"],
                neuron=NeuronConfig(response=c["neuron"]["response"],
                                    threshold=self.threshold(n), w_max=self.w_max),
                wta=WTAConfig(**c["wta"]), stdp=STDPConfig(**c["stdp"]),
            )
            for n, d in self.designs.items()
        }
        t = self.traffic
        self.monitor = span_monitor()
        self.svc = ClusteringService(
            cfgs, batch_size=t["batch_size"], refit_every=t["refit_every"],
            refit_window=t["refit_window"], refit_epochs=t["refit_epochs"],
            seed=self.seed % (2**31 - 1), weights=self.w0, monitor=self.monitor,
        )
        self.svc.warmup()
        self.monitor.totals.clear()
        self.monitor.counts.clear()

    def window(self, seconds: float) -> None:
        import jax

        t = self.traffic
        names = list(t["popularity"])
        due, design, series = schedule(
            self.seed, float(t["rate_per_s"]), seconds, names, float(t["zipf_s"]),
            {n: len(self.streams[n]) for n in names},
        )
        n = len(due)
        self.due = due
        self.design = design
        self.submitted = np.full(n, np.nan)
        self.answered = np.full(n, np.nan)
        self.outcome = [None] * n
        self.call_s = {"submit": 0.0, "flush": 0.0}
        self.slow_calls = 0  # submit/flush calls over SLOW_CALL_S
        svc, events = self.svc, self.events
        open_: list = []
        i = 0
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if i < n and due[i] <= now:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    h = svc.submit(self.streams[design[i]][series[i]], design[i])
                after = time.perf_counter() - t0
                self.submitted[i] = now
                self.call_s["submit"] += after - now
                self.slow_calls += after - now > SLOW_CALL_S
                events.append((design[i], int(series[i])))
                open_.append((i, h))
                i += 1
            elif open_:
                with jax.profiler.TraceAnnotation("bench.flush"):
                    svc.flush()
                after = time.perf_counter() - t0
                self.call_s["flush"] += after - now
                self.slow_calls += after - now > SLOW_CALL_S
                events.append(None)
            elif i < n:
                wait = due[i] - now
                if wait > 0.002:
                    time.sleep(wait - 0.001)
                continue
            else:
                break
            still = []
            for j, h in open_:
                if h.done:
                    self.answered[j] = after
                    self.outcome[j] = h.outcome
                else:
                    still.append((j, h))
            open_ = still
        self.elapsed = time.perf_counter() - t0

    # ------------------------------------------------------------ numbers
    @property
    def attempted(self) -> int:
        return len(self.due)

    def _served(self):
        from repro.serve import ServeResult

        return np.array([isinstance(o, ServeResult) for o in self.outcome])

    @property
    def failed(self) -> int:
        return int((~self._served()).sum())

    def latencies_ms(self) -> np.ndarray:
        """Latency of every request due in the window, from its due time;
        a request that failed or was not answered reads as infinite."""
        lat = (self.answered - self.due) * 1e3
        return np.where(self._served() & np.isfinite(lat), lat, np.inf)

    def e2e(self) -> dict:
        return {"serve_p50_ms": float(np.percentile(self.latencies_ms(), 50))}

    def lateness(self) -> str:
        """How late the generator submitted, and the tail it saw (the p95
        is reported here, not as a metric: see PERF.md)."""
        late = (self.submitted - self.due) * 1e3
        lat = self.latencies_ms()
        return (f"generator lateness: p50 {np.percentile(late, 50):.6f} ms, "
                f"p99 {np.percentile(late, 99):.6f} ms, max {late.max():.6f} ms "
                f"over {len(late)} requests in {self.elapsed:.6f} s; latency p95 "
                f"{np.percentile(lat, 95):.6f} ms, p99 {np.percentile(lat, 99):.6f} ms")

    def layer_context(self) -> dict:
        ops = 0
        nbytes = 0
        for name in self.design:
            d = self.designs[name]
            ops += work.fire_ops(d["p"], d["q"], d["t_max"])
            nbytes += d["p"] * work.TIME_BYTES + 4
        nbytes += sum(d["p"] * d["q"] * work.WEIGHT_BYTES for d in self.designs.values())
        return {
            "window_s": self.elapsed, "chips": self.chips, "requests": len(self.due),
            "call_s": dict(self.call_s), "stage_s": dict(self.monitor.totals),
            "stage_n": dict(self.monitor.counts), "assign_ops": ops,
            "assign_bytes": nbytes, "refit_ops": self._refit_ops,
            "slow_calls": self.slow_calls,
        }

    # --------------------------------------------------------------- check
    def release(self) -> None:
        """Read what the check compares, then free the service."""
        from repro.serve import ServeResult

        rng = np.random.default_rng([self.seed, 4])
        n = len(self.due)
        k = min(int(self.traffic["check_requests"]), n)
        self._sample = np.sort(rng.choice(n, k, replace=False))
        self._ids = [
            self.outcome[j].cluster if isinstance(self.outcome[j], ServeResult) else None
            for j in self._sample
        ]
        self._weights = {name: self.svc.weights(name) for name in self.designs}
        del self.svc

    def replay(self, dtype):
        """The reference service replayed over the window's events."""
        names = list(self.designs)
        encodes = {
            nm: reference.encode(self.streams[nm], self.designs[nm]["t_max"]) for nm in names
        }
        t = self.traffic
        sim = reference.ServiceReplay(
            {nm: (d["p"], d["q"], d["t_max"], self.threshold(nm))
             for nm, d in self.designs.items()},
            self.w0, encodes, batch_size=t["batch_size"], refit_every=t["refit_every"],
            refit_window=t["refit_window"], refit_epochs=t["refit_epochs"],
            statics=reference.statics(self.config), dtype=dtype,
        )
        for ev in self.events:
            if ev is None:
                sim.flush()
            else:
                sim.submit(*ev)
        sim.flush()
        return sim, encodes

    def reference_outputs(self, dtype):
        """(ids of the sampled requests, final weights per design)."""
        return self._reference(dtype)[:2]

    def _reference(self, dtype):
        """As ``reference_outputs``, plus the volleys each design re-fit on."""
        import jax.numpy as jnp

        sim, encodes = self.replay(dtype)
        ids = []
        for j in self._sample:
            name, series, version = sim.requests[j]
            d = self.designs[name]
            w = sim.versions[name][version]
            ids.append(int(reference.assign(
                w, encodes[name][series][None], jnp.float32(self.threshold(name)),
                t_max=d["t_max"], dtype=dtype)[0]))
        weights = {nm: np.asarray(v[-1], np.float32) for nm, v in sim.versions.items()}
        return ids, weights, sim.trained

    def compare(self, got, want) -> list:
        """Numbers compared: the share of sampled requests whose answer
        differs from the reference (a missing answer differs), and the
        widest gap of the final weights, in units of w_max."""
        ids, weights = got
        ref_ids, ref_w = want
        wrong = sum(1 for a, b in zip(ids, ref_ids) if a is None or a != b)
        gap = max(float(np.max(np.abs(weights[n] - ref_w[n]))) for n in ref_w) / self.w_max
        return [("answer_mismatch", wrong / max(1, len(ref_ids))), ("weight_gap", gap)]

    def prepare_control(self, seconds: float) -> None:
        """Run the service for ``seconds`` at the cell's load: the control
        replays that window's requests and re-fits."""
        self.setup()
        self.window(seconds)
        self.release()

    def check(self) -> list:
        import jax.numpy as jnp

        ids, weights, trained = self._reference(jnp.float32)
        # the re-fits' model work, from the same schedule the replay followed
        self._refit_ops = sum(
            work.fit_ops(self.designs[n]["p"], self.designs[n]["q"], self.designs[n]["t_max"],
                         v * self.traffic["refit_epochs"])
            for n, v in trained.items()
        )
        return self.compare((self._ids, self._weights), (ids, weights))
