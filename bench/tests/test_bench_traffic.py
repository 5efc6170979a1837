"""Traffic generators: seeded, open loop, and the sweep grids' buckets."""
import json
import os
import time

import numpy as np
import pytest

import benchkit
from repro.serve import ServeResult
import reference
import streams
from kinds import serve, sweep


def _load(sub, name):
    with open(os.path.join(benchkit.BENCH, sub, f"{name}.json")) as f:
        return json.load(f)


def test_same_seed_same_schedule_mix_and_series():
    names = ["a", "b", "c"]
    sizes = {"a": 50, "b": 7, "c": 900}
    one = serve.schedule(2**31 + 77, 150.0, 10.0, names, 1.0, sizes)
    two = serve.schedule(2**31 + 77, 150.0, 10.0, names, 1.0, sizes)
    for x, y in zip(one, two):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    meta = {"length": 40, "classes": 3, "n": 30, "modality": "ecg"}
    xa, ya = streams.synthetic("ECG200", meta, 5)
    xb, yb = streams.synthetic("ECG200", meta, 5)
    assert np.array_equal(xa, xb) and np.array_equal(ya, yb)


def test_every_seed_offers_the_same_work_in_another_order():
    names = ["a", "b", "c", "d"]
    sizes = {n: 10 for n in names}
    due1, d1, _ = serve.schedule(1, 150.0, 10.0, names, 1.0, sizes)
    due2, d2, _ = serve.schedule(2, 150.0, 10.0, names, 1.0, sizes)
    assert len(due1) == len(due2) == 1500
    # the gaps are one multiset, shuffled (each schedule drops its last gap)
    g1, g2 = np.round(np.diff(due1), 9), np.round(np.diff(due2), 9)
    assert len(np.intersect1d(g1, g2)) >= len(np.unique(g1)) - 2
    assert sorted(d1) == sorted(d2) and d1 != d2
    assert [d1.count(n) for n in names] == serve.zipf_counts(1500, 4, 1.0)
    assert serve.zipf_counts(1500, 4, 1.0)[0] > serve.zipf_counts(1500, 4, 1.0)[-1]
    assert due1[-1] < 10.0 and due2[-1] < 10.0


class _Handle:
    def __init__(self, rid):
        self.done = True
        self.outcome = ServeResult(rid, "a", 0, 0.0)


class _StallingService:
    """Answers every request at once, but stalls on one submit."""

    def __init__(self, stall_at, stall_s):
        self.n = 0
        self.stall_at, self.stall_s = stall_at, stall_s

    def submit(self, x, design):
        if self.n == self.stall_at:
            time.sleep(self.stall_s)
        self.n += 1
        return _Handle(self.n)

    def flush(self):
        pass


def _open_loop(stall_at, stall_s):
    cell = object.__new__(serve.Cell)
    cell.seed = 3
    cell.traffic = {"rate_per_s": 200, "zipf_s": 1.0, "popularity": ["a"]}
    cell.streams = {"a": np.zeros((4, 8))}
    cell.events = []
    cell.svc = _StallingService(stall_at, stall_s)
    cell.window(0.5)
    return cell.latencies_ms()


def test_open_loop_latency_counts_from_the_due_time():
    calm = _open_loop(stall_at=-1, stall_s=0.0)
    stalled = _open_loop(stall_at=20, stall_s=0.15)
    assert len(calm) == len(stalled) == 100
    # requests due during the stall wait for it: their latency is counted
    # from when they were due, not from when the loop got to them
    assert stalled[21:30].min() > 50.0
    assert calm.max() < 50.0


def _sweep_shapes(config, traffic):
    shapes = []
    for d in config["designs"]:
        qs = traffic["q"] or [d["q"]]
        shapes.append([(d["p"], q, t) for q in qs for t in traffic["t_max"]
                       for _ in sweep.scales(traffic["threshold_scales"])])
    return shapes


@pytest.mark.parametrize("cell,config,buckets,designs", [
    ("ws-sweep", "wordsynonyms-270x25", 1, 64),
    ("ws-sweep-4chip", "wordsynonyms-270x25", 1, 256),
    ("fleet-sweep", "table2-fleet", 7, 56),
])
def test_sweep_grids_land_in_their_buckets(cell, config, buckets, designs):
    from repro.core import backend

    per_explore = _sweep_shapes(_load("configs", config), _load("traffic", cell))
    assert sum(len(s) for s in per_explore) == designs
    got = sum(len(backend.envelope_buckets(s)) for s in per_explore)
    assert got == buckets


def test_four_chips_shard_the_256_designs_64_each(monkeypatch):
    import jax

    from repro.core import backend

    monkeypatch.setattr(jax, "local_device_count", lambda: 4)
    assert backend.design_shards(256) == 4


def test_the_reference_packs_the_fleet_like_the_service():
    from repro.core import backend

    designs = _load("configs", "table2-fleet")["designs"]
    shapes = [(d["p"], d["q"], d["t_max"]) for d in designs]
    want = [sorted(m) for _, m in backend.envelope_buckets(shapes)]
    assert [sorted(m) for m in reference.envelope_buckets(shapes)] == want
    assert len(want) == 4
