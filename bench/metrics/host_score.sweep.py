"""Step: the share of the window the host spent scoring the sweeps'
results, in %: the self time of the program's ``sim.score`` (guards, Rand
index, results), ``dse.record`` (forecast, design points, journal
records) and ``dse.pareto`` (the frontier) spans, over the window."""

import spans

NAMES = ("sim.score", "dse.record", "dse.pareto")


def read(ctx):
    snap = spans.snapshot()
    if snap is None:
        return None
    return 100.0 * sum(snap.self_s.get(n, 0.0) for n in NAMES) / ctx["window_s"]
