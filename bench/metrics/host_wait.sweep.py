"""Step: the share of the window the host spent dispatching the sweeps'
fit and assign programs and waiting for them, in %: the program's
``sim.fit`` (dispatch), ``sim.lowering`` (the first wait on the fit's
weights) and ``sim.assign`` (dispatch and fetch of the ids) spans, over
the window."""

import spans

NAMES = ("sim.fit", "sim.lowering", "sim.assign")


def read(ctx):
    snap = spans.snapshot()
    if snap is None:
        return None
    return 100.0 * sum(snap.total_s.get(n, 0.0) for n in NAMES) / ctx["window_s"]
