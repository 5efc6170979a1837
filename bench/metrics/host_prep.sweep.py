"""Step: the share of the window the host spent preparing the sweeps'
device work, in %: the self time of the program's ``dse.init``
(per-candidate initial weights), ``sim.encode`` (the stream's encodes)
and ``sim.pad`` (stack, pad and place each bucket's operands) spans,
over the window."""

import spans

NAMES = ("dse.init", "sim.encode", "sim.pad")


def read(ctx):
    snap = spans.snapshot()
    if snap is None:
        return None
    return 100.0 * sum(snap.self_s.get(n, 0.0) for n in NAMES) / ctx["window_s"]
