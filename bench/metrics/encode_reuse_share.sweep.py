"""Step: the share of the sweep's per-design stream encodes that reused
another design's encode, in %: 100 × (``sim.encode_designs`` −
``sim.encode_runs``) ÷ ``sim.encode_designs``.  The sweep encodes its
stream once per distinct t_max among a call's designs, so a grid of k
designs over two windows reads 100 × (k − 2) ÷ k.  Nothing for a program
that does not declare these counters (``SWEEP_COUNTERS``)."""

import spans

DESIGNS, RUNS = "sim.encode_designs", "sim.encode_runs"


def declared() -> tuple:
    """The sweep counters the program declares it records."""
    from repro.core import simulator

    return getattr(simulator, "SWEEP_COUNTERS", ())


def read(ctx):
    snap = spans.snapshot()
    if snap is None or DESIGNS not in declared():
        return None
    designs = snap.counters.get(DESIGNS, 0)
    return 100.0 * spans.ratio(designs - snap.counters.get(RUNS, 0), designs)
