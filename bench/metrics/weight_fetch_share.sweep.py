"""Step: the share of the designs' trained weights that left the device in
a fetch shared with another design, in %: 100 × (``sim.weight_designs`` −
``sim.weight_fetches``) ÷ ``sim.weight_designs``.  The sweep fetches each
envelope bucket's trained weight block once and crops the designs on the
host, so one bucket of k designs reads 100 × (k − 1) ÷ k.  Nothing for a
program that does not declare these counters (``SWEEP_COUNTERS``)."""

import spans

DESIGNS, FETCHES = "sim.weight_designs", "sim.weight_fetches"


def declared() -> tuple:
    """The sweep counters the program declares it records."""
    from repro.core import simulator

    return getattr(simulator, "SWEEP_COUNTERS", ())


def read(ctx):
    snap = spans.snapshot()
    if snap is None or DESIGNS not in declared():
        return None
    designs = snap.counters.get(DESIGNS, 0)
    return 100.0 * spans.ratio(designs - snap.counters.get(FETCHES, 0), designs)
