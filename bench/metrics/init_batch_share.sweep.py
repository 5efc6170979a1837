"""Step: the share of the candidates' initial draws (weights and, under
stochastic STDP, stream keys) that ran in a program shared with another
candidate, in %: 100 × (``dse.init_designs`` − ``dse.init_runs``) ÷
``dse.init_designs``.  An exploration draws its pending candidates in one
program per candidate shape, so a grid of k designs of one shape reads
100 × (k − 1) ÷ k.  Nothing for a program that does not declare these
counters (``repro.dse.explore.DSE_COUNTERS``)."""

import importlib

import spans

DESIGNS, RUNS = "dse.init_designs", "dse.init_runs"


def declared() -> tuple:
    """The exploration counters the program declares it records."""
    try:
        mod = importlib.import_module("repro.dse.explore")
    except ImportError:
        return ()
    return getattr(mod, "DSE_COUNTERS", ())


def read(ctx):
    snap = spans.snapshot()
    if snap is None or DESIGNS not in declared():
        return None
    designs = snap.counters.get(DESIGNS, 0)
    return 100.0 * spans.ratio(designs - snap.counters.get(RUNS, 0), designs)
