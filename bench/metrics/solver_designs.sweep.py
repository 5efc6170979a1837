"""Policy: designs of the window that the degradation ladder evaluated on
the bottom 'cycle' solver rung instead of a fused lowering: the program's
``sim.solver_designs`` counter; expected 0.  Nothing for a program that
does not declare it (``SWEEP_COUNTERS``)."""

import spans

NAME = "sim.solver_designs"


def declared() -> tuple:
    """The sweep counters the program declares it records."""
    from repro.core import simulator

    return getattr(simulator, "SWEEP_COUNTERS", ())


def read(ctx):
    snap = spans.snapshot()
    if snap is None or NAME not in declared():
        return None
    return float(snap.counters.get(NAME, 0))
