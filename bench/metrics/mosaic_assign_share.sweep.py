"""Kernels: the share of the sweep's designs whose batched assign ran on
the Mosaic assign kernel (``_fire_block_kernel``), in %: the program's
``sim.assign_mosaic`` over ``sim.assign_mosaic`` + ``sim.assign_reference``,
counted per bucket where the lowering is chosen.  The kernel is taken only
for trained weights on the integer grid (stochastic STDP from integer
counters); off-grid float weights take the reference body.  Nothing for a
program that does not declare these counters (``SWEEP_COUNTERS``)."""

import spans

MOSAIC, REFERENCE = "sim.assign_mosaic", "sim.assign_reference"


def declared() -> tuple:
    """The sweep counters the program declares it records."""
    from repro.core import simulator

    return getattr(simulator, "SWEEP_COUNTERS", ())


def read(ctx):
    snap = spans.snapshot()
    if snap is None or MOSAIC not in declared():
        return None
    mosaic = snap.counters.get(MOSAIC, 0)
    return 100.0 * spans.ratio(mosaic, mosaic + snap.counters.get(REFERENCE, 0))
