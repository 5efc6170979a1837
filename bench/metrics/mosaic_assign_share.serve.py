"""Kernels: the share of the window's assign batches that ran on the
Mosaic assign kernel (``_fire_block_kernel``), in %: the program's
``serve.assign_mosaic`` over ``serve.assign_mosaic`` +
``serve.assign_reference``, counted per batch by the lowering its bucket's
assign took.  The kernel is taken only for weights on the integer grid
(stochastic STDP's counters); off-grid float weights take the reference
body.  Nothing for a program that does not declare these counters
(``SERVE_COUNTERS``)."""

import spans

MOSAIC, REFERENCE = "serve.assign_mosaic", "serve.assign_reference"


def declared() -> tuple:
    """The serving counters the program declares it records."""
    try:
        from repro.serve import service
    except ImportError:
        return ()
    return getattr(service, "SERVE_COUNTERS", ())


def read(ctx):
    snap = spans.snapshot()
    if snap is None or MOSAIC not in declared():
        return None
    mosaic = snap.counters.get(MOSAIC, 0)
    return 100.0 * spans.ratio(mosaic, mosaic + snap.counters.get(REFERENCE, 0))
