"""Kernels: the online re-fits' share of their roofline, in %: the least
time of the window's re-fit work over the device time of the custom-call
kernels inside the ``jit_fit_scan_padded`` programs (the fused fit
kernel, ``fit_block``).

The work is ``bench/work.py``'s, summed over the designs at their own p,
q and t_max: the fire plus one update per synapse of every live
design-volley the window's committed re-fits trained on (``refit_ops``),
and the least bytes, per committed re-fit of a design its live volleys
read once and its weights read and written once (``refit_bytes``).  Both
come from the replay that the check follows, which knows each volley's
design; the program's counter ``serve.refit_design_volleys`` holds the
same design-volleys summed over designs, and the share is read only when
the two agree, so the work priced is the work the program did.  The
stochastic rule's random draws are how the kernel realises the update
and are not counted as work.

Nothing for a program that does not declare the serving counters
(``SERVE_COUNTERS``), for a kind whose replay does not count the
design-volleys and bytes (the ``serve`` kind, until the serving kinds are
folded into one), or when no re-fit kernel ran."""

import sys

import spans
import work

VOLLEYS = "serve.refit_design_volleys"


def declared() -> tuple:
    """The serving counters the program declares it records."""
    try:
        from repro.serve import service
    except ImportError:
        return ()
    return getattr(service, "SERVE_COUNTERS", ())


def read(ctx):
    if VOLLEYS not in declared() or "refit_design_volleys" not in ctx:
        return None
    snap = spans.snapshot()
    counted = snap.counters.get(VOLLEYS, 0) if snap is not None else 0
    if counted != ctx["refit_design_volleys"]:
        print(f"refit_roofline.serve: the program counted {counted} re-fit "
              f"design-volleys, the replay {ctx['refit_design_volleys']}",
              file=sys.stderr)
        return None
    share, _ = work.roofline_pct(
        ctx["refit_ops"], ctx["refit_bytes"],
        ctx["trace"].kernel_s("jit_fit_scan_padded"), ctx["peak"],
    )
    return share
