"""Kernels: the fused fit kernel (``fit_block``)'s share of its roofline,
in %: the least time of the window's fit operations and bytes on the
chip's peaks (``bench/work.py``) over the device time of the custom-call
kernels inside the ``jit_fit_scan_padded`` programs.  Scoped to the fit
program, so a sweep whose assign also runs a kernel (the Mosaic
``assign_fire`` on integer weights) leaves that kernel out.  The work
counts the fire and one update per synapse of every fit volley; the
stochastic rule's random draws are how the kernel realises the update
and are not counted as work."""

import work


def read(ctx):
    share, _ = work.roofline_pct(
        ctx["fit_ops"], ctx["fit_bytes"],
        ctx["trace"].kernel_s("jit_fit_scan_padded"), ctx["peak"],
    )
    return share
