"""Kernels: the fused fit kernel's share of its roofline, in %: the least
time of the window's fit operations and bytes on the chip's peaks
(``bench/work.py``) over the device time of the fit's Pallas kernel (the
custom-call operations in the trace; in a sweep only the fit runs one)."""

import work


def read(ctx):
    share, _ = work.roofline_pct(
        ctx["fit_ops"], ctx["fit_bytes"], ctx["trace"].kernel_s(""), ctx["peak"]
    )
    return share
