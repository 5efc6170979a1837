"""Kernels: the batched assign program's share of its roofline, in %: the
least time of the window's assign operations and bytes over the device
time of the ``jit_assign_padded`` programs in the trace."""

import work


def read(ctx):
    share, _ = work.roofline_pct(
        ctx["assign_ops"], ctx["assign_bytes"], ctx["trace"].program_s("jit_assign_padded"),
        ctx["peak"],
    )
    return share
