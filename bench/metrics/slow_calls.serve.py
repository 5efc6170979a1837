"""Front end: submit/flush calls of the window that took over 50 ms (from
the harness's wall time around each call).  Each such stall holds the
requests that fall due meanwhile, and sets the latency tail."""


def read(ctx):
    return float(ctx["slow_calls"])
