"""Policy: XLA compilations inside the measured window, counted at JAX's
compile funnel (``repro.testing.count_compiles``); expected 0."""


def read(ctx):
    return float(ctx["compiles"])
