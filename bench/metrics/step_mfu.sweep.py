"""Step: the model operations of the window's sweeps (fire and STDP of
every fit volley, fire of every assign volley, ``bench/work.py``) over
the window's length times the chips' bf16 peak, in %."""


def read(ctx):
    ops = ctx["fit_ops"] + ctx["assign_ops"]
    return 100.0 * ops / (ctx["window_s"] * ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
