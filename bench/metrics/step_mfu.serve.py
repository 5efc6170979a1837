"""Step: the model operations of the window's assigns and re-fits over the
summed wall time of the service's ``assign`` and ``refit`` stages times
the bf16 peak, in %."""


def read(ctx):
    busy = ctx["stage_s"].get("assign", 0.0) + ctx["stage_s"].get("refit", 0.0)
    if busy <= 0:
        return None
    ops = ctx["assign_ops"] + ctx["refit_ops"]
    return 100.0 * ops / (busy * ctx["peak"]["bf16_flops_per_s"])
