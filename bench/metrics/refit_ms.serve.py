"""Online re-fit: mean wall milliseconds of one re-fit stage (fit, checks
and commit), from the service's ``refit`` stage spans; nothing when the
window committed no re-fit."""


def read(ctx):
    n = ctx["stage_n"].get("refit", 0)
    return 1e3 * ctx["stage_s"]["refit"] / n if n else None
