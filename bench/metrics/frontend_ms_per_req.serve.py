"""Front end: host milliseconds per request outside the assign and re-fit
stages (admission, encode, batch assembly, queueing), from the harness's
wall time inside ``submit``/``flush`` minus the service's ``assign`` and
``refit`` stage spans."""


def read(ctx):
    inside = sum(ctx["call_s"].values())
    stages = ctx["stage_s"].get("assign", 0.0) + ctx["stage_s"].get("refit", 0.0)
    return 1e3 * (inside - stages) / ctx["requests"]
