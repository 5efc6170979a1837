"""Online re-fit: host milliseconds per committed re-fit outside the fit
itself: the finiteness check of the new weights and the commit (which
re-checks the whole weight block for the assign lowering), both of which
wait for the device; from the program's ``serve.refit_check`` and
``serve.commit`` spans, over the number of commits."""

import spans


def read(ctx):
    snap = spans.snapshot()
    if snap is None:
        return None
    s = snap.total_s.get("serve.refit_check", 0.0) + snap.total_s.get("serve.commit", 0.0)
    return 1e3 * spans.ratio(s, snap.count.get("serve.commit", 0))
