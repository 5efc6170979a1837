"""Front end: host milliseconds to assemble one batch (each request's
volley in its design's lane, silent padding to the compiled batch size),
the mean of the program's ``serve.batch_xs`` spans."""

import spans


def read(ctx):
    snap = spans.snapshot()
    if snap is None:
        return None
    return 1e3 * spans.ratio(snap.total_s.get("serve.batch_xs", 0.0),
                             snap.count.get("serve.batch_xs", 0))
