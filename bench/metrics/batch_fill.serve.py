"""Step: the share of the assign batches' rows that carried a request, in
%: the program's ``serve.rows_live`` over ``serve.rows_slots`` (batches
times the compiled batch size).  The rest are silent padding that a
partial-batch flush dispatches."""

import spans


def read(ctx):
    snap = spans.snapshot()
    if snap is None:
        return None
    return 100.0 * spans.ratio(snap.counters.get("serve.rows_live", 0),
                               snap.counters.get("serve.rows_slots", 0))
