"""Front end: host milliseconds per request in admission (drain and
overload checks, route, width, finiteness, deadline estimate) and in the
rest of ``submit`` outside encode and batch execution (handle, queue),
from the program's ``serve.admit`` spans and the self time of its
``serve.submit`` spans."""

import spans


def read(ctx):
    snap = spans.snapshot()
    if snap is None:
        return None
    s = snap.self_s.get("serve.submit", 0.0) + snap.total_s.get("serve.admit", 0.0)
    return 1e3 * s / ctx["requests"]
