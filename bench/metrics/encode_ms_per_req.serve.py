"""Front end: host milliseconds per request in the service's encode stage
(the eager ``encoding.encode`` of the series and its fetch to the host),
from the program's ``serve.encode`` spans."""

import spans


def read(ctx):
    snap = spans.snapshot()
    if snap is None:
        return None
    return 1e3 * snap.total_s.get("serve.encode", 0.0) / ctx["requests"]
