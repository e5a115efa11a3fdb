"""p95 of the wait from a request's due time to its admission into a
slot, over the requests due in the window (one not admitted by the end
counts its wait so far); host clock, seen by the harness after each
``ServeEngine._admit``."""
import numpy as np


def read(ctx):
    waits = ctx.host.get("queue_waits")
    if not waits:
        return None
    return float(np.percentile(np.asarray(waits, np.float64), 95))
