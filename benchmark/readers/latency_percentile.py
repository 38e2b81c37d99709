"""Percentile of the time from when a command was due to its reply, over
every request due in the window.  A request that failed has no reply: it
enters with the time to the end of the drain, which is the least its
latency can have been (and keeps the number finite)."""

import numpy as np


def read(ctx, q):
    rec = ctx["measured"]
    if not len(rec["due"]):
        return None
    replied = np.where(rec["status"] == 0, rec["acked"], ctx["drain_end"])
    return float(np.percentile((replied - rec["due"]) * 1000.0, q, method="higher"))
