"""Commands acknowledged inside the window, per second of window."""

import numpy as np


def read(ctx):
    rec = ctx["measured"]
    inside = (rec["status"] == 0) & (rec["acked"] <= ctx["t0"] + ctx["seconds"])
    return float(np.count_nonzero(inside) / ctx["seconds"])
