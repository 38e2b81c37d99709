"""How late the generator sent, against each request's due time."""

import numpy as np


def read(ctx, q):
    rec = ctx["measured"]
    if not len(rec["due"]):
        return None
    return float(np.percentile((rec["sent"] - rec["due"]) * 1000.0, q, method="higher"))
