"""Percentile of the durations of one stage's spans, in ms, over the spans
that end inside the counted stretch of the window.

The server writes the ring of its round-stage spans as ``round_spans.json``
when it stops, beside its captures (``profile_dir`` in its snapshot), with
times in ns of the monotonic clock the window's start was read from.  A
server that writes none reads as nothing, as do fewer than ``at_least``
spans: a percentile of a handful says nothing."""

import json
import os

import numpy as np


def read(ctx, stage, q, at_least=20):
    where = ctx["snapshot_end"].get("profile_dir")
    if not where:
        return None
    try:
        with open(os.path.join(where, "round_spans.json")) as fh:
            ring = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None
    name, t0, t1 = (ring["columns"].index(column) for column in ("name", "t0_ns", "t1_ns"))
    first, last = ctx["t0"] * 1e9, (ctx["t0"] + ctx["counted_s"]) * 1e9
    took = [(row[t1] - row[t0]) / 1e6 for row in ring["spans"]
            if row[name] == stage and first <= row[t1] <= last]
    if len(took) < at_least:
        return None
    return float(np.percentile(took, q, method="higher"))
