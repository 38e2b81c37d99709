"""Share of the window that a server counter of milliseconds grew by.

The growth of ``key`` between the snapshots at the ends of the stretch the
deltas cover (``counted_s``: the whole window, or in a ``--trace 1`` run the
part of it before the capture), over that stretch's milliseconds; ``scale``
multiplies the result (100 for %).  A server whose snapshot has no such
counter reads nothing."""


def read(ctx, key, scale=1.0):
    grown = ctx["snapshot_delta"].get(key)
    if grown is None or ctx["counted_s"] <= 0:
        return None
    return float(scale * grown / (1000.0 * ctx["counted_s"]))
