"""Ratio of sums of server counter deltas over the window.

``num`` and ``den`` list counter names; ``den_times_config`` names a number
of the configuration file the denominator is multiplied by (a round's
capacity); ``scale`` multiplies the result (100 for a share in %)."""


def read(ctx, num, den, den_times_config=None, scale=1.0):
    delta = ctx["snapshot_delta"]
    if any(key not in delta for key in (*num, *den)):
        return None
    bottom = sum(delta[key] for key in den)
    if den_times_config is not None:
        bottom *= ctx["config"][den_times_config]
    if bottom <= 0:
        return None
    return float(scale * sum(delta[key] for key in num) / bottom)
