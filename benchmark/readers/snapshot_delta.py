"""Growth of one server counter between the snapshots at the window's ends."""


def read(ctx, key):
    value = ctx["snapshot_delta"].get(key)
    return None if value is None else float(value)
