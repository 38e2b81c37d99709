"""A gauge of the server's last snapshot in the window, as it stands."""


def read(ctx, key):
    value = ctx["snapshot_end"].get(key)
    return None if value is None else float(value)
