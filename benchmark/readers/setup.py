"""Process start to the first measured send: loading, compiling, warming."""


def read(ctx):
    return float(ctx["startup_seconds"])
