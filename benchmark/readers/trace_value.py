"""One number of the trace reduction (``benchmark/trace_reduce.py``)."""


def read(ctx, key, scale=1.0):
    trace = ctx.get("trace")
    if not trace or trace.get(key) is None:
        return None
    return float(scale * trace[key])
