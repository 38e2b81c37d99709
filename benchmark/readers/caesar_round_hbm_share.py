"""The share of the memory roofline of the Caesar round with a coordinator at
every site, in %: the bytes a dispatch must read and write on a
device (``benchmark/round_bytes_caesar.py``, from the configuration's shape and
the mesh the server reports, times the rounds a dispatch chains: snapshot delta
``rounds`` / ``device_dispatches``) over the device time of a dispatch
(``busy_per_round_s`` of the trace reduction) times the device's published
memory bandwidth (``benchmark/peaks.json``), as ``sites_round_hbm_share`` has
it for the dependency round.

An untraced run, a capture without a device program, a server that names no
TPU, one at whose sites no client registered (its snapshot says
``sites_registered`` under 2, or nothing: the round with one coordinator
served) and a protocol without a byte count all read as nothing; a TPU that
is not in the table of peaks is an error."""

from benchmark.round_bytes_caesar import round_min_bytes
from benchmark.trace_reduce import peaks_for


def read(ctx):
    trace = ctx.get("trace") or {}
    end = ctx["snapshot_end"]
    backend = end.get("backend") or {}
    delta = ctx["snapshot_delta"]
    per_dispatch_s = trace.get("busy_per_round_s")
    if not per_dispatch_s or backend.get("platform") != "tpu":
        return None
    if (end.get("sites_registered") or 0) < 2:
        return None
    if delta.get("device_dispatches", 0) <= 0 or delta.get("rounds", 0) <= 0:
        return None
    replica_axis = (backend.get("mesh_shape") or {}).get("replica", 1)
    per_round = round_min_bytes(ctx["config"], replica_axis)
    if per_round is None:
        return None
    rounds_per_dispatch = delta["rounds"] / delta["device_dispatches"]
    peak = peaks_for(backend.get("device_kind") or "")["hbm_bytes_per_s"]
    return float(100.0 * per_round * rounds_per_dispatch / (per_dispatch_s * peak))
