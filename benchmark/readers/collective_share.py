"""One number of ``benchmark/collectives.py`` (``share``: the device's time
in collective operations over its busy time), in %, from the capture of a
traced run.

The capture is found as ``host_spans_share`` finds it: through the server's
own word for where it puts captures (``profile_dir`` in its snapshot).  A
server that does not say, an untraced run and a capture without device
operations all read as nothing.  The reduction runs once a run, in a process
of its own (it imports jax; the parent does not), and prints the collectives
it found as a line of its own, for the reader of the run."""

import json
import os
import subprocess
import sys

KEPT = "_collectives"  # the reduction of this run, kept in the context


def _reduce(ctx) -> dict:
    snapshot = ctx["snapshot_end"]
    where = snapshot.get("profile_dir")
    platform = (snapshot.get("backend") or {}).get("platform")
    if not ctx.get("trace") or not where or not platform:
        return {}
    root = os.path.dirname(ctx["base"])
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.collectives", where, platform],
        capture_output=True, text=True, cwd=root, timeout=300,
        env={**os.environ, "PYTHONPATH": root, "JAX_PLATFORMS": "cpu"},
    )
    if done.returncode != 0:
        print("# collectives: the reduction failed:", done.stderr[-500:], flush=True)
        return {}
    reduced = json.loads(done.stdout.strip().splitlines()[-1])
    if reduced:
        print("# collectives", json.dumps(reduced), flush=True)
    return reduced


def read(ctx, key):
    if KEPT not in ctx:
        ctx[KEPT] = _reduce(ctx)
    value = ctx[KEPT].get(key)
    return None if value is None else float(100.0 * value)
