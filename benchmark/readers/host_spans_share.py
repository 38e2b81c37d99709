"""One share of the device's idle time by host stage, in %
(``benchmark/host_spans.py``), from the capture of a traced run.

The capture is found through the server's own word for where it puts
captures (``profile_dir`` in its snapshot); a server that does not say, an
untraced run and a capture without annotated stages all read as nothing.
The reduction runs once a run, in a process of its own (it imports jax; the
parent does not), and its reading of the gaps is printed as a line of its
own, for the reader of the run."""

import json
import os
import subprocess
import sys

KEPT = "_host_spans"  # the reduction of this run, kept in the context


def _reduce(ctx) -> dict:
    snapshot = ctx["snapshot_end"]
    where = snapshot.get("profile_dir")
    platform = (snapshot.get("backend") or {}).get("platform")
    if not ctx.get("trace") or not where or not platform:
        return {}
    root = os.path.dirname(ctx["base"])
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.host_spans", where, platform],
        capture_output=True, text=True, cwd=root, timeout=300,
        env={**os.environ, "PYTHONPATH": root, "JAX_PLATFORMS": "cpu"},
    )
    if done.returncode != 0:
        print("# idle gaps by stage: the reduction failed:", done.stderr[-500:], flush=True)
        return {}
    reduced = json.loads(done.stdout.strip().splitlines()[-1])
    if reduced:
        print("# idle gaps by stage", json.dumps(
            {"longest_s": reduced["longest"], "idle_s_by_stage": reduced["by_stage"]}), flush=True)
    return reduced


def read(ctx, key):
    if KEPT not in ctx:
        ctx[KEPT] = _reduce(ctx)
    value = ctx[KEPT].get(key)
    return None if value is None else float(100.0 * value)
