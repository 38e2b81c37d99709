"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py        # on a machine with a TPU; exit 0 = proven

Three legs, one after the other, each with its own device-owning process
(a chip has one owner at a time; this parent imports no jax):

1. **served** — the served path through the entry points a user calls:
   ``python -m fantoch_tpu.bin.server --device-step`` at the upstream
   deployment's shape (``fantoch_exp`` defaults: n=5, f=1, 1M keys per
   shard -> 1,048,576 key buckets, device batch 4096), then two
   ``python -m fantoch_tpu.bin.client`` bursts of 64 clients x 128
   commands: zipf(1.0) over 1M keys (pays the compile: set-up), then
   50% conflict rate (warm).  Pass: every command acknowledged in both;
   the server's snapshot shows ``executed == replied ==`` commands sent,
   ``device_dispatches > 0`` and no XLA compile during the warm burst;
   SIGTERM stops the server with exit 0 and a final snapshot.
2. **kernel** — the founding kernel: ``resolve_functional_keyed`` over
   ``bench.build_workload(1_000_000, 0.5)``; every command resolved, no
   overflow, and the emitted order checked against the input graph on
   the host (a permutation in which every dependency precedes its
   dependent).
3. **planes** — the table, pred and graph plane programs at
   ``bench.py``'s default sizes; each row asserts per-key order parity
   with its sequential host twin in-row; on top: dispatches > 0, the
   residency invariant on ``resident_uploads`` and zero failovers.

Exit 0 only if every leg passed on ``platform == "tpu"``.  Stdout is
then two JSON lines: the report (every leg's result, mesh shape, cache
directory, compile seconds, ``reduced``), and LAST the verdict, exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``
with the device as jax reported it.  A leg that raises, times out,
finds the CPU or reports a plane failover fails the run: non-zero exit,
the reason on stderr, no result on stdout.  ``JAX_PLATFORMS=cpu`` is
refused at once — this script exists to prove the chip.

Sizes shrink only if the time limit forces it; ``reduced`` in the JSON
says so (today: nothing is reduced).  The legs are functions taking
their sizes, so tests/test_chip_smoke.py drives them tiny on the CPU,
and one leg runs alone as e.g.
``python -c "import chip_smoke; print(chip_smoke.leg_served(protocol='newt'))"``.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
# the whole run must end inside the driver's 1200 s, compilation included
BUDGET_S = 1100.0


class LegFailed(Exception):
    """A leg's pass condition did not hold; the message says which."""


def _check(cond: bool, what: str, detail=None) -> None:
    if not cond:
        if isinstance(detail, str):  # a log tail: keep its lines
            raise LegFailed(f"{what}:\n{detail}")
        raise LegFailed(what if detail is None else f"{what}: {detail!r}")


# ---------------------------------------------------------------------------
# leg 1: the served path (runs in the caller: stdlib only, the server it
# starts owns the device)
# ---------------------------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def _read_json(path: str):
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None  # not written yet


def _tail(path: str, limit: int = 4000) -> str:
    try:
        with open(path, errors="replace") as fh:
            return fh.read()[-limit:]
    except OSError:
        return ""


def _client_burst(port: int, ids: str, commands: int, workload, timeout_s: float):
    """One ``bin/client`` burst; returns its JSON summary.  The client
    plane never imports jax; ``JAX_PLATFORMS=cpu`` states that it must
    not ask for the chip its server owns."""
    out = subprocess.run(
        [
            sys.executable, "-m", "fantoch_tpu.bin.client",
            "--ids", ids, "--addresses", f"0=127.0.0.1:{port}",
            "--commands-per-client", str(commands), *workload,
        ],
        capture_output=True, text=True, timeout=timeout_s, cwd=ROOT,
        env={**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu"},
    )
    _check(out.returncode == 0, "client burst failed", out.stderr[-2000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def _wait_snapshot(path: str, replied: int, timeout_s: float = 30.0) -> dict:
    """The server writes its snapshot every 500 ms; wait for the one that
    has caught up with the burst just acknowledged."""
    deadline = time.monotonic() + timeout_s
    snap = None
    while time.monotonic() < deadline:
        snap = _read_json(path)
        if snap is not None and snap.get("replied", 0) >= replied:
            return snap
        time.sleep(0.25)
    raise LegFailed(f"snapshot never reached replied={replied}: {snap!r}")


def leg_served(
    protocol: str = "epaxos",
    n: int = 5,
    f: int = 1,
    key_buckets: int = 1 << 20,
    batch: int = 4096,
    pending: int = 4096,
    clients: int = 64,
    commands: int = 128,
    keys_per_shard: int = 1_000_000,
    workdir: str | None = None,
    timeout_s: float = 600.0,
) -> dict:
    """Leg 1.  Returns the leg's result (``platform`` as the server's
    snapshot reports it); raises :class:`LegFailed` otherwise."""
    import tempfile

    deadline = time.monotonic() + timeout_s
    workdir = workdir or tempfile.mkdtemp(prefix="chip_smoke_")
    metrics = os.path.join(workdir, f"served_{protocol}.json")
    out_log = os.path.join(workdir, f"served_{protocol}.out")
    err_log = os.path.join(workdir, f"served_{protocol}.err")
    port = _free_port()
    argv = [
        sys.executable, "-m", "fantoch_tpu.bin.server", "--device-step",
        "--protocol", protocol, "-n", str(n), "-f", str(f),
        "--client-port", str(port),
        "--device-key-buckets", str(key_buckets),
        "--device-batch", str(batch), "--device-pending", str(pending),
        "--metrics-file", metrics, "--metrics-interval", "500",
    ]
    sent = clients * commands
    t0 = time.monotonic()
    with open(out_log, "w") as out_f, open(err_log, "w") as err_f:
        server = subprocess.Popen(
            argv, stdout=out_f, stderr=err_f, cwd=ROOT,
            env={**os.environ, "PYTHONPATH": ROOT},
        )
    try:
        banner = None
        while banner is None:
            _check(
                server.poll() is None,
                f"server exited with {server.returncode} before its banner",
                _tail(err_log),
            )
            _check(time.monotonic() < deadline, "no banner in time", _tail(err_log))
            lines = [l for l in _tail(out_log).splitlines() if "serving clients" in l]
            banner = lines[0] if lines else None
            time.sleep(0.2)
        banner_s = time.monotonic() - t0

        t1 = time.monotonic()
        first = _client_burst(
            port, f"1-{clients}", commands,
            ["--key-gen", "zipf", "--keys-per-shard", str(keys_per_shard),
             "--zipf-coefficient", "1.0"],
            max(1.0, deadline - time.monotonic()),
        )
        first_s = time.monotonic() - t1
        _check(first["commands"] == sent, "first burst not fully acknowledged", first)
        cold = _wait_snapshot(metrics, sent)

        t2 = time.monotonic()
        second = _client_burst(
            port, f"{1001}-{1000 + clients}", commands, ["--conflict-rate", "50"],
            max(1.0, deadline - time.monotonic()),
        )
        second_s = time.monotonic() - t2
        _check(second["commands"] == sent, "second burst not fully acknowledged", second)
        warm = _wait_snapshot(metrics, 2 * sent)

        _check(
            warm["executed"] == warm["replied"] == warm["submitted"] == 2 * sent,
            "snapshot does not account for every command",
            {k: warm[k] for k in ("submitted", "executed", "replied")},
        )
        _check(warm["device_dispatches"] > 0, "no device dispatch", warm)
        _check(
            warm["jax_recompiles"] == cold["jax_recompiles"],
            "XLA compiled during the warm burst",
            (cold["jax_recompiles"], warm["jax_recompiles"]),
        )

        term_at = time.time()
        server.send_signal(signal.SIGTERM)
        try:
            rc = server.wait(timeout=60)
        except subprocess.TimeoutExpired:
            raise LegFailed("server did not stop within 60 s of SIGTERM")
        _check(rc == 0, f"server exited with {rc} on SIGTERM", _tail(err_log))
        final = _read_json(metrics)
        _check(
            final is not None
            and os.path.getmtime(metrics) >= term_at - 1.0
            and final["replied"] == 2 * sent,
            "no final snapshot after SIGTERM", final,
        )
    finally:
        if server.poll() is None:
            server.kill()
            server.wait()
    backend = final["backend"]
    return {
        "ok": True,
        "platform": backend["platform"],
        "backend": backend,
        "banner": banner,
        "protocol": protocol,
        "shape": {"n": n, "f": f, "key_buckets": key_buckets, "batch": batch,
                  "pending": pending, "clients": clients,
                  "commands_per_client": commands},
        "acknowledged": [first["commands"], second["commands"]],
        "executed": final["executed"],
        "replied": final["replied"],
        "device_dispatches": final["device_dispatches"],
        "recompiles_cold": cold["jax_recompiles"],
        "recompiles_warm_burst": warm["jax_recompiles"] - cold["jax_recompiles"],
        "cache_hits": final["jax_cache_hits"],
        "cache_misses": final["jax_cache_misses"],
        "compile_s": round(final["jax_compile_ms"] / 1000.0, 3),
        # host-clock walls of this run, for orientation only
        "banner_s": round(banner_s, 1),
        "first_burst_s": round(first_s, 1),
        "second_burst_s": round(second_s, 1),
        "client_summaries": [first, second],
    }


# ---------------------------------------------------------------------------
# legs 2 and 3: each owns the device, so each runs in a child of its own
# ---------------------------------------------------------------------------


def _own_device(entry: str) -> dict:
    """Child start-up: the platform rule, the compile cache, counters."""
    sys.path.insert(0, ROOT)
    from fantoch_tpu.bin.common import start_device_entry
    from fantoch_tpu.observability.device import subscribe_recompiles

    device = start_device_entry(entry)
    subscribe_recompiles()
    return device


def _compile_tally() -> dict:
    from fantoch_tpu.observability import device

    return {
        "compile_s": round(device.compile_ms() / 1000.0, 3),
        "recompiles": device.recompile_count(),
        "cache_hits": device.cache_hit_count(),
        "cache_misses": device.cache_miss_count(),
    }


def leg_kernel(batch: int = 1_000_000, conflict: float = 0.5) -> dict:
    """Leg 2: the founding kernel on the north-star workload."""
    device = _own_device("chip_smoke kernel leg")
    import jax.numpy as jnp
    import numpy as np

    import bench
    from fantoch_tpu.ops.graph_resolve import (
        _residual_size_for,
        resolve_functional_keyed,
    )

    key, dep, src, seq = bench.build_workload(batch, conflict)
    res = resolve_functional_keyed(
        jnp.asarray(key), jnp.asarray(dep), jnp.asarray(src), jnp.asarray(seq),
        residual_size=_residual_size_for(batch), return_structure=False,
    )
    n_resolved, overflow = int(res.n_resolved), bool(res.overflow)
    _check(n_resolved == batch, f"resolved {n_resolved}/{batch}")
    _check(not overflow, "residual overflow")
    # the reference: the input graph itself.  The order must execute
    # every command once, each after the command it depends on
    order = np.asarray(res.order)
    position = np.full(batch, -1, np.int64)
    position[order] = np.arange(batch)
    _check(bool((position >= 0).all()), "order is not a permutation")
    has_dep = dep >= 0
    _check(
        bool((position[dep[has_dep]] < position[has_dep]).all()),
        "a command was ordered before its dependency",
    )
    return {
        "ok": True, **device, "batch": batch, "conflict": conflict,
        "n_resolved": n_resolved, "overflow": overflow,
        "order_checked_against_graph": True, **_compile_tally(),
    }


def leg_planes(table: dict | None = None, pred: dict | None = None,
               graph: dict | None = None) -> dict:
    """Leg 3: the three plane programs against their sequential host
    twins (parity asserted inside each bench row), at ``bench.py``'s
    default sizes unless a test passes smaller ones."""
    device = _own_device("chip_smoke planes leg")
    import bench

    rows = {}
    rows.update(bench.bench_table_path(**(table or {})))
    rows.update(bench.bench_pred_path(**(pred or {})))
    rows.update(bench.bench_graph_plane(**(graph or {})))
    counters = {}
    for plane in ("table", "pred", "graph"):
        prefix = f"{plane}_plane_"
        got = {
            name: rows[prefix + name]
            for name in ("dispatches", "resident_uploads", "grows", "failovers")
        }
        got["compactions"] = rows.get(prefix + "compactions", 0)
        _check(got["dispatches"] > 0, f"{plane} plane never dispatched", got)
        # the residency invariant: one lazy materialization, plus one
        # counted re-upload per grow or compaction — never one per batch
        _check(
            1 <= got["resident_uploads"] <= 1 + got["grows"] + got["compactions"],
            f"{plane} plane broke the residency invariant", got,
        )
        _check(got["failovers"] == 0, f"{plane} plane failed over", got)
        counters[plane] = got
    return {
        "ok": True, **device, "host_twin_parity": True, "planes": counters,
        "sizes": {
            "table_batch": rows["table_batch"],
            "pred_batch": rows["pred_plane_batch"],
            "graph_batch": rows["graph_plane_batch"],
        },
        **_compile_tally(),
    }


def _run_leg_child(name: str, timeout_s: float) -> dict:
    """Run ``leg_<name>()`` in a fresh interpreter that owns the device;
    its last stdout line is the leg's JSON."""
    code = (
        "import json, chip_smoke; "
        f"print(json.dumps(chip_smoke.leg_{name}()), flush=True)"
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", code], stdout=subprocess.PIPE, text=True,
            timeout=timeout_s, cwd=ROOT,
            env={**os.environ, "PYTHONPATH": ROOT},
        )
    except subprocess.TimeoutExpired:
        raise LegFailed(f"leg {name} exceeded {timeout_s:.0f} s")
    _check(out.returncode == 0, f"leg {name} exited with {out.returncode}",
           out.stdout[-1000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def result_lines(legs: dict, wall_s: float) -> list[str]:
    """What a passed run prints: the report (every leg's result, for the
    records), then the verdict.  The verdict is the LAST line and holds
    exactly ``ok`` and ``device`` — the device as jax reported it to the
    process that served."""
    backend = legs["served"]["backend"]
    report = {
        "report": "chip_smoke",
        "backend": backend,
        "compile_cache_dir": legs["kernel"]["compile_cache_dir"],
        "reduced": [],
        "wall_s": round(wall_s, 1),
        "legs": legs,
    }
    verdict = {
        "ok": True,
        "device": {
            "platform": backend["platform"],
            "kind": backend["device_kind"],
            "count": backend["device_count"],
        },
    }
    return [json.dumps(report), json.dumps(verdict)]


def main() -> int:
    sys.path.insert(0, ROOT)
    if not os.path.isdir(os.path.join(ROOT, "fantoch_tpu")):
        print("chip_smoke.py runs from a checkout of the repo: no "
              f"fantoch_tpu/ next to {__file__}", file=sys.stderr)
        return 2
    from fantoch_tpu.hostenv import cpu_requested

    if cpu_requested():
        print("chip_smoke.py proves the TPU; JAX_PLATFORMS=cpu asks for the "
              "CPU (run the tier-1 tests there instead)", file=sys.stderr)
        return 2
    started = time.monotonic()

    def remaining() -> float:
        return BUDGET_S - (time.monotonic() - started)

    legs: dict = {}
    try:
        legs["served"] = leg_served(timeout_s=min(500.0, remaining()))
        legs["kernel"] = _run_leg_child("kernel", min(300.0, remaining()))
        legs["planes"] = _run_leg_child("planes", remaining())
        for name, leg in legs.items():
            _check(leg["platform"] == "tpu",
                   f"leg {name} ran on {leg['platform']!r}, not the tpu")
    except (LegFailed, subprocess.TimeoutExpired, OSError, KeyError,
            ValueError) as exc:
        print(f"chip_smoke FAILED after {time.monotonic() - started:.0f} s: "
              f"{type(exc).__name__}: {exc}", file=sys.stderr)
        print("legs so far: " + json.dumps(legs), file=sys.stderr)
        return 1
    for line in result_lines(legs, time.monotonic() - started):
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
