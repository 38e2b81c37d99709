"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The parent imports no jax.  It starts the program's server
(``bin/server --device-step``, through ``benchmark/server_entry.py``) with
the configuration's flags; the server owns the chip, and a run that finds no
TPU fails: the command line has no switch for anything else.  Generator
processes (``benchmark/generators/<name>.py``, ``JAX_PLATFORMS=cpu``) warm
the path with the cell's own traffic, then measure for ``--seconds``, stop
offering, drain; the parent reads a sample of keys back, stops the server,
checks the answers (``benchmark/check.py``) and prints the result as the
last line of stdout.  Set-up time runs from process start to the first
measured send.  A record of the history is one command; the further keys
of a command over several are rows of a side table (the ``more_*``
columns, ``benchmark/check.py``), carried beside the records.

Everything that belongs to one cell is data found by name from
``BENCHMARK.json``: the configuration (``benchmark/configs``), the mix
(``benchmark/traffic``), the cell's own parameters (``benchmark/cells``,
optional), each metric's reader (``benchmark/end_to_end``,
``benchmark/layer_metrics`` -> ``benchmark/readers``).  This file holds no
cell, protocol or metric name.
"""

from __future__ import annotations

import time

_STARTED = time.monotonic()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark.check import MORE, check_history, command_rows  # noqa: E402

BANNER_LIMIT_S = 600.0   # the server's start, the first import of jax included
COMPILE_LIMIT_S = 900.0  # the first commands of a cold checkout compile the round
SNAPSHOT_MS = 250
TRACE_BEFORE_END_S = 4.0  # where in the window the capture of a traced run starts


class RunFailed(Exception):
    """The run cannot report a result; the message says why."""


def log(*parts) -> None:
    """A line before the last: for the reader of the run, not the driver."""
    print(*parts, flush=True)


# --- what a cell is: data found by name ------------------------------------


def _load(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_cell(root: str, workload: str, overrides: dict | None = None) -> dict:
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    base = os.path.join(root, bench["paths"][0])
    cells = {cell["name"]: cell for cell in bench["workloads"]}
    if workload not in cells:
        raise RunFailed(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _load(os.path.join(root, entry["file"]))
    mix = _load(os.path.join(base, "traffic", cell["traffic"] + ".json"))
    own = os.path.join(base, "cells", workload + ".json")
    if os.path.exists(own):
        mix.update(_load(own))
    mix.update(overrides or {})

    def metrics(section: str, folder: str) -> list[dict]:
        chosen = []
        for metric in bench[section]:
            if workload not in metric.get("workloads", [workload]):
                continue
            if section == "per_layer" and "workloads" not in metric:
                moved = next(m for m in bench["end_to_end"] if m["name"] == metric["moves"])
                if workload not in moved.get("workloads", [workload]):
                    continue
            definition = _load(os.path.join(base, folder, metric["name"] + ".json"))
            chosen.append({**metric, "reader": definition["reader"],
                           "args": definition.get("args", {})})
        return chosen

    return {
        "name": workload, "chips": cell["chips"], "config": config, "mix": mix, "base": base,
        "end_to_end": metrics("end_to_end", "end_to_end"),
        "per_layer": metrics("per_layer", "layer_metrics"),
    }


def _module(base: str, folder: str, name: str):
    """A reader or generator of the benchmark, found by name under ``base``
    (the checkout's own copy: a later PR adds files, it edits none)."""
    spec = importlib.util.spec_from_file_location(
        f"_benchmark_{folder}_{name}", os.path.join(base, folder, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_metrics(metrics: list[dict], ctx: dict) -> dict:
    """Each metric through its reader; one that finds nothing is left out."""
    out = {}
    for metric in metrics:
        reader = _module(ctx["base"], "readers", metric["reader"])
        value = reader.read(ctx, **metric["args"])
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


# --- processes --------------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def _read_json(path: str):
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None  # not written yet, or being replaced


def _tail(path: str, limit: int = 3000) -> str:
    try:
        with open(path, errors="replace") as fh:
            return fh.read()[-limit:]
    except OSError:
        return ""


def _snapshot(path: str) -> dict:
    for _ in range(40):
        snap = _read_json(path)
        if snap is not None:
            return snap
        time.sleep(0.05)
    raise RunFailed(f"the server wrote no snapshot at {path}")


def _expect(child: subprocess.Popen, word: str, deadline: float, err_path: str) -> str:
    """The child's next stdout line, which must start with ``word``."""
    import select

    while True:
        ready, _, _ = select.select([child.stdout], [], [], 0.5)
        if ready:
            line = child.stdout.readline()
            if line.startswith(word):
                return line[len(word):].strip()
            if not line:
                raise RunFailed(f"a generator ended before {word}:\n{_tail(err_path)}")
        elif time.monotonic() > deadline:
            raise RunFailed(f"a generator did not reach {word} in time:\n{_tail(err_path)}")


def _pin(server: subprocess.Popen, children: list[subprocess.Popen]) -> None:
    """Each generator on a core of its own and the server on the rest, where
    the host has cores to spare: a generator that migrates, or shares a core
    with the server's threads, shows as run-to-run drift of the latencies."""
    cores = sorted(os.sched_getaffinity(0))
    if len(cores) < 2 * len(children) + 4:
        return
    for child in children:
        os.sched_setaffinity(child.pid, {cores.pop()})
    os.sched_setaffinity(server.pid, set(cores))
    log(f"# pinned: generators on one core each, the server on {len(cores)} cores")


def _stop(process: subprocess.Popen, sig=signal.SIGTERM, wait_s: float = 60.0):
    if process.poll() is None:
        process.send_signal(sig)
        try:
            process.wait(timeout=wait_s)
        except subprocess.TimeoutExpired:
            process.kill()
            process.wait()
    return process.returncode


# --- the run -----------------------------------------------------------------


def run_cell(
    workload: str, seed: int, seconds: float, trace: bool, *,
    root: str = ROOT, platform: str = "tpu", overrides: dict | None = None,
    config_overrides: dict | None = None, started: float | None = None,
    server_module: str = "benchmark.server_entry",
) -> dict:
    """Run one cell once; returns the result object of the last line.

    ``platform`` is what the server must report (the command line passes
    "tpu", always); ``overrides`` replace mix parameters and
    ``config_overrides`` configuration keys (tests and the knee sweep use
    them, at small sizes or other rates); ``server_module`` lets a test put a
    server with a broken timed path in the program's place."""
    started = _STARTED if started is None else started
    cell = load_cell(root, workload, overrides)
    config, mix = {**cell["config"], **(config_overrides or {})}, cell["mix"]
    generator = _module(cell["base"], "generators", mix["generator"])
    out_dir = os.path.join(root, "benchmark_out", workload, f"trace{int(trace)}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    snap_path = os.path.join(out_dir, "snapshot.json")
    memory_path = os.path.join(out_dir, "memory.json")
    port = _free_port()
    env = {**os.environ, "PYTHONPATH": root}
    env.pop("BENCH_RUN", None)
    if platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    n_procs = int(mix["generator_processes"])
    log(f"# host cores {os.cpu_count()}, generator processes {n_procs}, "
        f"cell {workload}, seed {seed}, window {seconds} s, trace {int(trace)}")

    server_log = os.path.join(out_dir, "server.out")
    server_err = os.path.join(out_dir, "server.err")
    with open(server_log, "w") as out_f, open(server_err, "w") as err_f:
        server = subprocess.Popen(
            [sys.executable, "-m", server_module, memory_path, "--device-step",
             *config["server_flags"], "--client-port", str(port),
             "--metrics-file", snap_path, "--metrics-interval", str(SNAPSHOT_MS)],
            stdout=out_f, stderr=err_f, cwd=root, env=env,
        )
    children: list[subprocess.Popen] = []
    try:
        deadline = time.monotonic() + BANNER_LIMIT_S
        while "serving clients" not in _tail(server_log):
            if server.poll() is not None:
                raise RunFailed(f"the server exited with {server.returncode} before its "
                                f"banner:\n{_tail(server_err)}")
            if time.monotonic() > deadline:
                raise RunFailed(f"no banner in {BANNER_LIMIT_S} s:\n{_tail(server_err)}")
            time.sleep(0.1)
        if f"platform={platform} " not in _tail(server_log):
            raise RunFailed(f"the server does not serve from {platform}: {_tail(server_log)}")
        devices = re.search(r" devices=(\d+) ", _tail(server_log))
        if devices is None or int(devices.group(1)) < cell["chips"]:
            raise RunFailed(f"the cell asks for {cell['chips']} chips: {_tail(server_log)}")
        log(f"# server up after {time.monotonic() - started:.1f} s: {_tail(server_log).strip()}")

        gen_env = {**env, "JAX_PLATFORMS": "cpu"}
        for proc in range(n_procs):
            plan = {
                "host": "127.0.0.1", "port": port, "seed": seed, "proc_index": proc,
                "n_procs": n_procs, "mix": mix, "payload_bytes": config["payload_bytes"],
                "seconds": seconds, "compile_limit_s": COMPILE_LIMIT_S,
                "out": os.path.join(out_dir, f"records_{proc}.npz"),
            }
            plan_path = os.path.join(out_dir, f"plan_{proc}.json")
            with open(plan_path, "w") as fh:
                json.dump(plan, fh)
            with open(os.path.join(out_dir, f"generator_{proc}.err"), "w") as err_f:
                children.append(subprocess.Popen(
                    [sys.executable, "-m", f"benchmark.generators.{mix['generator']}", plan_path],
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=err_f,
                    text=True, cwd=root, env=gen_env,
                ))
        _pin(server, children)
        limit = time.monotonic() + COMPILE_LIMIT_S + float(mix["warmup_s"]) + float(mix["drain_limit_s"])
        for proc, child in enumerate(children):
            _expect(child, "READY", limit, os.path.join(out_dir, f"generator_{proc}.err"))

        snap_start = _snapshot(snap_path)
        t0 = time.monotonic() + 0.25
        for child in children:
            child.stdin.write(f"GO {t0!r}\n")
            child.stdin.flush()
        startup_seconds = t0 - started
        # the counters are read over the whole window, or in a traced run over
        # the stretch before its one capture of about a second: the profiler
        # stalls the server when it starts and stops
        counted_s = max(seconds / 2, seconds - TRACE_BEFORE_END_S) if trace else seconds
        time.sleep(max(0.0, t0 + counted_s - time.monotonic()))
        snap_end = _snapshot(snap_path)
        if trace:
            server.send_signal(signal.SIGUSR2)
            time.sleep(max(0.0, t0 + seconds - time.monotonic()))

        limit = time.monotonic() + 2 * float(mix["drain_limit_s"]) + 60.0
        reports = [
            json.loads(_expect(child, "DONE", limit, os.path.join(out_dir, f"generator_{proc}.err")))
            for proc, child in enumerate(children)
        ]
        for child in children:
            child.wait(timeout=30)
        drain_end = time.monotonic()

        parts = [dict(np.load(os.path.join(out_dir, f"records_{proc}.npz")))
                 for proc in range(n_procs)]
        records = {name: np.concatenate([part[name] for part in parts]) for name in parts[0]}
        keys = _readback_keys(records, int(mix["readback_keys"]), seed)
        if hasattr(generator, "read_back_mix"):  # it has to know the mix: which shard holds a key
            readback = generator.read_back_mix("127.0.0.1", port, seed, mix, config["payload_bytes"],
                                               keys, float(mix["drain_limit_s"]))
        else:
            readback = generator.read_back("127.0.0.1", port, seed, int(mix["clients"]),
                                           config["payload_bytes"], keys, float(mix["drain_limit_s"]))
        records = {name: np.concatenate([records[name], readback[name]]) for name in records}

        rc = _stop(server)
        snap_final = _snapshot(snap_path)
        memory = _read_json(memory_path) or {}
    finally:
        for child in children:
            _stop(child, signal.SIGKILL, 5.0)
        _stop(server, signal.SIGKILL, 5.0)

    # --- after the window: the check, then the numbers ---
    np.savez(os.path.join(out_dir, "history.npz"), **records)  # what the control mutates
    strays = records.pop("strays")
    verdict = check_history(records, strays)
    backend = snap_final.get("backend", {})
    delta = {key: snap_end[key] - snap_start[key] for key in snap_end
             if isinstance(snap_end[key], (int, float)) and key in snap_start}
    served = backend.get("platform") == platform and delta.get("device_dispatches", 0) > 0
    if not served:
        verdict["witnesses"].insert(0, {
            "check": "device_served", "note": "the final snapshot must name the platform and "
            "show device_dispatches grown in the window",
            "ops": [{"platform": backend.get("platform"),
                     "device_dispatches_in_window": delta.get("device_dispatches")}]})
    correct = verdict["correct"] and served

    # a record is a command; a command's further keys (the more_* columns)
    # are in the window where their command is
    in_window = records["phase"] == 1
    more_in_window = in_window[command_rows(records)]
    measured = {name: col[more_in_window if name.startswith(MORE) else in_window]
                for name, col in records.items()}
    failed = int(np.count_nonzero(measured["status"] != 0))
    ctx = {
        "measured": measured, "t0": t0, "seconds": seconds, "drain_end": drain_end,
        "startup_seconds": startup_seconds, "snapshot_delta": delta, "snapshot_end": snap_end,
        "counted_s": counted_s,  # the stretch of the window the counter deltas cover
        "config": config, "mix": mix, "trace": None, "base": cell["base"],
    }
    device = {
        "platform": backend.get("platform"), "kind": backend.get("device_kind"),
        "count": backend.get("device_count"),
        "memory_peak_bytes": max(memory.get("peak_bytes_in_use") or [0]),
    }
    result = {"correct": bool(correct), "attempted": int(len(measured["due"])), "failed": failed}
    if trace:
        reduced = _reduce_trace(out_dir, root, platform, device["kind"])
        ctx["trace"] = reduced
        if "busy_s" in reduced:
            device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
        elif platform == "tpu":
            raise RunFailed(f"the capture shows no device operation: {reduced!r}")
    metrics = {kind: read_metrics(cell[kind], ctx) for kind in ("end_to_end", "per_layer")}
    result["metrics"] = metrics["per_layer" if trace else "end_to_end"]
    result["device"] = device
    # every number the verdict compared, beside its limit; last in the line
    result["compared"] = {
        "violations": {"value": verdict["stats"]["violations"], "limit": 0},
        "device_dispatches_in_window": {"value": delta.get("device_dispatches", 0), "limit": ">=1"},
    }
    _report(ctx, reports, verdict, result, {**metrics["end_to_end"], **metrics["per_layer"]},
            server_rc=rc, out_dir=out_dir)
    return result


def _readback_keys(records: dict, count: int, seed: int) -> list[int]:
    """A seeded sample of the keys written, whether first or further keys of
    their commands, the hottest among them."""
    further = records.get("more_key", np.zeros(0, np.int32))
    written, times = np.unique(np.concatenate([
        records["key"][records["op"] == 0], further[records["op"][command_rows(records)] == 0],
    ]), return_counts=True)
    hottest = written[np.argsort(-times, kind="stable")[: count // 8]]
    rest = np.setdiff1d(written, hottest)
    rng = np.random.default_rng([int(seed), 23])
    sample = rng.choice(rest, size=min(len(rest), count - len(hottest)), replace=False)
    return [int(key) for key in np.concatenate([hottest, sample])]


def _reduce_trace(out_dir: str, root: str, platform: str, device_kind) -> dict:
    """The capture the server left next to its snapshot, reduced in a process
    of its own (it imports jax; the parent does not)."""
    for _ in range(100):  # the server writes the capture after stop_trace
        if glob.glob(os.path.join(out_dir, "device_trace_*", "**", "*.xplane.pb"), recursive=True):
            break
        time.sleep(0.1)
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.trace_reduce", out_dir, platform, str(device_kind)],
        capture_output=True, text=True, cwd=root, timeout=300,
        env={**os.environ, "PYTHONPATH": root, "JAX_PLATFORMS": "cpu"},
    )
    if done.returncode != 0:
        raise RunFailed(f"the trace reduction failed:\n{done.stderr[-2000:]}")
    return json.loads(done.stdout.strip().splitlines()[-1])


def _report(ctx, reports, verdict, result, metrics, server_rc, out_dir) -> None:
    """The lines before the last."""
    rec, seconds = ctx["measured"], ctx["seconds"]
    ok = rec["status"] == 0
    late = (rec["sent"] - rec["due"]) * 1000.0
    latency = np.where(ok, rec["acked"], ctx["drain_end"]) - rec["due"]

    def pct(values, q):
        return float(np.percentile(values, q, method="higher")) if len(values) else None

    log("# offered", len(rec["due"]) / seconds, "cmds/s; acknowledged in the window",
        float(np.count_nonzero(ok & (rec["acked"] <= ctx["t0"] + seconds)) / seconds),
        "cmds/s; failed", result["failed"], "of", result["attempted"])
    log("# lateness ms p50/p95/p99/max", pct(late, 50), pct(late, 95), pct(late, 99), pct(late, 100))
    log("# latency ms p50/p95/p99/max", *(None if v is None else v * 1000.0
                                          for v in (pct(latency, q) for q in (50, 95, 99, 100))),
        f"over {len(latency)} requests")
    log("# generators", json.dumps(reports))
    log(f"# server counter deltas over the first {ctx['counted_s']:.1f} s of the window",
        json.dumps(ctx["snapshot_delta"]))
    log("# metrics", json.dumps({name: m["value"] for name, m in metrics.items()}))
    log("# check: violations", verdict["stats"]["violations"], "(limit 0);",
        json.dumps(verdict["stats"]), "; server exit code (not compared)", server_rc)
    if verdict["witnesses"]:
        with open(os.path.join(out_dir, "witness.json"), "w") as fh:
            json.dump(verdict["witnesses"], fh, indent=1)
        log("# WITNESS", json.dumps(verdict["witnesses"][0]))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        print("benchmark: JAX_PLATFORMS=cpu; a run needs the TPU", file=sys.stderr)
        return 1
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except RunFailed as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    for name, pair in result["compared"].items():
        print(f"compared {name}: {pair['value']} (limit {pair['limit']})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
