"""The load generator for YCSB traffic: ``kv_loop``'s closed loop over a
store that was loaded first.

YCSB has two phases: *load* inserts every record once, *run* reads and
updates them.  Without the load, a mix of 95% reads returns records nobody
wrote and a reply carries nothing.  So before its warm-up each process
``Put``s its share of the mix's ``load_records`` records (keys ``1 ..
load_records``, the ranks the zipf generator draws) once, each with a whole
value of the configuration's ``payload_bytes``, through the served path,
closed loop.  The load's requests are records of phase ``WARM`` like the
warm-up's: the check replays them (a read in the window names a loaded
value's write), and set-up time counts them.  Everything else (connection,
values, keys, clocks, records, the stdin/stdout protocol, the read-back) is
``kv_loop``'s, imported.

Run as ``python -m benchmark.generators.kv_ycsb <plan.json>`` by
``benchmark.run``.  Mix parameters beside ``kv_loop``'s: ``load_records``;
``loop`` must be ``closed``.  A process whose load was not acknowledged in
full ends before ``READY``: a run on a store that is not loaded is not the
cell.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

from benchmark.generators.kv_loop import (  # noqa: F401  (read_back: the harness calls it)
    _SEND_CHUNK,
    MEASURED,
    OK,
    WARM,
    Engine,
    own_clients,
    read_back,
)


def load_keys(load_records: int, proc_index: int, n_procs: int) -> np.ndarray:
    """The records this process loads: the processes' shares are a partition
    of ``1 .. load_records``."""
    return np.arange(1 + proc_index, load_records + 1, n_procs, dtype=np.int32)


def client_keys(keys: np.ndarray, own: np.ndarray) -> dict[int, list[int]]:
    """Which client loads which of the process's records, in which order: a
    function of the split alone, so every client's sequence numbers, and with
    them its draws after the load, are the seed's."""
    return {int(client): keys[local :: len(own)].tolist() for local, client in enumerate(own)}


def run_load(engine: Engine, own: np.ndarray, keys: np.ndarray, deadline: float) -> int:
    """``Put`` every key once, each client its next when its last is
    acknowledged; how many of them were acknowledged by ``deadline``."""
    left = {client: iter(todo) for client, todo in client_keys(keys, own).items()}
    first = engine.rec.n
    ready = [int(c) for c in own]
    while (ready or engine.outstanding) and time.monotonic() < deadline:
        now = time.monotonic()
        for client in ready[:_SEND_CHUNK]:
            key = next(left[client], None)
            if key is not None:
                engine.submit(client, key, False, now, now, WARM)
        del ready[:_SEND_CHUNK]
        ready.extend(engine.pump(0.0 if ready else 0.005))
    return int(np.count_nonzero(engine.rec.cols["status"][first : engine.rec.n] == OK))


def main(plan_path: str) -> None:
    with open(plan_path) as fh:
        plan = json.load(fh)
    mix = plan["mix"]
    if mix["loop"] != "closed" or int(mix.get("keys_per_command", 1)) != 1:
        raise SystemExit("kv_ycsb runs a closed loop of one key per command")
    seed, proc, n_procs = plan["seed"], plan["proc_index"], plan["n_procs"]
    own = own_clients(int(mix["clients"]), proc, n_procs)
    engine = Engine(
        plan["host"], plan["port"], seed, int(mix["clients"]), plan["payload_bytes"],
        own, mix["key_gen"], float(mix["read_share"]), proc,
    )
    try:
        drain_limit = float(mix["drain_limit_s"])
        warm_s, seconds = float(mix["warmup_s"]), float(plan["seconds"])
        # the load's first commands pay whatever the server still has to
        # compile or load
        keys = load_keys(int(mix["load_records"]), proc, n_procs)
        began = time.monotonic()
        loaded = run_load(engine, own, keys, began + plan["compile_limit_s"])
        load_s = time.monotonic() - began
        if loaded != len(keys):
            raise SystemExit(f"loaded {loaded} of {len(keys)} records in {load_s:.1f} s")
        now = time.monotonic()
        engine.run_closed(now, now + warm_s, own, WARM)
        warm_drained = engine.drain(drain_limit)
        print("READY", flush=True)
        word, t0 = sys.stdin.readline().split()
        if word != "GO":
            raise SystemExit(f"expected GO, got {word!r}")
        t0 = float(t0)
        engine.run_closed(t0, t0 + seconds, own, MEASURED)
        stop = time.monotonic()
        drained = engine.drain(drain_limit)
        np.savez(plan["out"], **engine.history())
        print("DONE " + json.dumps({
            "proc": proc, "loaded": loaded, "load_s": load_s, "warm_drained": warm_drained,
            "drained": drained, "stopped_offering_s": stop - t0,
            "drain_s": time.monotonic() - stop,
        }), flush=True)
    finally:
        engine.close()


if __name__ == "__main__":
    main(sys.argv[1])
