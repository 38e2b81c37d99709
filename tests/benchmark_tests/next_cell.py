"""What the next PR adds for ``tempo_n5_4shard.zipf07_sat`` (PERF.md s7, #1),
made in a copy of the tree as new files and list entries only: a
configuration with ``--shard-count`` and ``--device-key-width``, a
``kv_multi`` mix, a four-chip cell, the cell's name on the end-to-end metric
it reports, and a per-layer metric that lists only that cell.  The contract
tests hold their rules to such a copy, the end-to-end test runs it small on
the CPU, and on the chip it is run at the deployment's size:

    python3 tests/benchmark_tests/next_cell.py --chips 1 --seed 7 --seconds 20 --trace 0

copies the tree to ``_export/next_cell`` (a directory ``.gitignore`` lists),
adds the cell there and runs it from there.  ``BENCHMARK.json`` of the tree
itself never names the cell."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG, TRAFFIC = "tempo_n5_4shard", "zipf07_sat"
CELL = f"{CONFIG}.{TRAFFIC}"
METRIC = "multi_shard_share.sat"
SOURCE = ("Tempo paper (EuroSys'21) s6, partial replication: YCSB+T, two keys a transaction, "
          "zipf 0.5 / 0.7, 2-6 shards; here 4 shards, one per chip; from memory, see assumed")


def copy_tree(root: str, source: str = ROOT) -> str:
    """``BENCHMARK.json`` and the benchmark's own directories of ``source``,
    copied to ``root``; the program is linked, not copied."""
    os.makedirs(root, exist_ok=True)
    for path in ("benchmark", os.path.join("tests", "benchmark_tests")):
        shutil.copytree(os.path.join(source, path), os.path.join(root, path),
                        ignore=shutil.ignore_patterns("__pycache__"), dirs_exist_ok=True)
    shutil.copy(os.path.join(source, "BENCHMARK.json"), os.path.join(root, "BENCHMARK.json"))
    if not os.path.lexists(os.path.join(root, "fantoch_tpu")):
        os.symlink(os.path.join(source, "fantoch_tpu"), os.path.join(root, "fantoch_tpu"))
    return root


def add_next_cell(root: str, *, chips: int = 4, n: int = 5, shards: int = 4,
                  keys_per_shard: int = 1_000_000, buckets: int = 4_194_304, batch: int = 4096,
                  clients: int = 8192,
                  generator_processes: int = 4, warmup_s: float = 8.0,
                  readback_keys: int = 512) -> str:
    """Write the cell's files under ``root`` and append its entries to
    ``root``'s ``BENCHMARK.json``; returns the cell's name."""
    base = os.path.join(root, "benchmark")

    def write(folder: str, name: str, content: dict) -> None:
        path = os.path.join(base, folder, name + ".json")
        assert not os.path.exists(path), path  # new files only
        with open(path, "w") as fh:
            json.dump(content, fh, indent=1)

    write("configs", CONFIG, {
        "name": CONFIG, "source": SOURCE,
        "deployment": {"protocol": "newt", "n": n, "f": 1, "shards": shards,
                       "keys_per_shard": keys_per_shard, "keys_per_command": 2,
                       "replication": "partial: each shard on its own n replicas",
                       "chips": chips,
                       "layout": f"one --device-step server, {shards} shards x {n} replica rows "
                                 f"on {chips} chip(s)"},
        "server_flags": ["--protocol", "newt", "-n", str(n), "-f", "1",
                         "--shard-count", str(shards), "--device-key-width", "2",
                         "--device-key-buckets", str(buckets),
                         "--device-batch", str(batch), "--device-pending", str(batch)],
        "device_batch": batch, "payload_bytes": 100,
        "guarantees": ["one order of commands across keys and shards", "per-key linearizable writes",
                       "a reply only after execution", "exactly once per rifl",
                       "in memory, no WAL, as upstream runs it"],
        "assumed": {"f": "1", "keys_per_command": "2: YCSB+T as the paper ran it, from memory",
                    "payload_bytes": "100 B", "write_share": "100% writes",
                    "n": f"{n} replicas a shard by the cell's name; the paper's partial-replication "
                         "runs used 3 sites a shard"},
        "reduced": [],
    })
    write("traffic", TRAFFIC, {
        "generator": "kv_multi", "loop": "closed", "clients": clients,
        "generator_processes": generator_processes,
        "key_gen": {"kind": "zipf", "coefficient": 0.7, "keys_per_shard": keys_per_shard},
        "keys_per_command": 2, "shard_count": shards, "read_share": 0.0, "warmup_s": warmup_s,
        "drain_limit_s": 30.0, "readback_keys": readback_keys,
        "assumed": {"key_gen.coefficient": "0.7: the paper's higher skew, from memory",
                    "clients": "as the one-shard saturated mix", "read_share": "0"},
    })
    metric = {"name": METRIC, "unit": "%", "better": "higher", "source": "host_clock",
              "layer": "client plane (the benchmark's generator)", "moves": "goodput_cmds_s",
              "workloads": [CELL]}
    write("layer_metrics", METRIC, {
        **metric, "reader": "record_share", "args": {"column": "shards", "above": 1, "scale": 100.0},
        "reads": "generator records: commands of the window that touched more than one shard, in %"})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        spec = json.load(fh)
    spec["configs"].append({"name": CONFIG, "source": SOURCE,
                            "file": f"benchmark/configs/{CONFIG}.json", "reduced": [],
                            "why": "table family under partial replication: per-shard quorums, "
                                   "multi-shard commands at the max of their shards' clocks"})
    spec["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": chips,
        "why": f"closed loop, {clients} clients, 2 keys a command, zipf 0.7 over {shards} shards: "
               "cross-shard commands, collectives, the reply stage's per-shard aggregation"})
    for entry in spec["end_to_end"]:
        if entry["name"] == "goodput_cmds_s":
            entry["workloads"].append(CELL)
    spec["per_layer"].append(metric)
    with open(path, "w") as fh:
        json.dump(spec, fh, indent=1)
    return CELL


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--root", default=os.path.join(ROOT, "_export", "next_cell"))
    args = parser.parse_args(argv)
    for path in ("benchmark", "tests"):  # a compile cache the last run left there stays
        shutil.rmtree(os.path.join(args.root, path), ignore_errors=True)
    add_next_cell(copy_tree(args.root), chips=args.chips)
    sys.path.insert(0, ROOT)
    from benchmark import run

    try:
        result = run.run_cell(CELL, args.seed, args.seconds, bool(args.trace), root=args.root)
    except run.RunFailed as exc:
        print(f"next_cell: {exc}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
