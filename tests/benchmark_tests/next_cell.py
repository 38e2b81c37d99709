"""What a later PR adds for a cell `PERF.md` s7 keeps in line, made in a copy
of the tree as new files and appended list entries only.  Two cells are
described here, each under its row's own names:

``tempo_n5_4shard.zipf07_sat`` (PR 26's; the tree's own four-chip cell,
``tempo_n5_4shard_2key.ycsbt_zipf07_sat``, is what it became): a
configuration with ``--shard-count`` and ``--device-key-width``, a
``kv_multi`` mix, a four-chip cell, the cell's name on the end-to-end metric
it reports, and a per-layer metric that lists only that cell.

``epaxos_n5_1m.conflict50_sat`` (s7's #1): a ``kv_loop`` mix of the
conflict-rate key generator at 50% and a one-chip cell of the configuration
the tree has, its name on ``goodput_cmds_s``; no configuration and no metric
of its own.

The contract tests hold their rules to such copies, the end-to-end tests run
them small on the CPU, and on the chip each is run at the deployment's size:

    python3 tests/benchmark_tests/next_cell.py --chips 1 --seed 7 --seconds 20 --trace 0
    python3 tests/benchmark_tests/next_cell.py --cell epaxos_n5_1m.conflict50_sat --chips 1 --seed 7 --seconds 20 --trace 0

copies the tree to ``_export/next_cell`` (a directory ``.gitignore`` lists),
adds the cell there and runs it from there.  ``BENCHMARK.json`` of the tree
itself never names either cell."""

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
CONFLICT_CONFIG, CONFLICT_TRAFFIC = "epaxos_n5_1m", "conflict50_sat"
CONFLICT_CELL = f"{CONFLICT_CONFIG}.{CONFLICT_TRAFFIC}"


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


def _write(root: str, folder: str, name: str, content: dict) -> None:
    path = os.path.join(root, "benchmark", folder, name + ".json")
    assert not os.path.exists(path), path  # new files only
    with open(path, "w") as fh:
        json.dump(content, fh, indent=1)


def _append(root: str, cell: dict, *, config: dict | None = None, metric: dict | None = None) -> None:
    """The cell at the end of ``workloads`` and of ``goodput_cmds_s``'s list,
    a configuration and a per-layer metric at the ends of theirs."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        spec = json.load(fh)
    if config is not None:
        spec["configs"].append(config)
    spec["workloads"].append(cell)
    for entry in spec["end_to_end"]:
        if entry["name"] == "goodput_cmds_s":
            entry["workloads"].append(cell["name"])
    if metric is not None:
        spec["per_layer"].append(metric)
    with open(path, "w") as fh:
        json.dump(spec, fh, indent=1)


def add_next_cell(root: str, *, chips: int = 4, n: int = 5, shards: int = 4,
                  keys_per_shard: int = 1_000_000, buckets: int = 4_194_304, batch: int = 4096,
                  clients: int = 8192,
                  generator_processes: int = 4, warmup_s: float = 8.0,
                  readback_keys: int = 512) -> str:
    """Write the cell's files under ``root`` and append its entries to
    ``root``'s ``BENCHMARK.json``; returns the cell's name."""
    _write(root, "configs", CONFIG, {
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
    _write(root, "traffic", TRAFFIC, {
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
    _write(root, "layer_metrics", METRIC, {
        **metric, "reader": "record_share", "args": {"column": "shards", "above": 1, "scale": 100.0},
        "reads": "generator records: commands of the window that touched more than one shard, in %"})
    _append(root, {
        "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": chips,
        "why": f"closed loop, {clients} clients, 2 keys a command, zipf 0.7 over {shards} shards: "
               "cross-shard commands, collectives, the reply stage's per-shard aggregation"},
        config={"name": CONFIG, "source": SOURCE, "file": f"benchmark/configs/{CONFIG}.json",
                "reduced": [],
                "why": "table family under partial replication: per-shard quorums, "
                       "multi-shard commands at the max of their shards' clocks"},
        metric=metric)
    return CELL


def add_conflict_cell(root: str) -> str:
    """`PERF.md` s7's #1: one new file, the mix, and the cell's two entries.
    Everything but ``key_gen`` is the saturated zipf mix's; the configuration
    is the tree's own, as it is."""
    _write(root, "traffic", CONFLICT_TRAFFIC, {
        "generator": "kv_loop", "loop": "closed", "clients": 8192, "generator_processes": 4,
        "key_gen": {"kind": "conflict_rate", "rate": 50},
        "keys_per_command": 1, "read_share": 0.0, "warmup_s": 8.0, "drain_limit_s": 30.0,
        "readback_keys": 512,
        "assumed": {"key_gen.rate": "50: BASELINE.json's founding mix; the Tempo and Atlas "
                                    "papers' full-replication microbenchmark swept it, from memory",
                    "clients": "as the saturated zipf mix: twice the device batch",
                    "read_share": "0 (100% writes): see the configuration's assumed.write_share"},
    })
    _append(root, {
        "name": CONFLICT_CELL, "config": CONFLICT_CONFIG, "traffic": CONFLICT_TRAFFIC, "chips": 1,
        "why": "closed loop, 8192 clients, one hot key with probability 50% else the client's "
               "own (KeyGen::ConflictRate): half of every round is one chain on one key"})
    return CONFLICT_CELL


CELLS = {CELL: add_next_cell, CONFLICT_CELL: add_conflict_cell}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--cell", choices=sorted(CELLS), default=CELL)
    parser.add_argument("--chips", type=int, choices=(1, 4), required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--root", default=os.path.join(ROOT, "_export", "next_cell"))
    args = parser.parse_args(argv)
    for path in ("benchmark", "tests"):  # a compile cache the last run left there stays
        shutil.rmtree(os.path.join(args.root, path), ignore_errors=True)
    if args.cell == CONFLICT_CELL and args.chips != 1:
        parser.error(f"{CONFLICT_CELL} is a cell of one chip")
    root = copy_tree(args.root)
    if args.cell == CELL:
        add_next_cell(root, chips=args.chips)
    else:
        add_conflict_cell(root)
    sys.path.insert(0, ROOT)
    from benchmark import run

    try:
        result = run.run_cell(args.cell, args.seed, args.seconds, bool(args.trace), root=args.root)
    except run.RunFailed as exc:
        print(f"next_cell: {exc}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
