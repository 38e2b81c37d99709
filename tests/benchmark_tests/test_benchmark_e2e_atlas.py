"""The Janus* cell, `atlas_n5_4shard_2key.ycsbt_w5_zipf07_sat`, end to end
through `run.run_cell` from the tree's own files, small, on the CPU: n=5, f=1
(Atlas's fast quorum 3, write quorum 2), 4 shards on one device, two keys a
command over 4 x 16 keys, 256 buckets, batch and pending 32, 48 closed-loop
clients, the cell's own mix (95% `Get`s of both keys, 5% `Put`s of both).
Traced, so it reports every per-layer metric of the cell, the three it
brought among them."""

import json
import os
import time

from benchmark import run
from tests.benchmark_tests.test_benchmark_e2e import names

CELL = "atlas_n5_4shard_2key.ycsbt_w5_zipf07_sat"
SMALL_CONFIG = {
    "server_flags": ["--protocol", "atlas", "-n", "5", "-f", "1", "--shard-count", "4",
                     "--device-key-width", "2", "--device-key-buckets", "256",
                     "--device-batch", "32", "--device-pending", "32"],
    "device_batch": 32,
}
SMALL_MIX = {"clients": 48, "generator_processes": 2, "warmup_s": 0.5, "drain_limit_s": 15.0,
             "readback_keys": 32,
             "key_gen": {"kind": "zipf", "coefficient": 0.7, "keys_per_shard": 16}}
OWN = {"deps_per_cmd.sat", "read_commute_share.sat", "cross_shard_cmd_share.sat"}


def small(trace, **more):
    return run.run_cell(CELL, 2**31 + 40, 3.0, trace, platform="cpu", overrides=SMALL_MIX,
                        config_overrides=SMALL_CONFIG, started=time.monotonic(), **more)


def test_the_cell_runs_from_the_trees_files_under_atlas_rule_and_reads_commute():
    cell = run.load_cell(run.ROOT, CELL)
    tempo = run.load_cell(run.ROOT, "tempo_n5_4shard_2key.ycsbt_zipf07_sat")
    # the four-shard Tempo cell's mix but for the share of reads, on one chip
    differs = {key for key in cell["mix"] if cell["mix"][key] != tempo["mix"][key]}
    assert differs == {"read_share", "note", "assumed"} and cell["mix"]["read_share"] == 0.95
    assert cell["chips"] == 1 and cell["config"]["deployment"]["protocol"] == "atlas"
    assert cell["config"]["reduced"] == []
    assert OWN <= {m["name"] for m in cell["per_layer"]}
    assert {m["reader"] for m in cell["per_layer"] if m["name"] in OWN} == {"snapshot_ratio"}

    result = small(True)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 300
    assert result["compared"]["violations"] == {"value": 0, "limit": 0}
    metrics = {name: metric["value"] for name, metric in result["metrics"].items()}
    assert set(metrics) == names("per_layer", CELL)
    assert all(metrics[name] is not None for name in OWN)
    assert metrics["slow_path_share.sat"] == 0.0  # Atlas at f = 1: whoever reports is one of f
    assert metrics["read_commute_share.sat"] > 50.0  # 95% reads: most neighbours on a key both are
    # two keys a command: a read keeps one dependency a key at most, a write two
    assert 0.0 < metrics["deps_per_cmd.sat"] <= 2 * (0.95 + 2 * 0.05) + 0.2
    assert 50.0 < metrics["cross_shard_cmd_share.sat"] < 95.0  # two keys over four shards: 75%
    assert metrics["drain_rows_per_cmd.sat"] == 1.0
    assert metrics["precompile_ms"] > 0 and metrics["compile_ms_in_window.sat"] == 0
    with open(os.path.join(run.ROOT, "benchmark_out", CELL, "trace1", "snapshot.json")) as fh:
        final = json.load(fh)
    assert final["backend"]["rule"] == "atlas" and final["backend"]["quorums"] == [3, 2]
    assert final["backend"]["resolver"] == "general"
    assert sorted({s for held in final["backend"]["shards_on_device"] for s in held}) == [0, 1, 2, 3]
    assert final["precompiled_programs"] == final["stage_precompile_n"] == 1
    assert final["fast_paths"] == final["executed"] > 0 and final["slow_paths"] == 0
    # the reads the round counted are the Gets the session plane replied to
    assert 0 < final["read_rows"] == final["gets_replied"] < final["executed"]
    assert final["read_links_commuted"] <= final["key_links"] <= 2 * final["executed"]
    # the round spans keep each round's reads: together, all of them
    with open(os.path.join(run.ROOT, "benchmark_out", CELL, "trace1", "round_spans.json")) as fh:
        ring = json.load(fh)
    name, reads = ring["columns"].index("name"), ring["columns"].index("read_rows")
    assert sum(row[reads] for row in ring["spans"] if row[name] == "round") == final["read_rows"]
    assert all(row[reads] is None for row in ring["spans"] if row[name] != "round")

    plain = small(False)
    assert plain["correct"] is True and set(plain["metrics"]) == {"goodput_cmds_s", "setup_s"}


def test_the_cell_comes_out_incorrect_over_a_server_that_reads_from_a_lagging_copy():
    # the guarantee the configuration adds is about reads, and 19 commands of 20 are reads:
    # every 40th `Get` comes back from before its key's last write (`stale_read_server.py`)
    result = small(False, server_module="tests.benchmark_tests.stale_read_server")
    assert result["correct"] is False
    assert result["failed"] == 0  # nothing failed and no write is lost: only reads are old
    assert result["compared"]["violations"]["value"] >= 1
    with open(os.path.join(run.ROOT, "benchmark_out", CELL, "trace0", "witness.json")) as fh:
        witnesses = json.load(fh)
    assert "stale_read" in {w["check"] for w in witnesses}
