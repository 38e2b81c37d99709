"""A cell of commands over two keys and two shards end to end through
`run.run_cell`, small, on the CPU: the cell is the next PR's
(`next_cell.py`), added to a copy of the tree as new files.  Once with the
program's server, and once with one whose order across keys is broken
underneath (`broken_multi_server.py`)."""

import json
import os
import time

import numpy as np
import pytest

from benchmark import run
from tests.benchmark_tests import next_cell

SMALL = dict(chips=1, n=3, shards=2, keys_per_shard=8, buckets=64, batch=32, clients=24,
             generator_processes=2, warmup_s=0.5, readback_keys=16)


def small_cell(tmp_path, **more):
    root = next_cell.copy_tree(str(tmp_path))
    cell = next_cell.add_next_cell(root, **SMALL)
    result = run.run_cell(cell, 2**31 + 9, 3.0, False, root=root, platform="cpu",
                          started=time.monotonic(), **more)
    return result, os.path.join(root, "benchmark_out", cell, "trace0")


def test_a_cell_of_two_keys_a_command_over_two_shards_is_served_and_checked_across_keys(
        tmp_path, capsys):
    result, out = small_cell(tmp_path)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 500
    assert set(result["metrics"]) == {"goodput_cmds_s", "setup_s"}
    assert list(result)[-1] == "compared"
    assert result["compared"]["violations"] == {"value": 0, "limit": 0}
    history = dict(np.load(os.path.join(out, "history.npz")))
    # a record is a command, and every command wrote a further key
    window = history["phase"] == 1
    assert result["attempted"] == int(window.sum())
    writes = history["op"] == 0
    assert len(history["more_key"]) == int(writes.sum()) and np.all(history["more_answers"] == 1)
    assert 0.2 < np.mean(history["shards"][window] > 1) < 0.9  # some over one shard, some over two
    # the read-back sampled keys that were written as further keys only, too
    read = set(history["key"][history["phase"] == 2].tolist())
    assert read <= set(history["key"][writes].tolist()) | set(history["more_key"].tolist())
    printed = capsys.readouterr().out
    stats = json.loads(printed.split("# check: violations 0 (limit 0); ")[1].split(" ; server")[0])
    assert stats["multi_key_commands"] == int(writes.sum()) and stats["cross_key_edges"] > 0
    assert stats["acked_writes"] == 2 * stats["multi_key_commands"] and stats["check_seconds"] > 0
    assert '"multi_shard_share"' in printed  # each generator process reports its own
    assert not os.path.exists(os.path.join(out, "witness.json"))


def test_a_server_that_applies_a_commands_shards_at_two_points_comes_out_incorrect(tmp_path, capsys):
    result, out = small_cell(tmp_path, server_module="tests.benchmark_tests.broken_multi_server")
    assert result["correct"] is False
    assert result["failed"] == 0  # nothing failed, nothing is lost: only the order is wrong
    assert result["compared"]["violations"]["value"] >= 1
    with open(os.path.join(out, "witness.json")) as fh:
        witnesses = json.load(fh)
    assert {w["check"] for w in witnesses} <= {"cross_key_cycle", "replay", "partial_answer"}
    cycle = next(w for w in witnesses if w["check"] == "cross_key_cycle")
    assert len(cycle["ops"]) >= 2 and len(cycle["ops"]) == len(cycle["keys"])
    assert all(len(op["keys"]) == 2 for op in cycle["ops"])  # each names both its keys
    assert "# WITNESS" in capsys.readouterr().out


def test_a_cell_of_four_chips_fails_and_prints_no_result_where_there_are_fewer(tmp_path, monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
    root = next_cell.copy_tree(str(tmp_path))
    cell = next_cell.add_next_cell(root, **{**SMALL, "chips": 4})
    with pytest.raises(run.RunFailed, match="asks for 4 chips"):
        run.run_cell(cell, 1, 1.0, False, root=root, platform="cpu", started=time.monotonic())
