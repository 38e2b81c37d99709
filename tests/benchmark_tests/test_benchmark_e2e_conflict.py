"""The cell `PERF.md` s7 keeps first in line, `epaxos_n5_1m.conflict50_sat`,
end to end through `run.run_cell`, small, on the CPU: added to a copy of the
tree by `next_cell.py` as one data file and two appended entries, the
conflict-rate key generator at 50% through the harness as it is."""

import json
import os
import time

import numpy as np

from benchmark import run
from tests.benchmark_tests import next_cell
from tests.benchmark_tests.test_benchmark_e2e import TINY_CONFIG, TINY_MIX, names


def test_the_cell_runs_from_a_copy_with_half_its_commands_on_the_hot_key(tmp_path):
    """Traced, so the cell reports every per-layer metric that moves
    `goodput_cmds_s` with no edit to any list: what `epaxos_n5_1m.zipf_sat`
    reports, the three `.sat` of PR 31's six among them."""
    root = next_cell.copy_tree(str(tmp_path))
    cell = next_cell.add_conflict_cell(root)
    small = {key: value for key, value in TINY_MIX.items() if key != "key_gen"}
    result = run.run_cell(cell, 2**31 + 31, 3.0, True, root=root, platform="cpu", overrides=small,
                          config_overrides=TINY_CONFIG, started=time.monotonic())
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 300
    assert result["compared"]["violations"] == {"value": 0, "limit": 0}
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    assert set(result["metrics"]) == names("per_layer", cell, spec)
    assert set(result["metrics"]) == names("per_layer", "epaxos_n5_1m.zipf_sat")
    assert result["metrics"]["frames_per_read.sat"]["value"] >= 1
    assert 0 <= result["metrics"]["gc_share.sat"]["value"] < 100
    assert result["metrics"]["gc_unscheduled.sat"]["value"] == 0
    history = np.load(os.path.join(root, "benchmark_out", cell, "trace1", "history.npz"))
    window = history["phase"] == 1
    hot = np.mean(history["key"][window] == 0)  # key 0 is the hot key, every other a client's own
    assert 0.40 < hot < 0.60, hot
    assert set(np.unique(history["key"][window])) - {0} <= set(range(1, TINY_MIX["clients"] + 1))
