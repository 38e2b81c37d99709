"""The cell with a coordinator at every site,
`epaxos_n5_1m_5site.conflict50_sat`, end to end through `run.run_cell` from
the tree's own files, small, on the CPU: n=5, 64 buckets, batch and pending
32, 60 closed-loop clients at five sites (five generator processes), the
cell's own mix (one hot key at 50%, else the client's own).  Traced, so it
reports every per-layer metric of the cell.  Held to counts of commands and
of what the server tallied, not to seconds of wall time."""

import json
import os
import time

from benchmark import run
from tests.benchmark_tests.test_benchmark_e2e import names

CELL = "epaxos_n5_1m_5site.conflict50_sat"
OURS = {"scc_rows_share.sat", "scc_rows_max.sat", "resolve_iters_per_round.sat",
        "finisher_rows_share.sat", "remote_site_share.sat"}
SMALL_CONFIG = {
    "server_flags": ["--protocol", "epaxos", "-n", "5", "-f", "1", "--device-key-buckets", "64",
                     "--device-batch", "32", "--device-pending", "32"],
    "device_batch": 32,
}
SMALL_MIX = {"clients": 60, "warmup_s": 0.5, "drain_limit_s": 15.0, "readback_keys": 32}


def small(trace, **more):
    return run.run_cell(CELL, 2**31 + 46, 3.0, trace, platform="cpu", overrides=SMALL_MIX,
                        config_overrides=SMALL_CONFIG, started=time.monotonic(), **more)


def test_the_cell_runs_from_the_trees_files_with_a_coordinator_at_every_site():
    cell = run.load_cell(run.ROOT, CELL)
    assert cell["mix"]["key_gen"] == {"kind": "conflict_rate", "rate": 50}  # not overridden
    assert cell["mix"]["generator_processes"] == cell["mix"]["client_sites"] == 5
    assert cell["config"]["deployment"]["n"] == 5 and cell["config"]["reduced"] == []
    # every shape flag is the one-coordinator deployment's
    assert cell["config"]["server_flags"] == run.load_cell(
        run.ROOT, "epaxos_n5_1m.zipf_sat")["config"]["server_flags"]
    result = small(True)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 100
    assert result["compared"]["violations"] == {"value": 0, "limit": 0}
    metrics = {name: metric["value"] for name, metric in result["metrics"].items()}
    assert set(metrics) == names("per_layer", CELL) and OURS <= set(metrics)
    assert 70 < metrics["remote_site_share.sat"] < 90  # four of five sites are not site 0
    assert metrics["scc_rows_share.sat"] > 0 and metrics["scc_rows_max.sat"] >= 0
    assert metrics["slow_path_share.sat"] > 0  # for the first time in a cell
    assert metrics["resolve_iters_per_round.sat"] == 1.0  # every replica live: one pass
    assert metrics["finisher_rows_share.sat"] == 0.0  # writes alone: the device cuts every run
    assert metrics["compile_ms_in_window.sat"] == 0
    with open(os.path.join(run.ROOT, "benchmark_out", CELL, "trace1", "snapshot.json")) as fh:
        final = json.load(fh)
    assert final["sites_registered"] == 5 and final["backend"]["resolver"] == "key_runs"
    assert final["scc_rows"] > 0 and final["slow_paths"] > 0 and final["finisher_rows"] == 0
    assert final["scc_count"] > 0 and final["resolve_iters"] > 0
    assert final["fast_paths"] + final["slow_paths"] >= final["executed"] > 0
    # two programs: the round with one coordinator at start-up, the round with five made
    # ready at the second site's hello, before any command of it; nothing compiled after
    assert final["precompiled_programs"] == final["stage_precompile_n"] == 2
    assert final["jax_recompiles"] + final["jax_cache_hits"] == 2
    assert final["jax_compile_ms"] <= final["stage_precompile_ms"]

    plain = small(False)
    assert plain["correct"] is True and plain["failed"] == 0
    assert set(plain["metrics"]) == {"goodput_cmds_s", "setup_s"}
