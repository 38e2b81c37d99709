"""Tempo's cell with a coordinator at every site,
`tempo_n5_f2_1m_5site.conflict50_sat`, end to end through `run.run_cell` from
the tree's own files, small, on the CPU: n=5, f=2, 64 buckets, batch and
pending 32, 60 closed-loop clients at five sites (five generator processes),
the cell's own mix (one hot key at 50%, else the client's own; the traffic
file of `epaxos_n5_1m_5site.conflict50_sat`, byte for byte).  Traced, so it
reports every per-layer metric of the cell.  Held to counts of commands and
of what the server tallied, not to seconds of wall time."""

import json
import os
import time

from benchmark import run
from tests.benchmark_tests.test_benchmark_e2e import names

CELL = "tempo_n5_f2_1m_5site.conflict50_sat"
OURS = {"tempo_remote_site_share.sat", "clock_spread_per_cmd.sat", "clock_tie_share.sat",
        "arrival_reorder_share.sat", "tempo_sites_round_hbm_share.sat"}
SMALL_CONFIG = {
    "server_flags": ["--protocol", "newt", "-n", "5", "-f", "2", "--device-key-buckets", "64",
                     "--device-batch", "32", "--device-pending", "32"],
    "device_batch": 32,
}
SMALL_MIX = {"clients": 60, "warmup_s": 0.5, "drain_limit_s": 15.0, "readback_keys": 32}


def small(trace, **more):
    return run.run_cell(CELL, 2**31 + 53, 3.0, trace, platform="cpu", overrides=SMALL_MIX,
                        config_overrides=SMALL_CONFIG, started=time.monotonic(), **more)


def test_the_cell_runs_from_the_trees_files_with_a_coordinator_at_every_site():
    cell = run.load_cell(run.ROOT, CELL)
    sibling = run.load_cell(run.ROOT, "epaxos_n5_1m_5site.conflict50_sat")
    assert cell["mix"] == sibling["mix"]  # one traffic file, the graph family's
    assert cell["mix"]["key_gen"] == {"kind": "conflict_rate", "rate": 50}  # not overridden
    assert cell["mix"]["generator_processes"] == cell["mix"]["client_sites"] == 5
    assert cell["config"]["deployment"]["n"] == 5 and cell["config"]["reduced"] == []
    assert cell["config"]["deployment"]["f"] == 2 and cell["config"]["on_device"]
    # every shape flag is the one-coordinator deployment's, but for -f
    flags = cell["config"]["server_flags"]
    theirs = run.load_cell(run.ROOT, "tempo_n5_1m.zipf_sat")["config"]["server_flags"]
    assert [at for at, (a, b) in enumerate(zip(flags, theirs)) if a != b] == [flags.index("-f") + 1]
    assert len(flags) == len(theirs) and flags[flags.index("-f") + 1] == "2"

    result = small(True)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 100
    assert result["compared"]["violations"] == {"value": 0, "limit": 0}
    metrics = {name: metric["value"] for name, metric in result["metrics"].items()}
    # on the CPU the trace names no TPU: the roofline share reads nothing here
    assert set(metrics) == names("per_layer", CELL) - {"tempo_sites_round_hbm_share.sat"}
    assert OURS <= names("per_layer", CELL)
    assert 70 < metrics["tempo_remote_site_share.sat"] < 90  # four of five sites are not site 0
    assert 0 < metrics["slow_path_share.sat"] < 100  # both branches of the count-of-max test
    assert metrics["clock_spread_per_cmd.sat"] > 0 and metrics["clock_tie_share.sat"] >= 0
    assert metrics["arrival_reorder_share.sat"] >= 0
    assert metrics["compile_ms_in_window.sat"] == 0
    with open(os.path.join(run.ROOT, "benchmark_out", CELL, "trace1", "snapshot.json")) as fh:
        final = json.load(fh)
    assert final["sites_registered"] == 5
    assert final["site_clock_spread"] > 0 and final["slow_paths"] > 0 and final["fast_paths"] > 0
    assert final["fast_paths"] + final["slow_paths"] >= final["executed"] > 0
    # the tuner's ladder twice: with one coordinator at start-up, with five at the second
    # site's hello, before any command of it; nothing compiled after.  (The second ladder
    # compiles side by side: a program is lowered under one span and waited for under
    # another, and the compile times sum to more than the spans.)
    ladder = final["precompiled_programs"] // 2
    assert final["precompiled_programs"] == 2 * ladder >= 2
    assert final["stage_precompile_n"] == 3 * ladder and final["stage_precompile_ms"] > 0
    assert final["jax_recompiles"] + final["jax_cache_hits"] == final["precompiled_programs"]

    plain = small(False)
    assert plain["correct"] is True and plain["failed"] == 0
    assert set(plain["metrics"]) == {"goodput_cmds_s", "setup_s"}
