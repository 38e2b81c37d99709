"""The Caesar cell, `caesar_n7_1m.hot50_sat`, end to end through
`run.run_cell` from the tree's own files, small, on the CPU: n=7, 64 buckets,
batch and pending 32, 48 closed-loop clients, the cell's own mix (one hot key
at 50%, else the client's own).  Traced, so it reports every per-layer metric
of the cell; and once with the timed path broken underneath."""

import json
import os
import time

from benchmark import run
from tests.benchmark_tests.test_benchmark_e2e import names

CELL = "caesar_n7_1m.hot50_sat"
SMALL_CONFIG = {
    "server_flags": ["--protocol", "caesar", "-n", "7", "-f", "3", "--device-key-buckets", "64",
                     "--device-batch", "32", "--device-pending", "32"],
    "device_batch": 32,
}
SMALL_MIX = {"clients": 48, "generator_processes": 2, "warmup_s": 0.5, "drain_limit_s": 15.0,
             "readback_keys": 32}


def small(trace, **more):
    return run.run_cell(CELL, 2**31 + 34, 3.0, trace, platform="cpu", overrides=SMALL_MIX,
                        config_overrides=SMALL_CONFIG, started=time.monotonic(), **more)


def test_the_cell_runs_from_the_trees_files_and_its_round_is_ready_before_the_first_client():
    cell = run.load_cell(run.ROOT, CELL)
    assert cell["mix"]["key_gen"] == {"kind": "conflict_rate", "rate": 50}  # not overridden
    assert cell["config"]["deployment"]["n"] == 7 and cell["config"]["reduced"] == []
    result = small(True)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 300
    assert result["compared"]["violations"] == {"value": 0, "limit": 0}
    metrics = {name: metric["value"] for name, metric in result["metrics"].items()}
    assert set(metrics) == names("per_layer", CELL)
    assert {"clock_ticks_per_cmd.sat", "own_key_share.sat"} <= set(metrics)
    assert 40 < metrics["own_key_share.sat"] < 60
    assert 0.4 < metrics["clock_ticks_per_cmd.sat"] < 0.6  # the hot key takes one tick a command
    assert metrics["slow_path_share.sat"] == 0.0  # seven of seven live: every command is fast
    assert metrics["precompile_ms"] > 0 and metrics["compile_ms_in_window.sat"] == 0
    # nothing was compiled or loaded after the banner: the process's one program is the
    # precompile's, and all the time it reports for compiling lies inside that span
    with open(os.path.join(run.ROOT, "benchmark_out", CELL, "trace1", "snapshot.json")) as fh:
        final = json.load(fh)
    assert final["precompiled_programs"] == final["stage_precompile_n"] == 1
    assert final["jax_recompiles"] + final["jax_cache_hits"] == 1
    assert final["jax_compile_ms"] <= final["stage_precompile_ms"]
    assert final["fast_paths"] == final["executed"] > 0 and final["slow_paths"] == 0
    assert 0.4 < final["stable_watermark"] / final["executed"] < 0.6

    plain = small(False)
    assert plain["correct"] is True and set(plain["metrics"]) == {"goodput_cmds_s", "setup_s"}


def test_the_cell_comes_out_incorrect_over_a_server_that_drops_acknowledged_writes():
    result = small(False, server_module="tests.benchmark_tests.broken_server")
    assert result["correct"] is False
    assert result["failed"] == 0  # nothing failed: only the answers are wrong
    with open(os.path.join(run.ROOT, "benchmark_out", CELL, "trace0", "witness.json")) as fh:
        witness = json.load(fh)[0]
    assert witness["check"] in ("fork", "stale_read", "real_time") and len(witness["ops"]) >= 2
