"""The FPaxos cell, `fpaxos_n5_1m.zipf_sat`, end to end through `run.run_cell`
from the tree's own files, small, on the CPU: n=5, f=1 (accept quorum 2), 64
buckets (which the leader round does not use), batch and pending 32, 48
closed-loop clients, the cell's own mix (zipf 1.0, the mix of the EPaxos and
Tempo saturated cells).  Traced, so it reports every per-layer metric of the
cell; and once with the timed path broken underneath."""

import json
import os
import time

from benchmark import run
from tests.benchmark_tests.test_benchmark_e2e import names

CELL = "fpaxos_n5_1m.zipf_sat"
SMALL_CONFIG = {
    "server_flags": ["--protocol", "fpaxos", "-n", "5", "-f", "1", "--device-key-buckets", "64",
                     "--device-batch", "32", "--device-pending", "32"],
    "device_batch": 32,
}
SMALL_MIX = {"clients": 48, "generator_processes": 2, "warmup_s": 0.5, "drain_limit_s": 15.0,
             "readback_keys": 32}


def small(trace, **more):
    return run.run_cell(CELL, 2**31 + 38, 3.0, trace, platform="cpu", overrides=SMALL_MIX,
                        config_overrides=SMALL_CONFIG, started=time.monotonic(), **more)


def test_the_cell_runs_from_the_trees_files_and_its_round_is_ready_before_the_first_client():
    cell = run.load_cell(run.ROOT, CELL)
    assert cell["mix"] == run.load_cell(run.ROOT, "epaxos_n5_1m.zipf_sat")["mix"]  # the control's mix
    assert cell["config"]["deployment"]["f"] == 1 and cell["config"]["reduced"] == []
    result = small(True)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 300
    assert result["compared"]["violations"] == {"value": 0, "limit": 0}
    metrics = {name: metric["value"] for name, metric in result["metrics"].items()}
    assert set(metrics) == names("per_layer", CELL)
    assert metrics["slots_per_cmd.sat"] == 1.0  # a dense log: a slot a command, each answered
    assert metrics["slow_path_share.sat"] == 100.0  # the leader's one path, as the tally names it
    assert metrics["drain_rows_per_cmd.sat"] == 1.0
    assert metrics["precompile_ms"] > 0 and metrics["compile_ms_in_window.sat"] == 0
    # nothing was compiled or loaded after the banner: the process's one program is the
    # precompile's, and all the time it reports for compiling lies inside that span
    with open(os.path.join(run.ROOT, "benchmark_out", CELL, "trace1", "snapshot.json")) as fh:
        final = json.load(fh)
    assert final["precompiled_programs"] == final["stage_precompile_n"] == 1
    assert final["jax_recompiles"] + final["jax_cache_hits"] == 1
    assert final["jax_compile_ms"] <= final["stage_precompile_ms"]
    assert final["slow_paths"] == final["executed"] == final["stable_watermark"] > 0
    assert final["fast_paths"] == 0 and final["requeued"] == 0 and final["device_slot_epochs"] == 0
    assert final["backend"]["round"] == "paxos_slot" and final["backend"]["accept_quorum"] == 2

    plain = small(False)
    assert plain["correct"] is True and set(plain["metrics"]) == {"goodput_cmds_s", "setup_s"}


def test_the_cell_comes_out_incorrect_over_a_server_that_drops_acknowledged_writes():
    result = small(False, server_module="tests.benchmark_tests.broken_server")
    assert result["correct"] is False
    assert result["failed"] == 0  # nothing failed: only the answers are wrong
    with open(os.path.join(run.ROOT, "benchmark_out", CELL, "trace0", "witness.json")) as fh:
        witness = json.load(fh)[0]
    assert witness["check"] in ("fork", "stale_read", "real_time") and len(witness["ops"]) >= 2
