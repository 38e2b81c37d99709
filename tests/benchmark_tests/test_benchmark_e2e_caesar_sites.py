"""Caesar's cell with a coordinator at every site,
`caesar_n7_1m_7site.conflict50_7site_sat`, end to end through `run.run_cell`
from the tree's own files, small, on the CPU: n=7, 64 buckets, batch and
pending 32, 70 closed-loop clients at seven sites (seven generator processes),
the cell's own mix (one hot key at 50%, else the client's own).  Traced, so it
reports every per-layer metric of the cell.  Held to counts of commands and of
what the server tallied, not to seconds of wall time."""

import json
import os
import time

import pytest

from benchmark import round_bytes_caesar, run
from tests.benchmark_tests.test_benchmark_e2e import names

CELL = "caesar_n7_1m_7site.conflict50_7site_sat"
OURS = {"caesar_wait_share.sat", "caesar_reject_acks_per_cmd.sat", "caesar_retry_lift_per_cmd.sat",
        "caesar_remote_site_share.sat", "caesar_sites_round_hbm_share.sat",
        "caesar_sites_clock_ticks_per_cmd.sat"}
SMALL_CONFIG = {
    "server_flags": ["--protocol", "caesar", "-n", "7", "-f", "3", "--device-key-buckets", "64",
                     "--device-batch", "32", "--device-pending", "32"],
    "device_batch": 32,
}
SMALL_MIX = {"clients": 70, "warmup_s": 0.5, "drain_limit_s": 15.0, "readback_keys": 32}


def small(trace, **more):
    return run.run_cell(CELL, 2**31 + 59, 3.0, trace, platform="cpu", overrides=SMALL_MIX,
                        config_overrides=SMALL_CONFIG, started=time.monotonic(), **more)


def test_the_cell_is_the_one_coordinator_deployment_with_clients_at_seven_sites():
    cell = run.load_cell(run.ROOT, CELL)
    sibling = run.load_cell(run.ROOT, "caesar_n7_1m.hot50_sat")
    five = run.load_cell(run.ROOT, "tempo_n5_f2_1m_5site.conflict50_sat")["mix"]
    assert cell["config"]["server_flags"] == sibling["config"]["server_flags"]  # no flag engages it
    assert cell["config"]["deployment"] == dict(
        sibling["config"]["deployment"], layout=cell["config"]["deployment"]["layout"])
    assert cell["config"]["guarantees"] == sibling["config"]["guarantees"]
    assert cell["config"]["reduced"] == [] and cell["config"]["on_device"]
    assert cell["chips"] == 1
    # the five-site mix with seven sites and seven generators, and nothing else changed
    changed = {name for name in five if five[name] != cell["mix"][name]}
    assert changed == {"generator_processes", "client_sites", "note", "assumed"}
    assert cell["mix"]["generator_processes"] == cell["mix"]["client_sites"] == 7
    assert cell["mix"]["key_gen"] == {"kind": "conflict_rate", "rate": 50}
    assert OURS <= names("per_layer", CELL) and "slow_path_share.sat" in names("per_layer", CELL)


@pytest.mark.parametrize("replica_axis, rows", [(1, 7), (7, 1)])
def test_the_rounds_bytes_are_the_deployments_shape(replica_axis, rows):
    config = run.load_cell(run.ROOT, CELL)["config"]
    want = 4096 * 3 * 4 + 2 * 4096 * 4 * 4 + 2 * rows * 8192 * 4 + 8192 * 19
    assert round_bytes_caesar.round_min_bytes(config, replica_axis) == want
    other = run.load_cell(run.ROOT, "tempo_n5_f2_1m_5site.conflict50_sat")["config"]
    assert round_bytes_caesar.round_min_bytes(other, replica_axis) is None


def test_the_clocks_guard_is_read_in_this_cell_as_in_the_one_coordinator_cell():
    """``CLOCK_GUARD`` is used up at another rate where retries lift clocks and
    seven coordinators number side by side: the one-coordinator cell's metric,
    reader and arguments, under a name of this cell's."""
    base = os.path.join(run.ROOT, "benchmark", "layer_metrics")
    ours = run._load(os.path.join(base, "caesar_sites_clock_ticks_per_cmd.sat.json"))
    theirs = run._load(os.path.join(base, "clock_ticks_per_cmd.sat.json"))
    same = ("unit", "better", "source", "layer", "moves", "reader", "args")
    assert {key: ours[key] for key in same} == {key: theirs[key] for key in same}
    assert ours["workloads"] == [CELL] and CELL not in theirs["workloads"]


def test_the_cell_runs_from_the_trees_files_with_a_coordinator_at_every_site():
    result = small(True)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 100
    assert result["compared"]["violations"] == {"value": 0, "limit": 0}
    metrics = {name: metric["value"] for name, metric in result["metrics"].items()}
    # on the CPU the trace names no TPU: the roofline share reads nothing here
    assert set(metrics) == names("per_layer", CELL) - {"caesar_sites_round_hbm_share.sat"}
    assert 80 < metrics["caesar_remote_site_share.sat"] < 90  # six of seven sites are not site 0
    assert 0 < metrics["slow_path_share.sat"] < metrics["caesar_wait_share.sat"] <= 100
    assert metrics["caesar_reject_acks_per_cmd.sat"] > 0
    assert metrics["caesar_retry_lift_per_cmd.sat"] > 0
    assert metrics["caesar_sites_clock_ticks_per_cmd.sat"] > 0
    assert metrics["compile_ms_in_window.sat"] == 0
    with open(os.path.join(run.ROOT, "benchmark_out", CELL, "trace1", "snapshot.json")) as fh:
        final = json.load(fh)
    assert final["sites_registered"] == 7
    assert final["slow_paths"] > 0 and final["fast_paths"] > 0 and final["wait_passes"] >= 2
    assert final["reject_acks"] == 5 * final["slow_paths"]  # all live: the ring but the coordinator
    assert final["fast_paths"] + final["slow_paths"] >= final["executed"] > 0
    # the round with one coordinator at start-up, the round with seven at the second site's
    # hello, before any command of it; nothing compiled after
    assert final["precompiled_programs"] == 2 and final["stage_precompile_n"] == 2
    assert final["jax_recompiles"] + final["jax_cache_hits"] == final["precompiled_programs"]

    plain = small(False)
    assert plain["correct"] is True and plain["failed"] == 0
    assert set(plain["metrics"]) == {"goodput_cmds_s", "setup_s"}


def test_the_roofline_share_reads_nothing_with_one_coordinator_and_a_share_with_seven():
    from benchmark.readers import caesar_round_hbm_share as reader

    ctx = {
        "config": run.load_cell(run.ROOT, CELL)["config"],
        "trace": {"busy_per_round_s": 1e-3},
        "snapshot_end": {"sites_registered": 7, "backend": {
            "platform": "tpu", "device_kind": "TPU v5 lite", "mesh_shape": {"replica": 1}}},
        "snapshot_delta": {"rounds": 100, "device_dispatches": 100},
    }
    share = reader.read(ctx)
    assert 0 < share < 1  # under a megabyte a round, a millisecond at 819 GB/s
    for broken in (
        dict(ctx, trace={}),
        dict(ctx, snapshot_end=dict(ctx["snapshot_end"], sites_registered=1)),
        dict(ctx, snapshot_end={"backend": ctx["snapshot_end"]["backend"]}),  # the parent's
        dict(ctx, snapshot_end=dict(ctx["snapshot_end"], backend={"platform": "cpu"})),
        dict(ctx, snapshot_delta={"rounds": 0, "device_dispatches": 0}),
    ):
        assert reader.read(broken) is None
