"""Atlas's cell at f = 2 with a coordinator at every site,
`atlas_n5_f2_1m_5site.conflict50_sat`, end to end through `run.run_cell` from
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

CELL = "atlas_n5_f2_1m_5site.conflict50_sat"
OURS = {"atlas_f2_remote_site_share.sat", "threshold_short_deps_per_cmd.sat",
        "threshold_fast_of_split_share.sat", "atlas_f2_scc_rows_share.sat",
        "atlas_f2_sites_round_hbm_share.sat"}
SMALL_CONFIG = {
    "server_flags": ["--protocol", "atlas", "-n", "5", "-f", "2", "--device-key-buckets", "64",
                     "--device-batch", "32", "--device-pending", "32"],
    "device_batch": 32,
}
SMALL_MIX = {"clients": 60, "warmup_s": 0.5, "drain_limit_s": 15.0, "readback_keys": 32}


def small(trace, **more):
    return run.run_cell(CELL, 2**31 + 55, 3.0, trace, platform="cpu", overrides=SMALL_MIX,
                        config_overrides=SMALL_CONFIG, started=time.monotonic(), **more)


def test_the_cell_runs_from_the_trees_files_with_the_threshold_at_every_site():
    cell = run.load_cell(run.ROOT, CELL)
    sibling = run.load_cell(run.ROOT, "epaxos_n5_1m_5site.conflict50_sat")
    assert cell["mix"] == sibling["mix"]  # one traffic file, EPaxos's and Tempo's
    assert cell["mix"]["key_gen"] == {"kind": "conflict_rate", "rate": 50}  # not overridden
    assert cell["mix"]["generator_processes"] == cell["mix"]["client_sites"] == 5
    config, theirs = cell["config"], sibling["config"]
    assert config["deployment"]["n"] == 5 and config["reduced"] == [] and config["on_device"]
    assert (config["deployment"]["protocol"], config["deployment"]["f"]) == ("atlas", 2)
    # after the EPaxos five-site configuration key for key; the shape flags differ
    # in the protocol and in f alone
    assert list(config) == list(theirs) and list(config["assumed"]) == list(theirs["assumed"])
    flags, other = config["server_flags"], theirs["server_flags"]
    assert [at for at, (a, b) in enumerate(zip(flags, other)) if a != b] == [1, 5]
    assert len(flags) == len(other) and flags[:6] == ["--protocol", "atlas", "-n", "5", "-f", "2"]
    assert config["guarantees"] == theirs["guarantees"]

    result = small(True)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 100
    assert result["compared"]["violations"] == {"value": 0, "limit": 0}
    metrics = {name: metric["value"] for name, metric in result["metrics"].items()}
    # on the CPU the trace names no TPU: the roofline share reads nothing here
    assert set(metrics) == names("per_layer", CELL) - {"atlas_f2_sites_round_hbm_share.sat"}
    assert OURS <= names("per_layer", CELL)
    assert 70 < metrics["atlas_f2_remote_site_share.sat"] < 90  # four of five sites are not site 0
    assert 0 < metrics["slow_path_share.sat"] < 100  # both sides of the threshold
    assert metrics["threshold_short_deps_per_cmd.sat"] > 0
    assert 0 < metrics["threshold_fast_of_split_share.sat"] < 100
    assert metrics["atlas_f2_scc_rows_share.sat"] > 0
    assert metrics["compile_ms_in_window.sat"] == 0
    with open(os.path.join(run.ROOT, "benchmark_out", CELL, "trace1", "snapshot.json")) as fh:
        final = json.load(fh)
    assert final["sites_registered"] == 5 and final["backend"]["resolver"] == "key_runs"
    assert final["slow_paths"] > 0 and final["fast_paths"] > 0
    assert final["threshold_short_deps"] >= final["slow_paths"]
    assert (final["split_quorum_rows"] - final["threshold_fast_split_rows"]
            == final["slow_paths"])  # every replica live: what missed the threshold was accepted
    assert final["threshold_fast_split_rows"] > 0
    # two programs: the round with one coordinator at start-up, the round with five at the
    # second site's hello, before any command of it; nothing compiled after
    assert final["precompiled_programs"] == final["stage_precompile_n"] == 2
    assert final["jax_recompiles"] + final["jax_cache_hits"] == 2

    plain = small(False)
    assert plain["correct"] is True and plain["failed"] == 0
    assert set(plain["metrics"]) == {"goodput_cmds_s", "setup_s"}
