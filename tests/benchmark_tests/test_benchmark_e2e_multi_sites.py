"""Janus* whole, `atlas_n5_4shard_2key_5site.ycsbt_w50_zipf07_5site_sat`, end
to end through `run.run_cell` from the tree's own files, small, on the CPU:
n=5, f=1 (Atlas's fast quorum 3, write quorum 2), 4 shards, two keys a command
over 4 x 16 keys, 256 buckets, batch and pending 32, 60 closed-loop clients at
five sites (five generator processes), the cell's own mix (half `Get`s of both
keys, half `Put`s of both).  Traced, so it reports every per-layer metric of
the cell, the five counters' it brought among them (its share of the roofline
needs a TPU).  Held to counts of commands and of what the server tallied, not
to seconds of wall time."""

import json
import os
import time

import numpy as np

from benchmark import run
from tests.benchmark_tests.test_benchmark_e2e import names

CELL = "atlas_n5_4shard_2key_5site.ycsbt_w50_zipf07_5site_sat"
ONE_SITE = "atlas_n5_4shard_2key.ycsbt_w5_zipf07_sat"
OURS = {"scc_span_rows_share.sat", "scc_shard_rows_share.sat", "multikey_scc_rows_share.sat",
        "multikey_resolve_iters_per_round.sat", "multikey_finisher_rows_share.sat"}
SMALL_CONFIG = {
    "server_flags": ["--protocol", "atlas", "-n", "5", "-f", "1", "--shard-count", "4",
                     "--device-key-width", "2", "--device-key-buckets", "256",
                     "--device-batch", "32", "--device-pending", "32"],
    "device_batch": 32,
}
SMALL_MIX = {"clients": 60, "warmup_s": 0.5, "drain_limit_s": 15.0, "readback_keys": 32,
             "key_gen": {"kind": "zipf", "coefficient": 0.7, "keys_per_shard": 16}}


def small(trace, **more):
    return run.run_cell(CELL, 2**31 + 49, 3.0, trace, platform="cpu", overrides=SMALL_MIX,
                        config_overrides=SMALL_CONFIG, started=time.monotonic(), **more)


def test_the_cell_runs_from_the_trees_files_with_components_across_keys_and_shards():
    cell, one_site = run.load_cell(run.ROOT, CELL), run.load_cell(run.ROOT, ONE_SITE)
    # the one-coordinator Janus* cell's deployment and flags, letter for letter; its mix
    # but for where the clients are and how many commands write
    assert cell["config"]["server_flags"] == one_site["config"]["server_flags"]
    assert cell["config"]["reduced"] == [] and cell["chips"] == 1
    assert {key: value for key, value in cell["config"]["deployment"].items() if key != "layout"} == {
        key: value for key, value in one_site["config"]["deployment"].items() if key != "layout"}
    differs = {key for key in cell["mix"] if cell["mix"][key] != one_site["mix"].get(key)}
    assert differs == {"generator", "generator_processes", "client_sites", "read_share", "note",
                       "assumed"}
    assert cell["mix"]["read_share"] == 0.5 and cell["mix"]["client_sites"] == 5
    assert OURS | {"sites_round_hbm_share.sat"} <= {m["name"] for m in cell["per_layer"]}

    result = small(True)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 100
    assert result["compared"]["violations"] == {"value": 0, "limit": 0}
    metrics = {name: metric["value"] for name, metric in result["metrics"].items()}
    # all but the share of the roofline, which a CPU run cannot read
    assert set(metrics) == names("per_layer", CELL) - {"sites_round_hbm_share.sat"}
    assert OURS <= set(metrics)
    assert metrics["slow_path_share.sat"] == 0.0  # Atlas at f = 1: the fast path is unconditional
    assert metrics["multikey_scc_rows_share.sat"] > 0  # ... and the graph has cycles all the same
    assert 0 < metrics["scc_span_rows_share.sat"] <= metrics["multikey_scc_rows_share.sat"]
    assert 0 < metrics["scc_shard_rows_share.sat"] <= metrics["multikey_scc_rows_share.sat"]
    assert metrics["multikey_finisher_rows_share.sat"] == 0.0  # 64 working rows fit the residual
    assert metrics["multikey_resolve_iters_per_round.sat"] > 0
    assert metrics["compile_ms_in_window.sat"] == 0
    with open(os.path.join(run.ROOT, "benchmark_out", CELL, "trace1", "snapshot.json")) as fh:
        final = json.load(fh)
    assert final["sites_registered"] == 5
    assert final["backend"]["rule"] == "atlas" and final["backend"]["quorums"] == [3, 2]
    assert final["backend"]["resolver"] == "general_components"
    assert final["fast_paths"] == final["executed"] > 0 and final["slow_paths"] == 0
    assert final["scc_rows"] >= final["scc_span_rows"] > 0 and final["scc_count"] > 0
    assert final["scc_rows"] >= final["scc_shard_rows"] > 0 and final["finisher_rows"] == 0
    assert 0 < final["cross_shard_executed"] < final["executed"]
    assert 0.3 * final["executed"] < final["read_rows"] == final["gets_replied"] < 0.7 * final["executed"]
    # two programs: the round with one coordinator at start-up, the round with five made
    # ready at the second site's hello, before any command of it; nothing compiled after
    assert final["precompiled_programs"] == final["stage_precompile_n"] == 2
    assert final["jax_recompiles"] + final["jax_cache_hits"] == 2

    plain = small(False)
    assert plain["correct"] is True and plain["failed"] == 0
    assert set(plain["metrics"]) == {"goodput_cmds_s", "setup_s"}


def test_the_generators_records_carry_the_site_and_the_shards_of_every_command():
    small(False)
    history = np.load(os.path.join(run.ROOT, "benchmark_out", CELL, "trace0", "history.npz"))
    assert {"site", "shards", "more_key"} <= set(history.files)
    site, shards = history["site"], history["shards"]
    assert len(site) == len(shards) == len(history["client"])
    # a generator process a site, an equal share of clients each; the read-back at site 0
    assert set(site.tolist()) == {0, 1, 2, 3, 4}
    assert (site == (history["client"] - 1) % 5)[history["client"] <= 60].all()
    assert set(shards.tolist()) <= {1, 2} and (shards == 2).mean() > 0.4


def test_the_bytes_of_a_sites_round_follow_the_shape_and_the_share_stays_under_the_roofline():
    import pytest

    from benchmark import round_bytes_sites

    config = run.load_cell(run.ROOT, CELL)["config"]
    work = 4096 + 4096
    slots = work * 2
    on_one = round_bytes_sites.round_min_bytes(config, 1)
    assert on_one == round_bytes_sites.sites_round_min_bytes(5, 4096, 4096, 2, 3) == (
        4096 * (4 * 4 + 1) + 2 * 4096 * (5 * 4 + 1) + 2 * 2 * 5 * slots * 4
        + work * 12 * 4 + work * (2 * 4 + 3)) == 2_035_712
    # a device of a 4 x 1 mesh holds one shard's five rows too; of an 8 x 1 mesh two or three
    assert round_bytes_sites.round_min_bytes(config, 4) == on_one
    assert round_bytes_sites.round_min_bytes(config, 10) < on_one
    assert round_bytes_sites.fast_quorum_size("epaxos", 5, 1) == 3
    assert round_bytes_sites.fast_quorum_size("atlas", 5, 2) == 4
    tempo = run.load_cell(run.ROOT, "tempo_n5_1m.zipf_sat")["config"]
    assert round_bytes_sites.round_min_bytes(tempo, 1) is None
    reader = run._module(os.path.join(run.ROOT, "benchmark"), "readers", "sites_round_hbm_share")
    ctx = {"trace": {"busy_per_round_s": 0.010}, "config": config,
           "snapshot_delta": {"rounds": 90, "device_dispatches": 90},
           "snapshot_end": {"sites_registered": 5,
                            "backend": {"platform": "tpu", "device_kind": "TPU v5 lite",
                                        "mesh_shape": {"replica": 1, "batch": 1}}}}
    value = reader.read(ctx)
    assert value == pytest.approx(100.0 * on_one / (0.010 * 819e9))
    assert 0 < value < 1  # far under the roofline: the round is not bandwidth-bound
    # what it cannot read it leaves out: no capture, no TPU, no dispatch in the window, a
    # server at whose sites no client registered (one coordinator served, or a parent's)
    assert reader.read({**ctx, "trace": None}) is None
    assert reader.read({**ctx, "snapshot_end": {"sites_registered": 5,
                                               "backend": {"platform": "cpu"}}}) is None
    assert reader.read({**ctx, "snapshot_delta": {}}) is None
    assert reader.read({**ctx, "snapshot_end": {**ctx["snapshot_end"], "sites_registered": 1}}) is None
    assert reader.read({**ctx, "snapshot_end": {"backend": ctx["snapshot_end"]["backend"]}}) is None
    with pytest.raises(KeyError):  # a TPU that is not in the table is an error, not a default
        reader.read({**ctx, "snapshot_end": {"sites_registered": 5, "backend": {
            "platform": "tpu", "device_kind": "TPU v9"}}})


def test_a_server_without_the_new_counters_reads_nothing_and_does_not_raise():
    """A snapshot without `scc_span_rows`, `scc_shard_rows` and `sites_registered`, as a
    server before PR 49 (or 46) writes it: the six metrics are left out, none raises."""
    cell = run.load_cell(run.ROOT, CELL)
    new = [m for m in cell["per_layer"] if m["name"] in OURS | {"sites_round_hbm_share.sat"}]
    assert len(new) == 6
    ctx = {"measured": {"due": []}, "snapshot_delta": {"replied": 10, "rounds": 3, "executed": 10,
                                                       "device_dispatches": 3},
           "snapshot_end": {"backend": {"platform": "tpu", "device_kind": "TPU v5 lite",
                                        "mesh_shape": {"replica": 1, "batch": 1}}},
           "config": cell["config"], "mix": cell["mix"], "trace": {"busy_per_round_s": 0.01},
           "base": cell["base"]}
    assert run.read_metrics(new, ctx) == {}
