"""The tree's own four-chip cell, `tempo_n5_4shard_2key.ycsbt_zipf07_sat`
(PR 27): it loads and finds its files, carries its five metrics and no other
cell carries the four that list it (said of `root`, `conftest.py`: of the
tree and of its copies with a later PR's entries appended, so by name and not
by place), its two new readers read a recorded capture and a shape, and the
cell runs small on the CPU over four forced devices, one shard a device, as
`test_benchmark_e2e_multi.py` runs the copy that `next_cell.py` makes."""

import gzip
import json
import os
import time

import pytest

from benchmark import collectives, round_bytes, run
from tests.benchmark_tests import contract_rules as rules
from tests.benchmark_tests import next_cell

ROOT = run.ROOT
CELL = rules.FOUR_CHIP_CELL
LISTED = set(rules.FOUR_CHIP_FIVE) - {"precompile_ms"}
RECORDED = os.path.join(ROOT, "benchmark", "testdata", "trace_small.xplane.pb.gz")


def test_the_cell_loads_finds_its_files_and_is_the_one_cell_on_four_chips(root):
    """(The name is the test's first: it is a cell on four chips, once; how
    many such cells a tree may have is the contract rules' to hold.)"""
    rules.a_cell_finds_its_files_by_name(root, CELL)
    cell = run.load_cell(root, CELL)
    assert cell["chips"] == 4 and cell["mix"]["generator"] == "kv_multi"
    assert CELL in [c["name"] for c in rules.bench(root)["workloads"] if c["chips"] == 4]
    flags = rules.flags_of(cell["config"])
    assert flags == {"--protocol": "newt", "-n": "5", "-f": "1", "--shard-count": "4",
                     "--device-key-width": "2", "--device-key-buckets": "4194304",
                     "--device-batch": "4096", "--device-pending": "4096"}
    mix = cell["mix"]
    assert (mix["loop"], mix["clients"], mix["generator_processes"]) == ("closed", 8192, 4)
    assert mix["key_gen"] == {"kind": "zipf", "coefficient": 0.7, "keys_per_shard": 1_000_000}
    assert (mix["keys_per_command"], mix["shard_count"], mix["read_share"]) == (2, 4, 0.0)
    assert (mix["warmup_s"], mix["drain_limit_s"], mix["readback_keys"]) == (8.0, 30.0, 512)
    assert cell["config"]["payload_bytes"] == 100 and cell["config"]["reduced"] == []
    assert "replica:4 x batch:1" in cell["config"]["deployment"]["layout"]
    assert "3 sites a shard" in cell["config"]["assumed"]["n"]
    assert {m["name"] for m in cell["end_to_end"]} == {"goodput_cmds_s", "setup_s"}


def test_the_cell_is_what_next_cell_writes_under_another_name(tmp_path):
    """`next_cell.py` stays as it was (its names are not this cell's, so its
    copy of the tree holds both): the deployment, the flags, the guarantees
    and the mix it writes are this cell's."""
    root = next_cell.copy_tree(str(tmp_path))
    theirs = run.load_cell(root, next_cell.add_next_cell(root))
    ours = run.load_cell(ROOT, CELL)
    for key in ("server_flags", "device_batch", "payload_bytes", "guarantees", "reduced"):
        assert ours["config"][key] == theirs["config"][key], key
    for key in ("protocol", "n", "f", "shards", "keys_per_shard", "keys_per_command",
                "replication", "chips"):
        assert ours["config"]["deployment"][key] == theirs["config"]["deployment"][key], key
    assert set(theirs["config"]["assumed"]) <= set(ours["config"]["assumed"])
    for key in ("generator", "loop", "clients", "generator_processes", "key_gen",
                "keys_per_command", "shard_count", "read_share", "warmup_s", "drain_limit_s",
                "readback_keys"):
        assert ours["mix"][key] == theirs["mix"][key], key
    assert sum(c["chips"] == 4 for c in rules.bench(root)["workloads"]) == 2  # 2 of 6: allowed


def test_the_cell_carries_its_five_metrics_and_no_other_cell_the_four_that_list_it(root):
    spec = rules.bench(root)
    ours = {m["name"] for m in run.load_cell(root, CELL)["per_layer"]}
    assert LISTED | {"precompile_ms"} <= ours
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name in LISTED:
        assert entries[name]["workloads"] == [CELL] and entries[name]["moves"] == "goodput_cmds_s"
    assert "workloads" not in entries["precompile_ms"] and entries["precompile_ms"]["moves"] == "setup_s"
    for other in rules.cells(root):
        carried = {m["name"] for m in run.load_cell(root, other)["per_layer"]}
        assert "precompile_ms" in carried  # set-up is every cell's
        if other != CELL:
            assert not carried & LISTED
    # all five are there, in the order they were appended in, wherever they stand;
    # the configuration and the cell are there once
    rules.the_four_chip_cell_is_there_once_with_its_five_metrics_in_their_order(root)


# --- the two readers that are new ---------------------------------------------------


def tpu_planes(per_device):
    return [("/host:CPU", [("python", [("$loop", 0.0, 100e9)])])] + [
        (f"/device:TPU:{at}", [("XLA Ops", ops), ("XLA Modules", [("jit_round", 0.0, 50e9)])])
        for at, ops in enumerate(per_device)]


def test_collective_time_is_the_union_of_the_collectives_over_the_union_of_all_operations():
    one = [("%while.41 = (s32[]) while(...)", 10e9, 6e9),
           ("%all-gather.31 = s32[20,64] all-gather(...)", 16e9, 2e9),
           ("%fusion.150 = s32[] fusion(...)", 18e9, 1e9),
           ("%all-reduce.5 = s32[] all-reduce(...)", 19e9, 1e9)]
    two = [("%while.41 = (s32[]) while(...)", 10e9, 8e9),
           ("%all-gather.31 = s32[20,64] all-gather(...)", 18e9, 1e9),
           ("%collective-permute.2 = s32[] collective-permute(...)", 18.5e9, 1e9),  # overlaps
           ("%copy.7 = s32[] copy(...)", 30e9, 1e9)]
    out = collectives.reduce_planes(tpu_planes([one, two]), "tpu")
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx(10.0 + 10.5)
    assert out["collective_s"] == pytest.approx(3.0 + 1.5)
    assert out["share"] == pytest.approx(4.5 / 20.5)
    assert out["by_op"][0] == ["all-gather.31", pytest.approx(3.0)]
    assert {name for name, _ in out["by_op"]} == {"all-gather.31", "all-reduce.5",
                                                  "collective-permute.2"}


def test_a_program_without_collectives_reads_zero_and_a_capture_without_a_device_nothing():
    out = collectives.reduce_planes(tpu_planes([[("%fusion.1 = s32[] fusion()", 1e9, 1e9)]]), "tpu")
    assert out["share"] == 0.0 and out["by_op"] == [] and out["busy_s"] == pytest.approx(1.0)
    assert collectives.reduce_planes([("/host:CPU", [("python", [("$x", 0.0, 5e9)])])], "tpu") == {}
    assert collectives.reduce_capture("/nonexistent", "tpu") == {}


def test_the_collectives_of_the_recorded_trace_from_the_chip(tmp_path):
    """The one-chip capture of PR 23: device operations, none of them a
    collective; the reader's command prints the same as one JSON line."""
    import subprocess
    import sys

    path = tmp_path / "recorded.xplane.pb"
    with gzip.open(RECORDED, "rb") as src:
        path.write_bytes(src.read())
    out = collectives.reduce_capture(str(tmp_path), "tpu")
    assert out["devices"] == 1 and out["busy_s"] > 0 and out["share"] == 0.0
    done = subprocess.run([sys.executable, "-m", "benchmark.collectives", str(tmp_path), "tpu"],
                          capture_output=True, text=True, cwd=ROOT, timeout=120,
                          env={**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0 and json.loads(done.stdout.strip().splitlines()[-1]) == out


def test_the_bytes_of_a_round_follow_the_shape_and_the_share_stays_under_the_roofline():
    config = run.load_cell(ROOT, CELL)["config"]
    slots = (4096 + 4096) * 2
    assert round_bytes.newt_round_min_bytes(5, 5, 4096, 4096, 2) == (
        4096 * 4 * 4 + 2 * 4096 * 5 * 4 + 2 * (2 * 5 * slots * 4) + 5 * slots * 4 + 8192 * 19)
    on_four, on_two, on_one = (round_bytes.round_min_bytes(config, axis) for axis in (4, 2, 1))
    assert on_four == round_bytes.newt_round_min_bytes(5, 5, 4096, 4096, 2)
    assert on_four < on_two < on_one < 8_000_000  # rows held: 5, 10, 20
    assert round_bytes.round_min_bytes(run.load_cell(ROOT, "epaxos_n5_1m.zipf_sat")["config"], 1) is None
    reader = run._module(os.path.join(ROOT, "benchmark"), "readers", "device_round_hbm_share")
    ctx = {"trace": {"busy_per_round_s": 0.162}, "config": config,
           "snapshot_delta": {"rounds": 90, "device_dispatches": 60},
           "snapshot_end": {"backend": {"platform": "tpu", "device_kind": "TPU v5 lite",
                                        "mesh_shape": {"replica": 4, "batch": 1}}}}
    value = reader.read(ctx)
    assert value == pytest.approx(100.0 * on_four * 1.5 / (0.162 * 819e9))
    assert 0 < value < 1  # far under the roofline: the round is not bandwidth-bound
    # what it cannot read it leaves out: no capture, no TPU, no dispatch in the window
    assert reader.read({**ctx, "trace": None}) is None
    assert reader.read({**ctx, "snapshot_end": {"backend": {"platform": "cpu"}}}) is None
    assert reader.read({**ctx, "snapshot_delta": {}}) is None
    with pytest.raises(KeyError):  # a TPU that is not in the table is an error, not a default
        reader.read({**ctx, "snapshot_end": {"backend": {"platform": "tpu", "device_kind": "TPU v9"}}})


def test_a_server_without_the_new_counters_and_stage_reads_nothing_and_does_not_raise():
    """The parent commit's snapshot, as the driver's traced runs of the
    parent meet it with this PR's benchmark files."""
    cell = run.load_cell(ROOT, CELL)
    new = [m for m in cell["per_layer"] if m["name"] in LISTED | {"precompile_ms"}]
    ctx = {"measured": {"due": []}, "snapshot_delta": {"replied": 10, "rounds": 3, "device_dispatches": 3},
           "snapshot_end": {"backend": {"platform": "tpu", "device_kind": "TPU v5 lite",
                                        "mesh_shape": {"replica": 2, "batch": 2}}},
           "config": cell["config"], "mix": cell["mix"], "trace": None, "base": cell["base"]}
    assert run.read_metrics(new, ctx) == {}


# --- the cell, small, on the CPU ---------------------------------------------------

SMALL_CONFIG = {
    "server_flags": ["--protocol", "newt", "-n", "5", "-f", "1", "--shard-count", "4",
                     "--device-key-width", "2", "--device-key-buckets", "256",
                     "--device-batch", "32", "--device-pending", "32"],
    "device_batch": 32,
}
SMALL_MIX = {"clients": 24, "generator_processes": 2, "warmup_s": 0.5, "readback_keys": 16,
             "key_gen": {"kind": "zipf", "coefficient": 0.7, "keys_per_shard": 16}}


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_the_cell_small_on_four_forced_devices_one_shard_a_device(trace, monkeypatch, capsys):
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    result = run.run_cell(CELL, 2**31 + 27, 3.0, trace, platform="cpu", overrides=SMALL_MIX,
                          config_overrides=SMALL_CONFIG, started=time.monotonic())
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 300
    assert result["compared"]["violations"] == {"value": 0, "limit": 0}
    assert result["device"]["count"] == 4
    out = os.path.join(ROOT, "benchmark_out", CELL, f"trace{int(trace)}")
    with open(os.path.join(out, "snapshot.json")) as fh:
        snapshot = json.load(fh)
    assert snapshot["backend"]["mesh_shape"] == {"replica": 4, "batch": 1}
    assert snapshot["backend"]["shards_on_device"] == [[0], [1], [2], [3]]
    assert snapshot["precompiled_programs"] == 4 and snapshot["stage_precompile_n"] == 4
    with open(os.path.join(out, "server.out")) as fh:
        assert " mesh=replica:4xbatch:1 shards_on_device=0|1|2|3 " in fh.read()
    printed = capsys.readouterr().out
    stats = json.loads(printed.split("# check: violations 0 (limit 0); ")[1].split(" ; server")[0])
    assert stats["multi_key_commands"] > 0 and stats["cross_key_edges"] > 0
    if not trace:
        assert set(result["metrics"]) == {"goodput_cmds_s", "setup_s"}
        return
    metrics = {name: m["value"] for name, m in result["metrics"].items()}
    # every per-layer metric of the cell but the roofline share, which needs the chip's peaks
    carried = {m["name"] for m in run.load_cell(ROOT, CELL)["per_layer"]}
    assert set(metrics) == carried - {"device_round_hbm_share.sat"}
    assert 40 < metrics["cross_shard_share.sat"] < 95
    assert metrics["shard_replies_per_cmd.sat"] == pytest.approx(
        1 + metrics["cross_shard_share.sat"] / 100, abs=0.1)
    assert metrics["precompile_ms"] > 0 and metrics["compile_ms_in_window.sat"] == 0
    assert 0 <= metrics["collective_share.sat"] <= 100
    assert "# collectives" in printed
