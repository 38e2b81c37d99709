"""The two per-layer metrics of a dispatch's transfers (PR 54:
`transfers_per_dispatch.open` / `.sat`): data files and appended entries on a
reader the benchmark had.  Their files say what their entries say and stand
right after the entry the benchmark ended with before them, in the issue's
order (a later PR's stand after them: nothing here is held to the end of the
list); every cell that reports what they move reports its one; the window's
counter deltas of a server with the counter read `device_transfers` /
`device_dispatches`, those of a parent, which has no such counter, nothing."""

import os

import pytest

from benchmark import run
from tests.benchmark_tests import contract_rules as rules

ROOT = run.ROOT
BASE = os.path.join(ROOT, "benchmark")
STEP = "dispatch / fetch / drain (run/pipeline.py PipelineCore)"
PAIR = ["transfers_per_dispatch.open", "transfers_per_dispatch.sat"]
ARGS = {"num": ["device_transfers"], "den": ["device_dispatches"]}
# what the benchmark's last entry was before them (PR 53's)
LAST_BEFORE = "tempo_sites_round_hbm_share.sat"


@pytest.mark.parametrize("name", PAIR)
def test_each_of_the_pair_has_a_file_that_says_what_its_entry_says_and_names_a_reader_that_exists(name):
    spec = rules.bench(ROOT)
    names = [m["name"] for m in spec["per_layer"]]
    entry = spec["per_layer"][names.index(name)]
    own = run._load(os.path.join(BASE, "layer_metrics", name + ".json"))
    kind = name.rsplit(".", 1)[1]
    # an accepted metric of the layer on the same reader, over the same denominator
    of_layer = run._load(os.path.join(BASE, "layer_metrics", f"enqueue_ms.{kind}.json"))
    assert {key: own[key] for key in entry} == entry and "workloads" not in entry
    assert set(own) == set(entry) | {"reader", "args", "reads"}
    # no benchmark code came with them
    assert own["reader"] == of_layer["reader"] == "snapshot_ratio" and own["args"] == ARGS
    assert of_layer["args"]["den"] == ARGS["den"]
    assert os.path.exists(os.path.join(BASE, "readers", "snapshot_ratio.py"))
    assert entry["layer"] == of_layer["layer"] == STEP
    assert (entry["unit"], entry["better"], entry["source"]) == ("transfers", "lower", "program_counter")
    assert entry["moves"] == of_layer["moves"] == ("commit_p50_ms" if kind == "open" else "goodput_cmds_s")
    assert len(own["reads"]) > 80 and "PR 54" in own["reads"] and "reads nothing" in own["reads"]
    # appended: right after the entry the benchmark ended with, in the issue's order
    assert names[names.index(LAST_BEFORE) + 1:][:2] == PAIR
    assert len(names) >= 114


def test_every_cell_that_reports_what_they_move_reports_its_one(root):
    """No list of cells: the open cells carry `.open`, the saturated ones,
    the four-chip cell among them, `.sat`."""
    spec = rules.bench(root)
    seen = set()
    for cell in rules.cells(root):
        reported = {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}
        carried = {m["name"] for m in run.load_cell(root, cell)["per_layer"]}
        kind = ".open" if "commit_p50_ms" in reported else ".sat"
        assert carried & set(PAIR) == {"transfers_per_dispatch" + kind}
        seen.add(kind)
    assert seen == {".open", ".sat"}


# The growth of a server's counters over a window, as `run_cell` takes it from the snapshots at
# its two ends: 20 s of an open cell's rounds of 8 ms, and of a saturated cell's 20 dispatches a
# second.  The parent's snapshot has the dispatches and no count of transfers.
PARENT_DELTA = {"executed": 144_000, "device_dispatches": 2500, "stage_enqueue_ms": 7900.0,
                "stage_fetch_ms": 6025.0, "rounds": 2500}
CHANGE_DELTA = {**PARENT_DELTA, "device_transfers": 5000}
# the dependency round with a coordinator at every site: one round in four had finish rows
FINISH_DELTA = {**PARENT_DELTA, "device_transfers": 5625}


def _ctx(loaded, delta, counted_s=20.0):
    return {"snapshot_delta": delta, "snapshot_end": delta, "counted_s": counted_s, "config": loaded["config"],
            "mix": loaded["mix"], "trace": None, "base": loaded["base"]}


@pytest.mark.parametrize("cell", ["epaxos_n5_1m.zipf_open80", "tempo_n5_1m.zipf_open80", "epaxos_n5_1m.zipf_sat",
                                  "fpaxos_n5_1m.zipf_sat", "caesar_n7_1m.hot50_sat",
                                  "epaxos_n5_1m_5site.conflict50_sat", "tempo_n5_4shard_2key.ycsbt_zipf07_sat"])
def test_deltas_with_the_counter_read_the_ratio_and_a_parents_nothing(cell):
    """The driver's traced run of the parent (no count of transfers) leaves
    the metric out and does not raise."""
    loaded = run.load_cell(ROOT, cell)
    (metric,) = [m for m in loaded["per_layer"] if m["name"] in PAIR]
    assert run.read_metrics([metric], _ctx(loaded, PARENT_DELTA)) == {}
    got = run.read_metrics([metric], _ctx(loaded, CHANGE_DELTA))
    assert got == {metric["name"]: {"value": pytest.approx(2.0), "unit": "transfers"}}
    with_finish = run.read_metrics([metric], _ctx(loaded, FINISH_DELTA))
    assert with_finish[metric["name"]]["value"] == pytest.approx(2.25)
    # a ratio of two counters of the same stretch: a traced run's shorter stretch reads the same
    assert run.read_metrics([metric], _ctx(loaded, CHANGE_DELTA, counted_s=16.0)) == got
    # no dispatch in the window: no ratio of nothing
    idle = {**CHANGE_DELTA, "device_dispatches": 0, "device_transfers": 0}
    assert run.read_metrics([metric], _ctx(loaded, idle)) == {}


def test_the_servers_snapshot_carries_the_counter_the_files_read():
    """The names the files read are the names the program publishes, and
    the count moves where an array crosses: the one `device_put` of a
    dispatch, the leaves of its one `device_get`, the finisher's fetch."""
    import inspect

    from fantoch_tpu.run import device_runner, pipeline

    counters = inspect.getsource(pipeline.PipelineCore.device_counters)
    assert '"device_transfers": self.transfers' in counters
    assert '"device_dispatches": self.dispatches' in counters
    publish = inspect.getsource(device_runner.DeviceRuntime._publish_tallies)
    assert "**d.device_counters()" in publish
    up = inspect.getsource(device_runner._DriverCore._columns_to_device)
    assert "self.transfers += 1" in up and "jax.device_put(staged.packed, sharding)" in up
    down = inspect.getsource(pipeline.PipelineCore._fetch)
    assert "self.transfers += len(jax.tree_util.tree_leaves(out))" in down and "jax.device_get(out)" in down
    finish = inspect.getsource(device_runner.DeviceDriver._finish_order)
    assert "self.transfers += 1" in finish and "out.deps_gid" in finish
    # ... and a driver's two counters after three rounds say two a dispatch
    driver = device_runner.PaxosDeviceDriver(3, f=1, batch_size=8, key_buckets=64, pending_capacity=8)
    for _ in range(3):
        driver.step([])
    got = driver.device_counters()
    assert (got["device_transfers"], got["device_dispatches"]) == (6, 3)
