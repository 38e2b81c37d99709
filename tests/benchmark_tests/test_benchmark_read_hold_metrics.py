"""The per-layer metric of the hold on the sessions' sockets (PR 56:
`read_hold_share.open`): a data file and an appended entry on a reader the
benchmark had.  The file says what its entry says and stands right after
the entry the benchmark ended with before it (a later PR's stand after it:
nothing here is held to the end of the list); every open cell reports it and
no saturated one; the window's counter deltas of a server with the counter
read 100 x `device_held_dispatches` / `device_dispatches`, those of a parent,
which has no such counter, nothing."""

import os

import pytest

from benchmark import run
from tests.benchmark_tests import contract_rules as rules

ROOT = run.ROOT
BASE = os.path.join(ROOT, "benchmark")
SESSION = "session / admission (_DeviceClientSession, BoundedSubmitRing)"
NAME = "read_hold_share.open"
ARGS = {"num": ["device_held_dispatches"], "den": ["device_dispatches"], "scale": 100.0}
# what the benchmark's last entry was before them (PR 55's)
LAST_BEFORE = "atlas_f2_sites_round_hbm_share.sat"


def test_the_metric_has_a_file_that_says_what_its_entry_says_and_names_a_reader_that_exists():
    spec = rules.bench(ROOT)
    names = [m["name"] for m in spec["per_layer"]]
    entry = spec["per_layer"][names.index(NAME)]
    own = run._load(os.path.join(BASE, "layer_metrics", NAME + ".json"))
    # an accepted metric of the layer on the same reader: what the share moves first
    of_layer = run._load(os.path.join(BASE, "layer_metrics", "frames_per_read.open.json"))
    # ... and the accepted ratio over the same denominator
    of_counter = run._load(os.path.join(BASE, "layer_metrics", "transfers_per_dispatch.open.json"))
    assert {key: own[key] for key in entry} == entry and "workloads" not in entry
    assert set(own) == set(entry) | {"reader", "args", "reads"}
    # no benchmark code came with it
    assert own["reader"] == of_layer["reader"] == of_counter["reader"] == "snapshot_ratio" and own["args"] == ARGS
    assert of_counter["args"]["den"] == ARGS["den"]
    assert os.path.exists(os.path.join(BASE, "readers", "snapshot_ratio.py"))
    assert entry["layer"] == of_layer["layer"] == SESSION
    assert (entry["unit"], entry["source"], entry["better"]) == ("%", "program_counter", "higher")
    assert entry["moves"] == of_layer["moves"] == "commit_p50_ms"
    assert len(own["reads"]) > 80 and "PR 56" in own["reads"] and "reads nothing" in own["reads"]
    # appended: right after the entry the benchmark ended with
    assert names[names.index(LAST_BEFORE) + 1] == NAME
    assert len(names) >= 120


def test_every_open_cell_reports_it_and_no_saturated_one(root):
    """No list of cells: the cells that report what it moves carry it."""
    spec = rules.bench(root)
    seen = set()
    for cell in rules.cells(root):
        reported = {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}
        carried = {m["name"] for m in run.load_cell(root, cell)["per_layer"]}
        is_open = "commit_p50_ms" in reported
        assert (NAME in carried) == is_open
        seen.add(is_open)
    assert seen == {True, False}


# The growth of a server's counters over a window, as `run_cell` takes it from the snapshots at
# its two ends: 20 s of an open cell's rounds of 5 ms, every one of them held; a window whose
# first dispatches ran before a client was connected.  The parent's snapshot has the dispatches
# and no count of the held ones.
PARENT_DELTA = {"executed": 144_000, "device_dispatches": 4000, "session_decoded": 144_000, "session_reads": 77_000,
                "rounds": 4000}
OPEN_DELTA = {**PARENT_DELTA, "device_held_dispatches": 4000, "session_reads": 16_000}
EARLY_DELTA = {**OPEN_DELTA, "device_held_dispatches": 3970}


def _ctx(loaded, delta, counted_s=20.0):
    return {"snapshot_delta": delta, "snapshot_end": delta, "counted_s": counted_s, "config": loaded["config"],
            "mix": loaded["mix"], "trace": None, "base": loaded["base"]}


@pytest.mark.parametrize("cell", ["epaxos_n5_1m.zipf_open80", "tempo_n5_1m.zipf_open80"])
def test_deltas_with_the_counter_read_the_share_and_a_parents_nothing(cell):
    """The driver's traced run of the parent (no count of held dispatches)
    leaves the metric out and does not raise."""
    loaded = run.load_cell(ROOT, cell)
    (metric,) = [m for m in loaded["per_layer"] if m["name"] == NAME]
    assert run.read_metrics([metric], _ctx(loaded, PARENT_DELTA)) == {}
    got = run.read_metrics([metric], _ctx(loaded, OPEN_DELTA))
    assert got == {metric["name"]: {"value": pytest.approx(100.0), "unit": "%"}}
    early = run.read_metrics([metric], _ctx(loaded, EARLY_DELTA))
    assert early[metric["name"]]["value"] == pytest.approx(99.25)
    # a ratio of two counters of the same stretch: a traced run's shorter stretch reads the same
    assert run.read_metrics([metric], _ctx(loaded, OPEN_DELTA, counted_s=16.0)) == got
    # no dispatch in the window: no share of nothing
    idle = {**OPEN_DELTA, "device_dispatches": 0, "device_held_dispatches": 0}
    assert run.read_metrics([metric], _ctx(loaded, idle)) == {}


def test_the_servers_snapshot_carries_the_counters_the_file_reads():
    """The names the file reads are names the program publishes (how the
    program counts them is the program's tests': tests/test_read_hold.py)."""
    import inspect

    from fantoch_tpu.run import device_runner, pipeline

    assert '"device_dispatches": self.dispatches' in inspect.getsource(pipeline.PipelineCore.device_counters)
    publish = inspect.getsource(device_runner.DeviceRuntime._publish_tallies)
    assert "**d.device_counters()" in publish and '"device_held_dispatches"' in publish
