"""The per-layer metric pair of a command that is what its frame carried
(PR 50: `wire_ops_share.sat`, `wire_ops_share.open`): data files and appended
entries on a reader the benchmark had.  Their files say what their entries say
and stand after every entry the benchmark had, every cell that reports what
they move reports its one of the pair (they have no list of cells), the
window's counter deltas of a server without the counter read nothing, those of
a server whose pass read every command off its frame's tuple 100, and those of
a server whose commands reached the store in their dict form (the per-command
loop of the benchmark's broken servers, a driver stepped by hand) 0."""

import os

import pytest

from benchmark import run
from tests.benchmark_tests import contract_rules as rules

ROOT = run.ROOT
BASE = os.path.join(ROOT, "benchmark")
PAIR = ["wire_ops_share.sat", "wire_ops_share.open"]
# what the benchmark's last entry was before them (PR 49's)
LAST_BEFORE = "sites_round_hbm_share.sat"
# the accepted metric of the same layer, and the one that divides by the same counter
LAYER_SIBLING = "submit_plain_share"
COUNTER_SIBLING = "execute_pass_share"


@pytest.mark.parametrize("name", PAIR)
def test_each_of_the_pair_has_a_file_that_says_what_its_entry_says_and_stands_last(name):
    spec = rules.bench(ROOT)
    names = [m["name"] for m in spec["per_layer"]]
    entry = spec["per_layer"][names.index(name)]
    own = run._load(os.path.join(BASE, "layer_metrics", name + ".json"))
    kind = name.rsplit(".", 1)[1]
    of_layer = run._load(os.path.join(BASE, "layer_metrics", f"{LAYER_SIBLING}.{kind}.json"))
    of_counter = run._load(os.path.join(BASE, "layer_metrics", f"{COUNTER_SIBLING}.{kind}.json"))
    assert {key: own[key] for key in entry} == entry and "workloads" not in entry
    assert set(own) == set(entry) | {"reader", "args", "reads"}
    # no benchmark code came with them: the siblings' reader over the sibling's denominator
    assert own["reader"] == of_layer["reader"] == of_counter["reader"] == "snapshot_ratio"
    assert own["args"] == {"num": ["executed_off_wire"], "den": of_counter["args"]["den"], "scale": 100.0}
    assert of_counter["args"]["den"] == ["executed"]
    assert {key: entry[key] for key in ("source", "layer", "moves", "unit", "better")} == {
        key: of_layer[key] for key in ("source", "layer", "moves", "unit", "better")}
    assert (entry["unit"], entry["better"]) == ("%", "higher")
    assert entry["moves"] == ("commit_p50_ms" if kind == "open" else "goodput_cmds_s")
    assert len(own["reads"]) > 80 and "PR 50" in own["reads"]
    # appended: after every entry the benchmark had, in the issue's order
    assert names[names.index(LAST_BEFORE) + 1:] == PAIR


def test_every_cell_that_reports_what_they_move_reports_its_one_of_the_pair(root):
    """No list of cells: the open cells carry `.open`, the saturated ones,
    the four-chip cell among them, `.sat`."""
    spec = rules.bench(root)
    seen = set()
    for cell in rules.cells(root):
        reported = {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}
        carried = {m["name"] for m in run.load_cell(root, cell)["per_layer"]}
        kind = ".open" if "commit_p50_ms" in reported else ".sat"
        assert carried & set(PAIR) == {"wire_ops_share" + kind}
        seen.add(kind)
    assert seen == {".open", ".sat"}


# The growth of a server's counters over a window, as `run_cell` takes it from the snapshots at
# its two ends. The parent's is PR 48's shape (`test_benchmark_execute_pass_metrics`: the pass
# applied every command, and no server before PR 50 has `executed_off_wire`); the change's read
# every command off its frame's tuple; the broken server's ran the per-command loop, which
# asks each command for its dicts.
PARENT_DELTA = {"executed": 127318, "rounds": 2235, "drain_rows_walked": 127318, "executed_in_pass": 127318}
CHANGE_DELTA = {**PARENT_DELTA, "executed_off_wire": 127318}
BROKEN_DELTA = {**PARENT_DELTA, "executed_in_pass": 0, "executed_off_wire": 0}


def _ctx(loaded, delta):
    return {"snapshot_delta": delta, "snapshot_end": delta, "counted_s": 20.0, "config": loaded["config"],
            "mix": loaded["mix"], "trace": None, "base": loaded["base"]}


@pytest.mark.parametrize("cell", ["epaxos_n5_1m.zipf_sat", "tempo_n5_1m.zipf_open80", "fpaxos_n5_1m.zipf_sat",
                                  "atlas_n5_4shard_2key.ycsbt_w5_zipf07_sat",
                                  "atlas_n5_4shard_2key_5site.ycsbt_w50_zipf07_5site_sat",
                                  "tempo_n5_4shard_2key.ycsbt_zipf07_sat"])
def test_deltas_without_the_counter_read_nothing_and_with_it_the_hand_computed_value(cell):
    """The driver's traced run of the parent (no counter) leaves the metric
    out and does not raise; a server whose commands reached the store in
    their dict form reports 0."""
    loaded = run.load_cell(ROOT, cell)
    (metric,) = [m for m in loaded["per_layer"] if m["name"] in PAIR]
    name = metric["name"]
    assert run.read_metrics([metric], _ctx(loaded, PARENT_DELTA)) == {}
    assert run.read_metrics([metric], _ctx(loaded, CHANGE_DELTA)) == {name: {"value": 100.0, "unit": "%"}}
    assert run.read_metrics([metric], _ctx(loaded, BROKEN_DELTA)) == {name: {"value": 0.0, "unit": "%"}}
    # one command in eight stepped in by hand, in its dict form
    part = {**PARENT_DELTA, "executed": 1000, "executed_in_pass": 1000, "executed_off_wire": 875}
    assert run.read_metrics([metric], _ctx(loaded, part))[name]["value"] == pytest.approx(87.5)
    # nothing executed in the window: no share of nothing
    idle = {**CHANGE_DELTA, "executed": 0, "executed_in_pass": 0, "executed_off_wire": 0}
    assert run.read_metrics([metric], _ctx(loaded, idle)) == {}


def test_the_servers_snapshot_carries_the_counter_beside_the_passes():
    """The name the files read is the name the runtime publishes."""
    import inspect

    from fantoch_tpu.run import device_runner

    source = inspect.getsource(device_runner.DeviceRuntime)
    assert '"executed_off_wire": d.executed_off_wire' in source and '"executed_in_pass": d.executed_in_pass' in source
