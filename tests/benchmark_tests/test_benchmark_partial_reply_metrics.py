"""The per-layer metric of the reply made from one partial (PR 43:
`reply_partial_share.sat`): a data file and an appended entry on a reader the
benchmark had.  Its file says what its entry says and stands after every entry
the benchmark had, its cells are the two whose commands have two keys and both
report what it moves, the window's counter deltas of a server without the
counter read nothing, and those of a two-key cell the hand-computed share."""

import os

import pytest

from benchmark import run
from tests.benchmark_tests import contract_rules as rules

ROOT = run.ROOT
BASE = os.path.join(ROOT, "benchmark")
NAME = "reply_partial_share.sat"
# what the benchmark's last entry was before it (PR 41's)
LAST_BEFORE = "reply_flat_share.open"
# the accepted metric of the reply stage that divides by the same counter
SIBLING = "reply_flat_share.sat"
CELLS = ["tempo_n5_4shard_2key.ycsbt_zipf07_sat", "atlas_n5_4shard_2key.ycsbt_w5_zipf07_sat"]


def test_the_file_says_what_its_entry_says_and_stands_last():
    spec = rules.bench(ROOT)
    names = [m["name"] for m in spec["per_layer"]]
    entry = spec["per_layer"][names.index(NAME)]
    own = run._load(os.path.join(BASE, "layer_metrics", NAME + ".json"))
    sibling = run._load(os.path.join(BASE, "layer_metrics", SIBLING + ".json"))
    assert {key: own[key] for key in entry} == entry
    assert set(own) == set(entry) | {"reader", "args", "reads"}
    # no benchmark code came with it: the sibling's reader over the sibling's denominator
    assert own["reader"] == sibling["reader"] == "snapshot_ratio"
    assert own["args"] == {**sibling["args"], "num": ["reply_partial_frames"]}
    assert {key: entry[key] for key in ("unit", "better", "source", "layer", "moves")} == {
        key: sibling[key] for key in ("unit", "better", "source", "layer", "moves")}
    assert entry["moves"] == "goodput_cmds_s"
    assert len(own["reads"]) > 80 and "PR 43" in own["reads"]
    # appended: after every entry the benchmark had
    assert names[names.index(LAST_BEFORE) + 1:][:1] == [NAME]


def test_its_cells_are_the_two_key_cells_and_both_report_what_it_moves(root):
    spec = rules.bench(root)
    (entry,) = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert entry["workloads"] == CELLS
    (goodput,) = [m for m in spec["end_to_end"] if m["name"] == entry["moves"]]
    for cell in rules.cells(root):
        loaded = run.load_cell(root, cell)
        carried = NAME in {m["name"] for m in loaded["per_layer"]}
        # a list of cells: a cell a later PR adds carries it only if that PR lists it
        assert carried == (cell in CELLS)
        if carried:
            assert cell in goodput["workloads"] and loaded["mix"]["keys_per_command"] == 2


# The growth of a server's counters over a window, as `run_cell` takes it from the snapshots at
# its two ends: the shape of `atlas_n5_4shard_2key.ycsbt_w5_zipf07_sat` (1.75 replies a command:
# three commands in four on two shards, a partial a reply; one in four with both keys on one
# shard, its one reply joined of two). A server before PR 43 has no `reply_partial_frames`.
PARENT_DELTA = {"session_decoded": 441000, "shard_replies": 771750, "reply_plain_frames": 771750,
                "reply_flat_frames": 0, "session_flat_admitted": 0, "replied": 441000, "executed": 441000}
TWO_KEY_DELTA = {**PARENT_DELTA, "reply_partial_frames": 661500}


def _ctx(loaded, delta):
    return {"snapshot_delta": delta, "snapshot_end": delta, "counted_s": 20.0, "config": loaded["config"],
            "mix": loaded["mix"], "trace": None, "base": loaded["base"]}


@pytest.mark.parametrize("cell", CELLS)
def test_deltas_without_the_counter_read_nothing_and_with_it_the_hand_computed_value(cell):
    """The driver's traced run of the parent (no counter) leaves the metric
    out and does not raise; 1.5 of a command's 1.75 replies are 85.71%."""
    loaded = run.load_cell(ROOT, cell)
    (metric,) = [m for m in loaded["per_layer"] if m["name"] == NAME]
    assert run.read_metrics([metric], _ctx(loaded, PARENT_DELTA)) == {}
    got = run.read_metrics([metric], _ctx(loaded, TWO_KEY_DELTA))
    assert got == {NAME: {"value": pytest.approx(100 * 1.5 / 1.75), "unit": "%"}}
    assert round(got[NAME]["value"], 2) == 85.71
    # every command with one key: every reply from its one partial
    one_key = {**TWO_KEY_DELTA, "shard_replies": 500, "reply_partial_frames": 500}
    assert run.read_metrics([metric], _ctx(loaded, one_key))[NAME]["value"] == 100.0
    # nothing replied in the window: no share of nothing
    idle = {**TWO_KEY_DELTA, "shard_replies": 0, "reply_partial_frames": 0}
    assert run.read_metrics([metric], _ctx(loaded, idle)) == {}
