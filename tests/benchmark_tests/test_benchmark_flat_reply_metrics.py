"""The per-layer metric pair of the one-key reply (PR 41:
`reply_flat_share.sat`, `reply_flat_share.open`): data files and appended
entries on a reader the benchmark had.  Their files say what their entries
say and stand after every entry the benchmark had, every cell that reports
what they move reports its one of the pair (the two-key cells too: they have
no list of cells), the window's counter deltas of a server without the
counter read nothing, those of a one-key cell 100 and those of a two-key cell
their 0."""

import os

import pytest

from benchmark import run
from tests.benchmark_tests import contract_rules as rules

ROOT = run.ROOT
BASE = os.path.join(ROOT, "benchmark")
PAIR = ["reply_flat_share.sat", "reply_flat_share.open"]
# what the benchmark's last entry was before them (PR 40's)
LAST_BEFORE = "cross_shard_cmd_share.sat"
# the accepted metric of the reply stage that divides by the same counter
SIBLING = "reply_plain_share"


@pytest.mark.parametrize("name", PAIR)
def test_each_of_the_pair_has_a_file_that_says_what_its_entry_says_and_stands_last(name):
    spec = rules.bench(ROOT)
    names = [m["name"] for m in spec["per_layer"]]
    entry = spec["per_layer"][names.index(name)]
    own = run._load(os.path.join(BASE, "layer_metrics", name + ".json"))
    kind = name.rsplit(".", 1)[1]
    sibling = run._load(os.path.join(BASE, "layer_metrics", f"{SIBLING}.{kind}.json"))
    assert {key: own[key] for key in entry} == entry and "workloads" not in entry
    assert set(own) == set(entry) | {"reader", "args", "reads"}
    # no benchmark code came with them: the sibling's reader over the sibling's denominator
    assert own["reader"] == sibling["reader"] == "snapshot_ratio"
    assert own["args"] == {**sibling["args"], "num": ["reply_flat_frames"]}
    assert {key: entry[key] for key in ("unit", "better", "source", "layer", "moves")} == {
        key: sibling[key] for key in ("unit", "better", "source", "layer", "moves")}
    assert entry["moves"] == ("commit_p50_ms" if kind == "open" else "goodput_cmds_s")
    assert len(own["reads"]) > 80 and "PR 41" in own["reads"]
    # appended: after every entry the benchmark had, in the issue's order
    assert names[names.index(LAST_BEFORE) + 1:][:2] == PAIR


def test_every_cell_that_reports_what_they_move_reports_its_one_of_the_pair(root):
    """No list of cells: the open cells carry `.open`, the saturated ones,
    the two-key cells among them, `.sat`."""
    spec = rules.bench(root)
    seen = set()
    for cell in rules.cells(root):
        reported = {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}
        carried = {m["name"] for m in run.load_cell(root, cell)["per_layer"]}
        kind = ".open" if "commit_p50_ms" in reported else ".sat"
        assert carried & set(PAIR) == {"reply_flat_share" + kind}
        seen.add(kind)
    assert seen == {".open", ".sat"}


# The growth of a server's counters over a window, as `run_cell` takes it from the snapshots at
# its two ends. The parent's is PR 39's recorded on the chip (`test_benchmark_plain_frame_metrics`:
# `epaxos_n5_1m.zipf_sat`, a server before PR 41 has no `reply_flat_frames`); the one-key cell's
# has every reply flat; the two-key cell's is the shape of `atlas_n5_4shard_2key.ycsbt_w5_zipf07_sat`
# (1.75 replies a command, none of them a one-key command's).
PARENT_DELTA = {"session_decoded": 691200, "shard_replies": 691456, "reply_plain_frames": 691456,
                "replied": 691456, "executed": 691456}
ONE_KEY_DELTA = {**PARENT_DELTA, "reply_flat_frames": 691456, "session_flat_admitted": 691200}
TWO_KEY_DELTA = {"session_decoded": 441000, "shard_replies": 771750, "reply_plain_frames": 771750,
                 "reply_flat_frames": 0, "session_flat_admitted": 0, "replied": 441000, "executed": 441000}


def _ctx(loaded, delta):
    return {"snapshot_delta": delta, "snapshot_end": delta, "counted_s": 20.0, "config": loaded["config"],
            "mix": loaded["mix"], "trace": None, "base": loaded["base"]}


@pytest.mark.parametrize("cell", ["epaxos_n5_1m.zipf_sat", "tempo_n5_1m.zipf_open80",
                                  "atlas_n5_4shard_2key.ycsbt_w5_zipf07_sat"])
def test_deltas_without_the_counter_read_nothing_and_with_it_the_hand_computed_value(cell):
    """The driver's traced run of the parent (no counter) leaves the metric
    out and does not raise; a two-key cell reports its 0."""
    loaded = run.load_cell(ROOT, cell)
    (metric,) = [m for m in loaded["per_layer"] if m["name"] in PAIR]
    name = metric["name"]
    assert run.read_metrics([metric], _ctx(loaded, PARENT_DELTA)) == {}
    assert run.read_metrics([metric], _ctx(loaded, ONE_KEY_DELTA)) == {name: {"value": 100.0, "unit": "%"}}
    assert run.read_metrics([metric], _ctx(loaded, TWO_KEY_DELTA)) == {name: {"value": 0.0, "unit": "%"}}
    # one command in four with two keys on two shards: 3 flat frames of 3 + 2
    mixed = {**ONE_KEY_DELTA, "shard_replies": 500, "reply_flat_frames": 300}
    assert run.read_metrics([metric], _ctx(loaded, mixed))[name]["value"] == pytest.approx(60.0)
    # nothing replied in the window: no share of nothing
    idle = {**ONE_KEY_DELTA, "shard_replies": 0, "reply_flat_frames": 0}
    assert run.read_metrics([metric], _ctx(loaded, idle)) == {}
