"""The four per-layer metrics of the client plane's codec (PR 39:
`submit_plain_share.*`, `reply_plain_share.*`): data files and appended
entries on a reader the benchmark had.  Their files say what their entries
say and stand after every entry the benchmark had, every cell that reports
what they move reports one of each pair (a later PR's cell too: `root`,
`conftest.py`), the window's counter deltas of a server without the counters
read nothing and those of one with them the value worked out by hand."""

import os

import pytest

from benchmark import run
from tests.benchmark_tests import contract_rules as rules

ROOT = run.ROOT
BASE = os.path.join(ROOT, "benchmark")
FOUR = ["submit_plain_share.sat", "submit_plain_share.open",
        "reply_plain_share.sat", "reply_plain_share.open"]
# what the benchmark's last entry was before them (PR 38's)
LAST_BEFORE = "slots_per_cmd.sat"
# stem -> the counters it divides, and an accepted metric of its layer
PARTS = {"submit_plain_share": (["session_plain_decoded"], ["session_decoded"], "session_us_per_cmd.sat"),
         "reply_plain_share": (["reply_plain_frames"], ["shard_replies"], "deliver_us_per_cmd.sat")}


@pytest.mark.parametrize("name", FOUR)
def test_each_of_the_four_has_a_file_that_says_what_its_entry_says_and_stands_last(name):
    spec = rules.bench(ROOT)
    names = [m["name"] for m in spec["per_layer"]]
    entry = spec["per_layer"][names.index(name)]
    own = run._load(os.path.join(BASE, "layer_metrics", name + ".json"))
    stem, kind = name.rsplit(".", 1)
    num, den, neighbour = PARTS[stem]
    assert {key: own[key] for key in entry} == entry and "workloads" not in entry
    assert set(own) == set(entry) | {"reader", "args", "reads"}
    assert own["reader"] == "snapshot_ratio" and entry["unit"] == "%"
    assert own["args"] == {"num": num, "den": den, "scale": 100.0}
    assert entry["better"] == "higher" and entry["source"] == "program_counter"
    assert entry["moves"] == ("commit_p50_ms" if kind == "open" else "goodput_cmds_s")
    # no benchmark code came with them: the reader is one an older metric uses
    assert own["reader"] in {run._load(os.path.join(BASE, "layer_metrics", m["name"] + ".json"))["reader"]
                             for m in spec["per_layer"] if m["name"] not in FOUR}
    assert len(own["reads"]) > 80 and "PR 39" in own["reads"]
    # the layer, letter for letter as its accepted metric names it
    assert entry["layer"] == spec["per_layer"][names.index(neighbour)]["layer"]
    # appended: after every entry the benchmark had, in the issue's order
    assert names.index(name) > names.index(LAST_BEFORE)
    assert names[names.index(LAST_BEFORE) + 1:][:4] == FOUR


def test_every_cell_that_reports_what_they_move_reports_one_of_each_pair(root):
    """No list of cells: the open cells carry the two `.open`, the saturated
    ones, the four-chip cell among them, the two `.sat`."""
    spec = rules.bench(root)
    seen = set()
    for cell in rules.cells(root):
        reported = {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}
        carried = {m["name"] for m in run.load_cell(root, cell)["per_layer"]}
        kind = ".open" if "commit_p50_ms" in reported else ".sat"
        assert carried & set(FOUR) == {stem + kind for stem in PARTS}
        seen.add(kind)
    assert seen == {".open", ".sat"}


# The growth of a server's counters over a window, as `run_cell` takes it from the snapshots at
# its two ends and logs it ("# server counter deltas over the first ... s of the window"). Recorded
# on the chip (PR 39 call 1, `epaxos_n5_1m.zipf_sat`, seed 3900000101, untraced, 20 s): the parent
# (PR 38's tree: neither counter), then the change. Each of the four generator processes holds
# one connection, whose `ClientHi` is decoded before the window, so inside it every client frame
# is a `Submit`.
PARENT_DELTA = {"session_decoded": 634428, "session_reads": 637, "shard_replies": 634171, "replied": 634171,
                "reply_bytes": 103937596, "executed": 634171}
CHANGE_DELTA = {"session_decoded": 691200, "session_plain_decoded": 691200, "session_reads": 688,
                "shard_replies": 691456, "reply_plain_frames": 691456, "replied": 691456,
                "reply_bytes": 84733699, "executed": 691456}


def _ctx(loaded, delta):
    return {"snapshot_delta": delta, "snapshot_end": delta, "counted_s": 20.0, "config": loaded["config"],
            "mix": loaded["mix"], "trace": None, "base": loaded["base"]}


@pytest.mark.parametrize("cell", ["epaxos_n5_1m.zipf_sat", "tempo_n5_1m.zipf_open80",
                                  "tempo_n5_4shard_2key.ycsbt_zipf07_sat"])
def test_deltas_without_the_counters_read_nothing_and_with_them_the_hand_computed_value(cell):
    """The driver's traced run of the parent (no counter) leaves the metrics
    out and does not raise."""
    loaded = run.load_cell(ROOT, cell)
    two = [m for m in loaded["per_layer"] if m["name"] in FOUR]
    submit, reply = sorted(m["name"] for m in two if m["name"].startswith("submit")), \
        sorted(m["name"] for m in two if m["name"].startswith("reply"))
    assert len(two) == 2 and len(submit) == len(reply) == 1
    submit, reply = submit[0], reply[0]
    assert run.read_metrics(two, _ctx(loaded, PARENT_DELTA)) == {}
    assert run.read_metrics(two, _ctx(loaded, CHANGE_DELTA)) == {
        submit: {"value": 100.0, "unit": "%"}, reply: {"value": 100.0, "unit": "%"}}
    # a connection opened inside the window: its ClientHi is a pickle
    decoded = CHANGE_DELTA["session_decoded"]
    late = {**CHANGE_DELTA, "session_decoded": decoded + 1}
    assert run.read_metrics(two, _ctx(loaded, late))[submit]["value"] == pytest.approx(
        100.0 * decoded / (decoded + 1))
    assert 99.999 < 100.0 * decoded / (decoded + 1) < 100.0
    # a tenth of the frames from a sender before PR 39 (pickles that name their callable), and a
    # reply stage that built a ToClient for one frame in four
    old = {**CHANGE_DELTA, "session_plain_decoded": decoded - decoded // 10,
           "reply_plain_frames": CHANGE_DELTA["shard_replies"] - CHANGE_DELTA["shard_replies"] // 4}
    got = run.read_metrics(two, _ctx(loaded, old))
    assert got[submit]["value"] == pytest.approx(90.0, abs=0.001)
    assert got[reply]["value"] == pytest.approx(75.0, abs=0.001)
    # nothing decoded and nothing replied in the window: no share of nothing
    idle = {**CHANGE_DELTA, "session_decoded": 0, "session_plain_decoded": 0,
            "shard_replies": 0, "reply_plain_frames": 0}
    assert run.read_metrics(two, _ctx(loaded, idle)) == {}
    # one of the two counters alone (a server half-way there) reads the one it has
    half = {key: value for key, value in CHANGE_DELTA.items() if key != "reply_plain_frames"}
    assert run.read_metrics(two, _ctx(loaded, half)) == {submit: {"value": 100.0, "unit": "%"}}
