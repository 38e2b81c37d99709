"""The two per-layer metrics of the masked drain (PR 37: `drain_rows_per_cmd.*`):
data files and appended entries on a reader the benchmark had.  Their files
say what their entries say and stand after every entry the benchmark had,
every cell that reports what they move reports one of them (a later PR's cell
too: `root`, `conftest.py`), a snapshot pair of a server without the counter
reads nothing and a pair with it the value worked out by hand."""

import os

import pytest

from benchmark import run
from tests.benchmark_tests import contract_rules as rules

ROOT = run.ROOT
BASE = os.path.join(ROOT, "benchmark")
TWO = ["drain_rows_per_cmd.open", "drain_rows_per_cmd.sat"]
# what the benchmark's last entry was before them (PR 36's)
LAST_BEFORE = "loop_stopped_ms.sat"


@pytest.mark.parametrize("name", TWO)
def test_each_of_the_two_has_a_file_that_says_what_its_entry_says_and_stands_last(name):
    spec = rules.bench(ROOT)
    names = [m["name"] for m in spec["per_layer"]]
    entry = spec["per_layer"][names.index(name)]
    own = run._load(os.path.join(BASE, "layer_metrics", name + ".json"))
    assert {key: own[key] for key in entry} == entry and "workloads" not in entry
    assert set(own) == set(entry) | {"reader", "args", "reads"}
    assert own["reader"] == "snapshot_ratio" and entry["unit"] == "rows"
    assert own["args"] == {"num": ["drain_rows_walked"], "den": ["executed"], "scale": 1.0}
    assert entry["better"] == "lower" and entry["source"] == "program_counter"
    assert entry["moves"] == ("commit_p50_ms" if name.endswith(".open") else "goodput_cmds_s")
    # no benchmark code came with them: the reader is one an older metric uses
    assert own["reader"] in {run._load(os.path.join(BASE, "layer_metrics", m["name"] + ".json"))["reader"]
                             for m in spec["per_layer"] if m["name"] not in TWO}
    assert len(own["reads"]) > 80
    # the drain's layer, letter for letter as `execute_us_per_cmd.*` names it
    assert entry["layer"] == spec["per_layer"][names.index("execute_us_per_cmd.sat")]["layer"]
    # appended: after every entry the benchmark had, in the issue's order
    assert names.index(name) > names.index(LAST_BEFORE)
    assert names[names.index(LAST_BEFORE) + 1:][:2] == TWO


def test_every_cell_that_reports_what_they_move_reports_one_of_them(root):
    """No list of cells: the open cells carry `.open`, the saturated ones, the
    four-chip cell among them, `.sat`."""
    spec = rules.bench(root)
    seen = set()
    for cell in rules.cells(root):
        reported = {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}
        carried = {m["name"] for m in run.load_cell(root, cell)["per_layer"]}
        kind = ".open" if "commit_p50_ms" in reported else ".sat"
        assert carried & set(TWO) == {"drain_rows_per_cmd" + kind}
        seen.add(kind)
    assert seen == {".open", ".sat"}


# A server's snapshots at the two ends of a window, as `run_cell` pairs them. The growth over
# both windows is recorded: the 20 s of `tempo_n5_1m.zipf_open80` on the chip (PR 37 call 1, seed
# 3700000101), first the parent's run (PR 36's tree: no `drain_rows_walked`; 1399 rounds for
# 127,139 commands), then the change's (2235 rounds for 127,318). The third pair is the change's
# with one round added by hand that dropped rows beyond its pending capacity and scanned 4,186
# candidates.
PARENT_PAIR = ({"executed": 20957, "rounds": 228, "stage_execute_ms": 1037.666},
               {"executed": 148096, "rounds": 1627, "stage_execute_ms": 7299.0})
CHANGE_PAIR = ({"executed": 20778, "rounds": 373, "drain_rows_walked": 20778, "stage_execute_ms": 229.468},
               {"executed": 148096, "rounds": 2608, "drain_rows_walked": 148096, "stage_execute_ms": 1635.0})
SCANNED_PAIR = (CHANGE_PAIR[0], {**CHANGE_PAIR[1], "drain_rows_walked": 148096 + 4186})


def _delta(pair):
    """The growth of the counters between a pair, as `run_cell` takes it."""
    start, end = pair
    return {key: end[key] - start[key] for key in end
            if isinstance(end[key], (int, float)) and key in start}


@pytest.mark.parametrize("cell", ["tempo_n5_1m.zipf_open80", "epaxos_n5_1m.zipf_sat",
                                  "tempo_n5_4shard_2key.ycsbt_zipf07_sat"])
def test_a_pair_without_the_counter_reads_nothing_and_one_with_it_the_hand_computed_value(cell):
    """The driver's traced run of the parent (no counter) leaves the metric
    out and does not raise."""
    loaded = run.load_cell(ROOT, cell)
    (one,) = [m for m in loaded["per_layer"] if m["name"] in TWO]
    ctx = {"snapshot_delta": _delta(PARENT_PAIR), "snapshot_end": PARENT_PAIR[1], "counted_s": 20.0,
           "config": loaded["config"], "mix": loaded["mix"], "trace": None, "base": loaded["base"]}
    assert run.read_metrics([one], ctx) == {}
    ctx["snapshot_delta"] = _delta(CHANGE_PAIR)
    assert run.read_metrics([one], ctx) == {one["name"]: {"value": 1.0, "unit": "rows"}}
    # 127,318 executed rows and one scan of 4,186 over 127,318 commands
    ctx["snapshot_delta"] = _delta(SCANNED_PAIR)
    assert run.read_metrics([one], ctx) == {
        one["name"]: {"value": pytest.approx(131504 / 127318), "unit": "rows"}}
    # the walks it replaced: 16384 rows a round whatever the fill, 1399 rounds for 127,139 commands
    ctx["snapshot_delta"] = {**_delta(PARENT_PAIR), "drain_rows_walked": 16384 * 1399}
    assert run.read_metrics([one], ctx)[one["name"]]["value"] == pytest.approx(180.29, abs=0.01)
    # no command executed in the window: no cost per command
    ctx["snapshot_delta"]["executed"] = 0
    assert run.read_metrics([one], ctx) == {}
