"""The per-layer metric pair of the one-pass drain (PR 48:
`execute_pass_share.sat`, `execute_pass_share.open`): data files and appended
entries on a reader the benchmark had.  Their files say what their entries
say and stand after every entry the benchmark had, every cell that reports
what they move reports its one of the pair (they have no list of cells), the
window's counter deltas of a server without the counter read nothing, those
of a server whose drains took the pass 100, and those of a server with a
method the pass spells out replaced (the benchmark's broken servers) 0."""

import os

import pytest

from benchmark import run
from tests.benchmark_tests import contract_rules as rules

ROOT = run.ROOT
BASE = os.path.join(ROOT, "benchmark")
PAIR = ["execute_pass_share.sat", "execute_pass_share.open"]
# what the benchmark's last entry was before them (PR 46's)
LAST_BEFORE = "remote_site_share.sat"
# the accepted metric of the same stage that divides by the same counter
SIBLING = "drain_rows_per_cmd"


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
    assert own["args"] == {"num": ["executed_in_pass"], "den": sibling["args"]["den"], "scale": 100.0}
    assert sibling["args"]["den"] == ["executed"]
    assert {key: entry[key] for key in ("source", "layer", "moves")} == {
        key: sibling[key] for key in ("source", "layer", "moves")}
    assert (entry["unit"], entry["better"]) == ("%", "higher")
    assert entry["moves"] == ("commit_p50_ms" if kind == "open" else "goodput_cmds_s")
    assert len(own["reads"]) > 80 and "PR 48" in own["reads"]
    # appended: after every entry the benchmark had, in the issue's order
    assert names[names.index(LAST_BEFORE) + 1:][:2] == PAIR


def test_every_cell_that_reports_what_they_move_reports_its_one_of_the_pair(root):
    """No list of cells: the open cells carry `.open`, the saturated ones,
    the four-chip cell among them, `.sat`."""
    spec = rules.bench(root)
    seen = set()
    for cell in rules.cells(root):
        reported = {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}
        carried = {m["name"] for m in run.load_cell(root, cell)["per_layer"]}
        kind = ".open" if "commit_p50_ms" in reported else ".sat"
        assert carried & set(PAIR) == {"execute_pass_share" + kind}
        seen.add(kind)
    assert seen == {".open", ".sat"}


# The growth of a server's counters over a window, as `run_cell` takes it from the snapshots at
# its two ends. The parent's is PR 37's recorded on the chip (`test_benchmark_drain_rows_metrics`:
# the 20 s of `tempo_n5_1m.zipf_open80`; a server before PR 48 has no `executed_in_pass`); the
# change's has every command applied in the pass; the broken server's ran the per-command loop.
PARENT_DELTA = {"executed": 127318, "rounds": 2235, "drain_rows_walked": 127318}
CHANGE_DELTA = {**PARENT_DELTA, "executed_in_pass": 127318}
BROKEN_DELTA = {**PARENT_DELTA, "executed_in_pass": 0}


def _ctx(loaded, delta):
    return {"snapshot_delta": delta, "snapshot_end": delta, "counted_s": 20.0, "config": loaded["config"],
            "mix": loaded["mix"], "trace": None, "base": loaded["base"]}


@pytest.mark.parametrize("cell", ["epaxos_n5_1m.zipf_sat", "tempo_n5_1m.zipf_open80",
                                  "atlas_n5_4shard_2key.ycsbt_w5_zipf07_sat",
                                  "tempo_n5_4shard_2key.ycsbt_zipf07_sat"])
def test_deltas_without_the_counter_read_nothing_and_with_it_the_hand_computed_value(cell):
    """The driver's traced run of the parent (no counter) leaves the metric
    out and does not raise; a server whose seams were replaced reports 0."""
    loaded = run.load_cell(ROOT, cell)
    (metric,) = [m for m in loaded["per_layer"] if m["name"] in PAIR]
    name = metric["name"]
    assert run.read_metrics([metric], _ctx(loaded, PARENT_DELTA)) == {}
    assert run.read_metrics([metric], _ctx(loaded, CHANGE_DELTA)) == {name: {"value": 100.0, "unit": "%"}}
    assert run.read_metrics([metric], _ctx(loaded, BROKEN_DELTA)) == {name: {"value": 0.0, "unit": "%"}}
    # a quarter of the window under a monitor
    part = {**PARENT_DELTA, "executed": 1000, "executed_in_pass": 750}
    assert run.read_metrics([metric], _ctx(loaded, part))[name]["value"] == pytest.approx(75.0)
    # nothing executed in the window: no share of nothing
    idle = {**CHANGE_DELTA, "executed": 0, "executed_in_pass": 0}
    assert run.read_metrics([metric], _ctx(loaded, idle)) == {}
