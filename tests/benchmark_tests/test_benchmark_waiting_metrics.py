"""The six per-layer metrics that waited for room at the end of `per_layer`
(`frames_per_read.*` since PR 28, `gc_share.*` and `gc_unscheduled.*` since
PR 30): the one reader that is new on a hand-made `ctx`, the reader they
share with older metrics held to what it read, their files and entries, and
that every cell which reports what they move reports them, a later PR's cell
too (`root`, `conftest.py`)."""

import os

import pytest

from benchmark import run
from tests.benchmark_tests import contract_rules as rules

ROOT = run.ROOT
BASE = os.path.join(ROOT, "benchmark")
SIX = [base + kind for base in ("frames_per_read", "gc_share", "gc_unscheduled")
       for kind in (".open", ".sat")]


def reader(name):
    return run._module(BASE, "readers", name)


def test_a_counter_of_milliseconds_reads_as_a_share_of_the_stretch_the_deltas_cover():
    read = reader("snapshot_window_share").read
    ctx = {"snapshot_delta": {"stage_gc_ms": 280.0, "rounds": 120}, "counted_s": 20.0}
    assert read(ctx, "stage_gc_ms", scale=100.0) == pytest.approx(1.4)
    assert read(ctx, "stage_gc_ms") == pytest.approx(0.014)
    # a traced run's deltas cover the 16 s before its capture, not the window's 20
    assert read({**ctx, "counted_s": 16.0}, "stage_gc_ms", scale=100.0) == pytest.approx(1.75)
    assert read({**ctx, "snapshot_delta": {"stage_gc_ms": 0.0}}, "stage_gc_ms", scale=100.0) == 0.0
    # a server without the counter, or a stretch of no length, reads nothing
    assert read({**ctx, "snapshot_delta": {"rounds": 120}}, "stage_gc_ms", scale=100.0) is None
    assert read({**ctx, "counted_s": 0.0}, "stage_gc_ms", scale=100.0) is None


@pytest.mark.parametrize("metric,expected", [
    ("round_fill.sat", 100.0 * 61440 / (20 * 4096)),
    ("session_us_per_cmd.sat", 1000.0 * (610.5 + 590.25) / 75000),
    ("replies_per_write.open", 75000 / 80),
    ("slow_path_share.open", 100.0 * 3 / 4),
    ("frames_per_read.sat", 75010 / 79),
])
def test_a_ratio_of_counters_reads_what_it_read(metric, expected):
    """`snapshot_ratio.py` is as it was: its old metrics on a hand-made `ctx`
    read the quotient of their counters' growth, bit for bit."""
    own = run._load(os.path.join(BASE, "layer_metrics", metric + ".json"))
    assert own["reader"] == "snapshot_ratio"
    delta = {"executed": 61440, "rounds": 20, "session_decode_ms": 610.5, "session_admit_ms": 590.25,
             "submitted": 75000, "replied": 75000, "reply_writes": 80, "slow_paths": 3,
             "fast_paths": 1, "session_decoded": 75010, "session_reads": 79}
    ctx = {"snapshot_delta": delta, "config": {"device_batch": 4096}, "counted_s": 20.0}
    read = reader("snapshot_ratio").read
    assert read(ctx, **own["args"]) == expected
    # nothing under the line, or a counter the server does not publish: nothing is read
    below = own["args"]["den"]
    assert read({**ctx, "snapshot_delta": {**delta, **dict.fromkeys(below, 0)}}, **own["args"]) is None
    assert read({**ctx, "snapshot_delta": {k: v for k, v in delta.items() if k != below[0]}},
                **own["args"]) is None


@pytest.mark.parametrize("name", SIX)
def test_each_of_the_six_has_a_file_that_says_what_its_entry_says_and_stands_after_pr27s(name):
    spec = rules.bench(ROOT)
    names = [m["name"] for m in spec["per_layer"]]
    entry = spec["per_layer"][names.index(name)]
    own = run._load(os.path.join(BASE, "layer_metrics", name + ".json"))
    assert {key: own[key] for key in entry} == entry and "workloads" not in entry
    assert entry["moves"] == ("commit_p50_ms" if name.endswith(".open") else "goodput_cmds_s")
    assert os.path.exists(os.path.join(BASE, "readers", own["reader"] + ".py")) and own["reads"]
    # its layer is one an older metric names, letter for letter
    assert entry["layer"] in {m["layer"] for m in spec["per_layer"] if m["name"] not in SIX}
    # appended: after what the benchmark had, in the issue's order among themselves
    assert names.index(name) > max(names.index(older) for older in rules.FOUR_CHIP_FIVE)
    assert [n for n in names if n in SIX] == SIX


def test_every_cell_that_reports_what_they_move_reports_them(root):
    """No list of cells: the open cells carry the three `.open`, the saturated
    ones the three `.sat`, and so does a cell a later PR appends."""
    spec = rules.bench(root)
    for cell in rules.cells(root):
        reported = {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}
        carried = {m["name"] for m in run.load_cell(root, cell)["per_layer"]}
        kind = ".open" if "commit_p50_ms" in reported else ".sat"
        assert carried & set(SIX) == {name for name in SIX if name.endswith(kind)}


@pytest.mark.parametrize("cell", ["epaxos_n5_1m.zipf_open80", "tempo_n5_4shard_2key.ycsbt_zipf07_sat"])
def test_a_server_without_the_counters_reads_nothing_and_one_with_them_reads_all_three(cell):
    """A snapshot of a commit before PR 28 has no `session_reads`, one before
    PR 30 no `stage_gc_ms` and no `gc_full_unscheduled`: the driver's traced
    runs of such a parent leave the metric out and do not raise."""
    loaded = run.load_cell(ROOT, cell)
    six = [m for m in loaded["per_layer"] if m["name"] in SIX]
    kind = six[0]["name"].rsplit(".", 1)[1]
    ctx = {"snapshot_delta": {"session_decoded": 1900, "rounds": 3}, "snapshot_end": {}, "counted_s": 16.0,
           "config": loaded["config"], "mix": loaded["mix"], "trace": None, "base": loaded["base"]}
    assert run.read_metrics(six, ctx) == {}
    ctx["snapshot_delta"].update(session_reads=2, stage_gc_ms=224.0, gc_full_unscheduled=0)
    assert run.read_metrics(six, ctx) == {
        f"frames_per_read.{kind}": {"value": 950.0, "unit": "frames/read"},
        f"gc_share.{kind}": {"value": pytest.approx(1.4), "unit": "%"},
        f"gc_unscheduled.{kind}": {"value": 0.0, "unit": "collections"}}
