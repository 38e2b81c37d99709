"""The four per-layer metrics of the collect stage (PR 52: `collect_us_per_cmd`
and `cmds_per_collect_slice`, each `.sat` / `.open`): data files and appended
entries on a reader the benchmark had.  Their files say what their entries say
and stand right after the entry the benchmark ended with before them, in the
issue's order (a later PR's stand after them: nothing here is held to the end
of the list); every cell that reports what they move reports its two; the
window's counter deltas of a server with the counters read the hand-computed
values, those of a parent the cost alone (both of its counters are older than
PR 52) and not the indicator."""

import os

import pytest

from benchmark import run
from tests.benchmark_tests import contract_rules as rules

ROOT = run.ROOT
BASE = os.path.join(ROOT, "benchmark")
INGEST = "ingest / batch assembly (run/ingest.py, _assemble_rows)"
# base name -> (unit, better, source, args, an accepted metric of the layer on the same reader)
PAIRS = {
    "collect_us_per_cmd": ("us", "lower", "program_span",
                           {"num": ["stage_collect_ms"], "den": ["queue_released"], "scale": 1000.0},
                           "assemble_us_per_cmd"),
    "cmds_per_collect_slice": ("cmds/slice", "higher", "program_counter",
                               {"num": ["queue_released"], "den": ["collect_slices"]}, "round_fill"),
}
FOUR = [base + kind for base in PAIRS for kind in (".sat", ".open")]
# what the benchmark's last entry was before them (PR 51's)
LAST_BEFORE = "rounds_per_dispatch.sat"


@pytest.mark.parametrize("name", FOUR)
def test_each_of_the_four_has_a_file_that_says_what_its_entry_says_and_names_a_reader_that_exists(name):
    spec = rules.bench(ROOT)
    names = [m["name"] for m in spec["per_layer"]]
    entry = spec["per_layer"][names.index(name)]
    own = run._load(os.path.join(BASE, "layer_metrics", name + ".json"))
    base, kind = name.rsplit(".", 1)
    unit, better, source, args, sibling = PAIRS[base]
    of_layer = run._load(os.path.join(BASE, "layer_metrics", f"{sibling}.{kind}.json"))
    assert {key: own[key] for key in entry} == entry and "workloads" not in entry
    assert set(own) == set(entry) | {"reader", "args", "reads"}
    # no benchmark code came with them: the reader an accepted metric of the layer reads through
    assert own["reader"] == of_layer["reader"] == "snapshot_ratio" and own["args"] == args
    assert os.path.exists(os.path.join(BASE, "readers", "snapshot_ratio.py"))
    assert entry["layer"] == of_layer["layer"] == INGEST
    assert (entry["unit"], entry["better"], entry["source"]) == (unit, better, source)
    assert entry["moves"] == of_layer["moves"] == ("commit_p50_ms" if kind == "open" else "goodput_cmds_s")
    assert len(own["reads"]) > 80 and "PR 52" in own["reads"]
    # appended: right after the entry the benchmark ended with, in the issue's order
    assert names[names.index(LAST_BEFORE) + 1:][:4] == FOUR
    assert len(names) >= 107


def test_the_indicator_says_that_it_is_one_and_the_cost_that_a_parent_reads_it():
    for kind in ("sat", "open"):
        slices = run._load(os.path.join(BASE, "layer_metrics", f"cmds_per_collect_slice.{kind}.json"))
        assert "indicator" in slices["reads"] and "reads nothing" in slices["reads"]
        cost = run._load(os.path.join(BASE, "layer_metrics", f"collect_us_per_cmd.{kind}.json"))
        assert "reads on a parent too" in cost["reads"]


def test_every_cell_that_reports_what_they_move_reports_its_two(root):
    """No list of cells: the open cells carry the two `.open`, the saturated
    ones, the four-chip cell among them, the two `.sat`."""
    spec = rules.bench(root)
    seen = set()
    for cell in rules.cells(root):
        reported = {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}
        carried = {m["name"] for m in run.load_cell(root, cell)["per_layer"]}
        kind = ".open" if "commit_p50_ms" in reported else ".sat"
        assert carried & set(FOUR) == {name for name in FOUR if name.endswith(kind)}
        seen.add(kind)
    assert seen == {".open", ".sat"}


# The growth of a server's counters over 20 s of a saturated window, as `run_cell` takes it from
# the snapshots at its two ends: the parent's has the span's sum and the released commands and
# no count of slices.
PARENT_DELTA = {"executed": 1_400_000, "queue_released": 1_400_000, "stage_collect_ms": 1470.0,
                "stage_collect_n": 400, "queue_wait_ms": 4.2e7}
CHANGE_DELTA = {**PARENT_DELTA, "stage_collect_ms": 126.0, "collect_slices": 2500}
EXPECTED_ON_PARENT = {"collect_us_per_cmd": 1.05}       # 1470 ms x 1000 / 1.4M
EXPECTED = {"collect_us_per_cmd": 0.09,                 # 126 ms x 1000 / 1.4M
            "cmds_per_collect_slice": 560.0}            # 1.4M / 2500


def _ctx(loaded, delta, counted_s=20.0):
    return {"snapshot_delta": delta, "snapshot_end": delta, "counted_s": counted_s, "config": loaded["config"],
            "mix": loaded["mix"], "trace": None, "base": loaded["base"]}


@pytest.mark.parametrize("cell", ["epaxos_n5_1m.zipf_sat", "epaxos_n5_1m.zipf_open80", "tempo_n5_1m.zipf_open80",
                                  "fpaxos_n5_1m.zipf_sat", "caesar_n7_1m.hot50_sat",
                                  "atlas_n5_4shard_2key_5site.ycsbt_w50_zipf07_5site_sat",
                                  "tempo_n5_4shard_2key.ycsbt_zipf07_sat"])
def test_deltas_with_the_counters_read_the_hand_computed_values_and_a_parents_the_cost_alone(cell):
    """The driver's traced run of the parent (no count of slices) leaves the
    indicator out and does not raise."""
    loaded = run.load_cell(ROOT, cell)
    metrics = [m for m in loaded["per_layer"] if m["name"] in FOUR]
    kind = metrics[0]["name"].rsplit(".", 1)[1]
    assert [m["name"] for m in metrics] == [base + "." + kind for base in PAIRS]
    on_parent = run.read_metrics(metrics, _ctx(loaded, PARENT_DELTA))
    assert {name: m["value"] for name, m in on_parent.items()} == pytest.approx(
        {base + "." + kind: value for base, value in EXPECTED_ON_PARENT.items()})
    got = run.read_metrics(metrics, _ctx(loaded, CHANGE_DELTA))
    assert {name: m["value"] for name, m in got.items()} == pytest.approx(
        {base + "." + kind: value for base, value in EXPECTED.items()})
    assert {name: m["unit"] for name, m in got.items()} == {m["name"]: m["unit"] for m in metrics}
    # ratios of two counters of the same stretch: a traced run's shorter stretch reads the same
    assert run.read_metrics(metrics, _ctx(loaded, CHANGE_DELTA, counted_s=16.0)) == got
    # nothing released in the window: no ratio of nothing
    idle = {**CHANGE_DELTA, "queue_released": 0, "collect_slices": 0}
    assert run.read_metrics(metrics, _ctx(loaded, idle)) == {}


def test_the_servers_snapshot_carries_the_counters_the_files_read():
    """The names the files read are the names the program publishes, and the
    count of slices moves where the released commands move."""
    import inspect

    from fantoch_tpu.observability import device
    from fantoch_tpu.run import device_runner

    recorder = inspect.getsource(device.StageRecorder.counters)
    assert 'out[f"stage_{name}_ms"]' in recorder and "collect" in device.ROUND_STAGES
    runtime = inspect.getsource(device_runner.DeviceRuntime._publish_tallies)
    assert '"queue_released": self._queue_released' in runtime
    assert '"collect_slices": self._collect_slices' in runtime
    collect = inspect.getsource(device_runner.DeviceRuntime._collect)
    assert "self._queue_released += released" in collect and "self._collect_slices += slices" in collect
    read = {key for base in PAIRS for part in ("num", "den") for key in PAIRS[base][3][part]}
    assert read == {"stage_collect_ms", "queue_released", "collect_slices"}
