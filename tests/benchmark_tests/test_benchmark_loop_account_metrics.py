"""The eleven per-layer metrics of the loop's thread's account (PR 51: five
pairs `.sat` / `.open` and `rounds_per_dispatch.sat`): data files and appended
entries on readers the benchmark had.  Their files say what their entries say
and stand after every entry the benchmark had, in the issue's order; each names
a reader that exists; every cell that reports what they move reports its ones
(they have no list of cells); the window's counter deltas of a server with the
counters read the hand-computed value, those of a server without them (the
parent; a runtime on a loop that was not made over the selector) nothing."""

import os

import pytest

from benchmark import run
from tests.benchmark_tests import contract_rules as rules

ROOT = run.ROOT
BASE = os.path.join(ROOT, "benchmark")
LOOP = "event loop (DeviceRuntime._driver_task, run_in_executor)"
STEP = "dispatch / fetch / drain (run/pipeline.py PipelineCore)"
INGEST = "ingest / batch assembly (run/ingest.py, _assemble_rows)"
# base name -> (layer, unit, reader, args, an accepted metric of the same layer on the same reader)
PAIRS = {
    "loop_busy_share": (LOOP, "%", "snapshot_window_share", {"key": "loop_busy_ms", "scale": 100.0}, "gc_share"),
    "loop_turn_ms": (LOOP, "ms", "snapshot_ratio", {"num": ["loop_busy_ms"], "den": ["loop_turns"]},
                     "host_cpu_us_per_cmd"),
    "loop_lock_wait_share": (LOOP, "%", "snapshot_window_share", {"key": "loop_poll_ready_ms", "scale": 100.0},
                             "gc_share"),
    "loop_unnamed_us_per_cmd": (LOOP, "us", "snapshot_ratio",
                                {"num": ["loop_unnamed_ms"], "den": ["executed"], "scale": 1000.0},
                                "host_cpu_us_per_cmd"),
    "step_unnamed_us_per_cmd": (STEP, "us", "snapshot_ratio",
                                {"num": ["step_unnamed_ms"], "den": ["executed"], "scale": 1000.0},
                                "execute_us_per_cmd"),
}
ELEVEN = [base + kind for base in PAIRS for kind in (".sat", ".open")] + ["rounds_per_dispatch.sat"]
# what the benchmark's last entry was before them (PR 50's)
LAST_BEFORE = "wire_ops_share.open"


def _spec_of(name):
    if name == "rounds_per_dispatch.sat":
        return INGEST, "rounds", "snapshot_ratio", {"num": ["rounds"], "den": ["stage_collect_n"]}, "round_fill"
    return PAIRS[name.rsplit(".", 1)[0]]


@pytest.mark.parametrize("name", ELEVEN)
def test_each_of_the_eleven_has_a_file_that_says_what_its_entry_says_and_names_a_reader_that_exists(name):
    spec = rules.bench(ROOT)
    names = [m["name"] for m in spec["per_layer"]]
    entry = spec["per_layer"][names.index(name)]
    own = run._load(os.path.join(BASE, "layer_metrics", name + ".json"))
    layer, unit, reader, args, sibling = _spec_of(name)
    kind = name.rsplit(".", 1)[1]
    of_layer = run._load(os.path.join(BASE, "layer_metrics", f"{sibling}.{kind}.json"))
    assert {key: own[key] for key in entry} == entry and "workloads" not in entry
    assert set(own) == set(entry) | {"reader", "args", "reads"}
    # no benchmark code came with them: a reader an accepted metric of the layer reads through
    assert own["reader"] == of_layer["reader"] == reader and own["args"] == args
    assert os.path.exists(os.path.join(BASE, "readers", reader + ".py"))
    assert (entry["layer"], of_layer["layer"]) == (layer, layer)
    assert (entry["unit"], entry["better"], entry["source"]) == (unit, "lower", "program_counter")
    assert entry["moves"] == of_layer["moves"] == ("commit_p50_ms" if kind == "open" else "goodput_cmds_s")
    assert len(own["reads"]) > 80 and "PR 51" in own["reads"]
    # appended: after every entry the benchmark had, in the issue's order (and a later PR's after them)
    assert names[names.index(LAST_BEFORE) + 1:][:11] == ELEVEN
    assert len(names) >= 103


def test_the_indicator_says_that_it_is_one():
    own = run._load(os.path.join(BASE, "layer_metrics", "rounds_per_dispatch.sat.json"))
    assert "indicator" in own["reads"] and "round_fill.sat" in own["reads"]


def test_every_cell_that_reports_what_they_move_reports_its_ones(root):
    """No list of cells: the open cells carry the five `.open`, the saturated
    ones, the four-chip cell among them, the five `.sat` and the indicator."""
    spec = rules.bench(root)
    seen = set()
    for cell in rules.cells(root):
        reported = {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}
        carried = {m["name"] for m in run.load_cell(root, cell)["per_layer"]}
        kind = ".open" if "commit_p50_ms" in reported else ".sat"
        assert carried & set(ELEVEN) == {name for name in ELEVEN if name.endswith(kind)}
        seen.add(kind)
    assert seen == {".open", ".sat"}


# The growth of a server's counters over 20 s of a saturated window, as `run_cell` takes it from
# the snapshots at its two ends. The parent's has none of PR 51's counters; a runtime on a loop
# that was not made over the selector (the tests' harness) has the step's and the chain's, and
# not the loop's six.
PARENT_DELTA = {"executed": 1_400_000, "rounds": 500, "device_dispatches": 500, "stage_collect_n": 400,
                "stage_step_ms": 9000.0, "stage_gc_ms": 280.0}
PLAIN_LOOP_DELTA = {**PARENT_DELTA, "step_unnamed_ms": 700.0, "stage_read_ms": 5100.0, "chain_adjustments": 0}
CHANGE_DELTA = {**PLAIN_LOOP_DELTA, "loop_turns": 2500, "loop_busy_ms": 17000.0, "loop_poll_wait_ms": 1800.0,
                "loop_poll_ready_ms": 1200.0, "loop_poll_ready_n": 2300, "loop_unnamed_ms": 4200.0}
EXPECTED = {
    "loop_busy_share": 85.0,          # 17000 ms of 20000
    "loop_turn_ms": 6.8,              # 17000 / 2500
    "loop_lock_wait_share": 6.0,      # 1200 ms of 20000
    "loop_unnamed_us_per_cmd": 3.0,   # 4200 ms x 1000 / 1.4M
    "step_unnamed_us_per_cmd": 0.5,   # 700 ms x 1000 / 1.4M
    "rounds_per_dispatch": 1.25,      # 500 rounds in 400 calls of serve (and 500 device dispatches: no fusing)
}


def _ctx(loaded, delta, counted_s=20.0):
    return {"snapshot_delta": delta, "snapshot_end": delta, "counted_s": counted_s, "config": loaded["config"],
            "mix": loaded["mix"], "trace": None, "base": loaded["base"]}


@pytest.mark.parametrize("cell", ["epaxos_n5_1m.zipf_sat", "epaxos_n5_1m.zipf_open80", "tempo_n5_1m.zipf_open80",
                                  "fpaxos_n5_1m.zipf_sat", "caesar_n7_1m.hot50_sat",
                                  "atlas_n5_4shard_2key_5site.ycsbt_w50_zipf07_5site_sat",
                                  "tempo_n5_4shard_2key.ycsbt_zipf07_sat"])
def test_deltas_with_the_counters_read_the_hand_computed_values_and_without_them_nothing(cell):
    """The driver's traced run of the parent (no counter) leaves the metrics
    out and does not raise; a runtime on a plain loop reports the step's and
    the indicator alone."""
    loaded = run.load_cell(ROOT, cell)
    metrics = [m for m in loaded["per_layer"] if m["name"] in ELEVEN]
    kind = metrics[0]["name"].rsplit(".", 1)[1]
    assert len(metrics) == (5 if kind == "open" else 6)
    # the parent has rounds and collect's count: the indicator reads on it too, and nothing else does
    on_parent = run.read_metrics(metrics, _ctx(loaded, PARENT_DELTA))
    assert set(on_parent) == ({"rounds_per_dispatch.sat"} if kind == "sat" else set())
    on_plain = run.read_metrics(metrics, _ctx(loaded, PLAIN_LOOP_DELTA))
    assert set(on_plain) == set(on_parent) | {"step_unnamed_us_per_cmd." + kind}
    got = run.read_metrics(metrics, _ctx(loaded, CHANGE_DELTA))
    assert {name: m["value"] for name, m in got.items()} == pytest.approx(
        {m["name"]: EXPECTED[m["name"].rsplit(".", 1)[0]] for m in metrics})
    assert {name: m["unit"] for name, m in got.items()} == {m["name"]: m["unit"] for m in metrics}
    # a traced run's deltas cover the 16 s before its capture: the shares are of that stretch
    traced = run.read_metrics(metrics, _ctx(loaded, CHANGE_DELTA, counted_s=16.0))
    assert traced["loop_busy_share." + kind]["value"] == pytest.approx(106.25)
    assert traced["loop_turn_ms." + kind]["value"] == pytest.approx(6.8)
    # nothing executed, no turn, no dispatch in the window: no ratio of nothing
    idle = {**CHANGE_DELTA, "executed": 0, "loop_turns": 0, "stage_collect_n": 0}
    assert set(run.read_metrics(metrics, _ctx(loaded, idle))) == {
        "loop_busy_share." + kind, "loop_lock_wait_share." + kind}


def test_the_servers_snapshot_carries_the_counters_the_files_read():
    """The names the files read are the names the program publishes."""
    import inspect

    from fantoch_tpu.observability import device
    from fantoch_tpu.run import device_runner

    recorder = inspect.getsource(device.StageRecorder.counters)
    for key in ("loop_turns", "loop_busy_ms", "loop_poll_wait_ms", "loop_poll_ready_ms", "loop_poll_ready_n",
                "loop_unnamed_ms", "step_unnamed_ms"):
        assert f'out["{key}"]' in recorder, key
    runtime = inspect.getsource(device_runner.DeviceRuntime._publish_tallies)
    assert "**self.stages.counters()" in runtime and '"rounds": d.rounds' in runtime and '"executed": d.executed' in runtime
    assert '"serving_chain": self._chain_tuner.chain' in runtime
    assert '"chain_adjustments": self._chain_tuner.adjustments' in runtime
    # a stage's count is the recorder's, for every stage declared up front
    assert 'out[f"stage_{name}_n"]' in recorder and "collect" in device.ROUND_STAGES
    read = {key for name in ELEVEN for part in ("num", "den", "key")
            for key in [_spec_of(name)[3].get(part, [])] for key in ([key] if isinstance(key, str) else key)}
    assert read == {"loop_busy_ms", "loop_turns", "loop_poll_ready_ms", "loop_unnamed_ms", "step_unnamed_ms",
                    "executed", "rounds", "stage_collect_n"}
