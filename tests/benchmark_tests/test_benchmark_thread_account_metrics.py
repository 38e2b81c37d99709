"""The six per-layer metrics of the thread account (PR 36:
`host_cpu_us_per_cmd.*`, `stage_wait_us_per_cmd.*`, `loop_stopped_ms.*`):
data files and appended entries on readers the benchmark had.  Their files
say what their entries say and stand after every entry the benchmark had,
every cell that reports what they move reports them (a later PR's cell too:
`root`, `conftest.py`), a snapshot without the counters reads nothing and one
with them the value worked out by hand.

ISSUE 36 named a fourth pair, `runq_wait_share.*` over `host_runq_ms`: the
kernel of the machine the benchmark runs on has no `schedstat`, so the
counter is never there, and a metric without a list of cells has to be
reported wherever what it moves is.  The program publishes the counter where
the kernel has it (`tests/test_thread_account.py`); the reader it would use is
held here to that counter, so that a later `benchmark` PR adds two data files."""

import os

import pytest

from benchmark import run
from tests.benchmark_tests import contract_rules as rules

ROOT = run.ROOT
BASE = os.path.join(ROOT, "benchmark")
SIX = [base + kind
       for base in ("host_cpu_us_per_cmd", "stage_wait_us_per_cmd", "loop_stopped_ms")
       for kind in (".open", ".sat")]
# what the benchmark's last entry was before them (PR 34's)
LAST_BEFORE = "own_key_share.sat"
READERS = {"host_cpu_us_per_cmd": "snapshot_ratio", "stage_wait_us_per_cmd": "snapshot_ratio",
           "loop_stopped_ms": "snapshot_delta"}
UNITS = {"host_cpu_us_per_cmd": "us", "stage_wait_us_per_cmd": "us", "loop_stopped_ms": "ms"}


@pytest.mark.parametrize("name", SIX)
def test_each_of_the_six_has_a_file_that_says_what_its_entry_says_and_stands_last(name):
    spec = rules.bench(ROOT)
    names = [m["name"] for m in spec["per_layer"]]
    entry = spec["per_layer"][names.index(name)]
    own = run._load(os.path.join(BASE, "layer_metrics", name + ".json"))
    assert {key: own[key] for key in entry} == entry and "workloads" not in entry
    assert set(own) == set(entry) | {"reader", "args", "reads"}
    base = name.rsplit(".", 1)[0]
    assert own["reader"] == READERS[base] and entry["unit"] == UNITS[base]
    assert entry["better"] == "lower" and entry["source"] == "program_counter"
    assert entry["moves"] == ("commit_p50_ms" if name.endswith(".open") else "goodput_cmds_s")
    # no benchmark code came with them: the reader is one an older metric uses
    assert own["reader"] in {run._load(os.path.join(BASE, "layer_metrics", m["name"] + ".json"))["reader"]
                             for m in spec["per_layer"] if m["name"] not in SIX}
    assert len(own["reads"]) > 80
    # the event loop's layer, letter for letter as `loop_stall_ms.*` names it
    assert entry["layer"] == spec["per_layer"][names.index("loop_stall_ms.sat")]["layer"]
    # appended: after every entry the benchmark had, in the issue's order
    assert names.index(name) > names.index(LAST_BEFORE)
    assert [n for n in names if n in SIX] == SIX
    assert names[names.index(LAST_BEFORE) + 1:][:6] == SIX


def test_every_cell_that_reports_what_they_move_reports_them(root):
    """No list of cells: the open cells carry the three `.open`, the saturated
    ones, the four-chip cell among them, the three `.sat`."""
    spec = rules.bench(root)
    seen = set()
    for cell in rules.cells(root):
        reported = {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}
        carried = {m["name"] for m in run.load_cell(root, cell)["per_layer"]}
        kind = ".open" if "commit_p50_ms" in reported else ".sat"
        assert carried & set(SIX) == {name for name in SIX if name.endswith(kind)}
        seen.add(kind)
    assert seen == {".open", ".sat"}


# the growth of a server's counters over 16 s in which it executed 480,000 commands
DELTA = {"executed": 480000, "host_cpu_ms": 14400.0, "stage_wait_ms": 3360.0, "host_runq_ms": 96.0,
         "loop_stopped_ms": 0.0, "loop_stall_ms": 10850.5}


@pytest.mark.parametrize("cell", ["epaxos_n5_1m.zipf_open80", "epaxos_n5_1m.zipf_sat",
                                  "tempo_n5_4shard_2key.ycsbt_zipf07_sat"])
def test_a_server_without_the_counters_reads_nothing_and_one_with_them_the_hand_computed_value(cell):
    """The driver's traced run of the parent (no account, no classes) leaves
    all three out and does not raise."""
    loaded = run.load_cell(ROOT, cell)
    three = [m for m in loaded["per_layer"] if m["name"] in SIX]
    assert len(three) == 3
    kind = three[0]["name"].rsplit(".", 1)[1]
    ctx = {"snapshot_delta": {"executed": 480000, "loop_stall_ms": 10850.5, "stage_step_cpu_ms": 7000.0},
           "snapshot_end": {}, "counted_s": 16.0, "config": loaded["config"], "mix": loaded["mix"],
           "trace": None, "base": loaded["base"]}
    assert run.read_metrics(three, ctx) == {}
    ctx["snapshot_delta"] = dict(DELTA)
    expected = {
        f"host_cpu_us_per_cmd.{kind}": {"value": pytest.approx(30.0), "unit": "us"},  # 14.4e6 us / 480k
        f"stage_wait_us_per_cmd.{kind}": {"value": pytest.approx(7.0), "unit": "us"},
        f"loop_stopped_ms.{kind}": {"value": 0.0, "unit": "ms"},
    }
    assert run.read_metrics(three, ctx) == expected
    # what a run-queue share would read through the reader `gc_share.*` uses: 96 ms of wait
    # over two threads and 16,000 ms is 0.3% a thread; nothing where the kernel gives none
    read = run._module(BASE, "readers", "snapshot_window_share").read
    assert read(ctx, key="host_runq_ms", scale=50.0) == pytest.approx(0.3)
    del ctx["snapshot_delta"]["host_runq_ms"]
    assert read(ctx, key="host_runq_ms", scale=50.0) is None
    # a stop of 310 ms is read whole, whatever the 10.8 s of long turns beside it
    ctx["snapshot_delta"]["loop_stopped_ms"] = 310.25
    assert run.read_metrics(three, ctx)[f"loop_stopped_ms.{kind}"]["value"] == 310.25


def test_no_executed_command_reads_no_cost_per_command():
    loaded = run.load_cell(ROOT, "epaxos_n5_1m.zipf_sat")
    two = [m for m in loaded["per_layer"] if m["name"] in SIX and "per_cmd" in m["name"]]
    ctx = {"snapshot_delta": {**DELTA, "executed": 0}, "snapshot_end": {}, "counted_s": 16.0,
           "config": loaded["config"], "mix": loaded["mix"], "trace": None, "base": loaded["base"]}
    assert len(two) == 2 and run.read_metrics(two, ctx) == {}
