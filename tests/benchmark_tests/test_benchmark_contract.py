"""BENCHMARK.json against the contract's form, and against the files it
names.  The rules are functions of a tree (``contract_rules.py``): here they
are applied to the tree itself, and to a copy of it for each cell
``next_cell.py`` describes, added there as new files and appended list
entries only."""

import json
import os

import pytest

from benchmark import run
from tests.benchmark_tests import contract_rules as rules
from tests.benchmark_tests import next_cell

ROOT = run.ROOT


def test_the_file_has_the_contracts_keys_and_forms():
    rules.the_file_has_the_contracts_keys_and_forms(ROOT)


@pytest.mark.parametrize("workload", rules.cells(ROOT))
def test_every_cell_finds_its_files_by_name(workload):
    rules.a_cell_finds_its_files_by_name(ROOT, workload)


def test_configurations_run_at_the_programs_defaults_and_mark_what_they_assume():
    rules.configurations_pass_their_shape_and_mark_what_they_assume(ROOT)


def test_per_layer_metrics_follow_what_they_move_and_list_no_cells():
    """(The name is the test's first: a metric may list cells since PR 26,
    where its mechanism lives in some cells only, and then follows its list.)"""
    rules.per_layer_metrics_follow_what_they_move(ROOT)


def test_the_harness_holds_no_cell_protocol_or_metric_name():
    rules.the_harness_holds_no_cell_protocol_or_metric_name(ROOT)


def _files(root):
    return {path: open(path, "rb").read()
            for folder, _, files in os.walk(root) if "__pycache__" not in folder
            for path in (os.path.join(folder, name) for name in files)
            if not os.path.islink(path)}


def _the_sharded_cell(root, loaded):
    """Four chips, `--shard-count 4 --device-key-width 2`, a `kv_multi` mix,
    a per-layer metric of its own that is reported in that cell alone."""
    assert loaded["chips"] == 4 and loaded["mix"]["generator"] == "kv_multi"
    flags = rules.flags_of(loaded["config"])
    assert flags["--shard-count"] == "4" and flags["--device-key-width"] == "2"
    assert next_cell.METRIC in {m["name"] for m in loaded["per_layer"]}
    for other in rules.cells(ROOT):
        assert next_cell.METRIC not in {m["name"] for m in run.load_cell(root, other)["per_layer"]}


def _the_conflict_cell(root, loaded):
    """One chip, the tree's own EPaxos configuration as it is, the saturated
    zipf mix with another key generator; it reports what that cell reports."""
    beside = run.load_cell(root, "epaxos_n5_1m.zipf_sat")
    assert loaded["chips"] == 1 and loaded["config"] == beside["config"]
    assert loaded["mix"]["key_gen"] == {"kind": "conflict_rate", "rate": 50}
    assert "key_gen.rate" in loaded["mix"]["assumed"]
    for key in set(beside["mix"]) - {"key_gen", "assumed", "note"}:
        assert loaded["mix"][key] == beside["mix"][key], key
    for section in ("end_to_end", "per_layer"):
        assert [m["name"] for m in loaded[section]] == [m["name"] for m in beside[section]]


@pytest.mark.parametrize("cell,added,its_own", [
    (next_cell.CELL, {"configs": 1, "workloads": 1, "per_layer": 1, "files": 3}, _the_sharded_cell),
    (next_cell.CONFLICT_CELL, {"configs": 0, "workloads": 1, "per_layer": 0, "files": 1},
     _the_conflict_cell),
], ids=[next_cell.CELL, next_cell.CONFLICT_CELL])
def test_the_rules_take_the_next_prs_four_chip_sharded_two_key_cell(tmp_path, cell, added, its_own):
    """A cell `PERF.md` s7 keeps in line, as the PR that adds it will (the
    name is the test's first: PR 26's sharded cell was its one case, s7's #1
    of today, `epaxos_n5_1m.conflict50_sat`, is its second).  Every rule and
    everything held by name holds for the tree with it, its name is on
    `goodput_cmds_s`, and no file that was there is changed."""
    root = next_cell.copy_tree(str(tmp_path))
    before = _files(root)
    spec_before = rules.bench(root)
    assert next_cell.CELLS[cell](root) == cell
    for rule in rules.RULES + rules.HELD_BY_NAME:
        rule(root)
    for workload in rules.cells(root):
        rules.a_cell_finds_its_files_by_name(root, workload)
    assert rules.cells(root) == rules.cells(ROOT) + [cell]
    loaded = run.load_cell(root, cell)
    assert "goodput_cmds_s" in {m["name"] for m in loaded["end_to_end"]}
    its_own(root, loaded)
    # new files and appended entries only
    after = _files(root)
    changed = {path for path, content in before.items() if after.get(path) != content}
    assert changed == {os.path.join(root, "BENCHMARK.json")}
    assert len(after) == len(before) + added["files"]
    spec = rules.bench(root)
    for section in ("configs", "workloads", "per_layer"):
        kept = len(spec_before[section])
        assert spec[section][:kept] == spec_before[section]
        assert len(spec[section]) == kept + added[section]
    for now, then in zip(spec["end_to_end"], spec_before["end_to_end"]):
        assert {**now, "workloads": None} == {**then, "workloads": None}
        assert now.get("workloads", [])[: len(then.get("workloads", []))] == then.get("workloads", [])


@pytest.mark.parametrize("breach,rule", [
    # a tuning flag pinned in a configuration
    (lambda config, mix, spec: config["server_flags"].extend(["--serving-chain-max", "1"]),
     rules.configurations_pass_their_shape_and_mark_what_they_assume),
    # a shape flag that says another thing than the deployment
    (lambda config, mix, spec: config["deployment"].update(shards=2),
     rules.configurations_pass_their_shape_and_mark_what_they_assume),
    # a deployment over shards whose flags leave the shards out
    (lambda config, mix, spec: config.update(server_flags=[
        w for w in config["server_flags"] if w not in ("--shard-count", "4")]),
     rules.configurations_pass_their_shape_and_mark_what_they_assume),
    # a mix of one key a command under a deployment of two
    (lambda config, mix, spec: mix.update(keys_per_command=1),
     rules.configurations_pass_their_shape_and_mark_what_they_assume),
    # four chips in the cell, one in the deployment
    (lambda config, mix, spec: config["deployment"].update(chips=1),
     lambda root: rules.a_cell_finds_its_files_by_name(root, next_cell.CELL)),
    # the batch the metrics divide by is not the batch the flags pass
    (lambda config, mix, spec: config.update(device_batch=2048),
     lambda root: rules.a_cell_finds_its_files_by_name(root, next_cell.CELL)),
    # a per-layer metric that lists a cell which does not report what it moves
    (lambda config, mix, spec: spec["per_layer"][-1]["workloads"].append(spec["workloads"][0]["name"]),
     rules.the_file_has_the_contracts_keys_and_forms),
    # three cells of five on four chips
    (lambda config, mix, spec: [cell.update(chips=4) for cell in spec["workloads"][:2]],
     rules.the_file_has_the_contracts_keys_and_forms),
], ids=["tuning_flag", "flag_against_deployment", "shards_without_their_flag", "mix_against_deployment",
        "chips_against_deployment", "batch_against_flag", "metric_lists_a_cell_it_cannot_move",
        "too_many_four_chip_cells"])
def test_the_rules_refuse_what_they_are_there_to_refuse(tmp_path, breach, rule):
    root = next_cell.copy_tree(str(tmp_path))
    next_cell.add_next_cell(root)
    paths = {"config": os.path.join(root, "benchmark", "configs", next_cell.CONFIG + ".json"),
             "mix": os.path.join(root, "benchmark", "traffic", next_cell.TRAFFIC + ".json"),
             "spec": os.path.join(root, "BENCHMARK.json")}
    parts = {name: run._load(path) for name, path in paths.items()}
    rule(root)  # holds before the breach
    breach(parts["config"], parts["mix"], parts["spec"])
    for name, path in paths.items():
        with open(path, "w") as fh:
            json.dump(parts[name], fh)
    with pytest.raises(AssertionError):
        rule(root)
