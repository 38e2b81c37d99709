"""BENCHMARK.json against the contract's form, and against the files it names."""

import json
import os
import re

import pytest

from benchmark import run

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def cells():
    return [cell["name"] for cell in bench()["workloads"]]


def test_the_file_has_the_contracts_keys_and_forms():
    spec = bench()
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= spec["run_seconds"] <= 51 and isinstance(spec["run_seconds"], int)
    assert all(os.path.isdir(os.path.join(ROOT, path)) for path in spec["paths"])
    assert any(word.startswith(spec["paths"][0] + "/") for word in spec["command"])
    for entry in spec["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(entry["name"]) and 1 <= len(entry["source"]) <= 200
        assert entry["file"].startswith(spec["paths"][0] + "/")
        with open(os.path.join(ROOT, entry["file"])) as fh:
            config = json.load(fh)
        assert config["source"] == entry["source"] and config["reduced"] == entry["reduced"]
        assert config["guarantees"] and config["assumed"]
    assert len({entry["file"] for entry in spec["configs"]}) == len(spec["configs"])
    for cell in spec["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
        assert cell["config"] in {entry["name"] for entry in spec["configs"]}
    pairs = [(cell["config"], cell["traffic"]) for cell in spec["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(cell["chips"] == 4 for cell in spec["workloads"]) <= max(1, len(pairs) // 2)
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(set(names)) == len(names)
    for metric in spec["end_to_end"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert metric["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= metric["bound"] <= 0.25
    assert any(m["name"] == "setup_s" and "workloads" not in m for m in spec["end_to_end"])
    for metric in spec["per_layer"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert metric["source"] in SOURCES and 1 <= len(metric["layer"]) <= 200
        moved = next(m for m in spec["end_to_end"] if m["name"] == metric["moves"])
        for cell in metric.get("workloads", []):
            assert cell in moved.get("workloads", cells())
    for metric in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
        assert metric["better"] in ("lower", "higher")
        assert set(metric.get("workloads", [])) <= set(cells())


@pytest.mark.parametrize("workload", cells())
def test_every_cell_finds_its_files_by_name(workload):
    cell = run.load_cell(ROOT, workload)
    assert cell["chips"] == 1
    assert cell["config"]["device_batch"] == 4096
    assert "--device-step" not in cell["config"]["server_flags"]  # the harness adds it
    assert cell["mix"]["loop"] in ("open", "closed")
    if cell["mix"]["loop"] == "open":
        assert cell["mix"]["rate_per_s"] > 0  # the cell's own number, 0.8 of its knee
        assert cell["mix"]["rate_per_s"] == pytest.approx(0.8 * cell["mix"]["knee_per_s"])
    end_to_end = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in end_to_end and len(end_to_end) >= 2 and cell["per_layer"]
    for metric in cell["end_to_end"] + cell["per_layer"]:
        assert os.path.exists(os.path.join(cell["base"], "readers", metric["reader"] + ".py"))
    assert {m["moves"] for m in cell["per_layer"]} <= end_to_end


SHAPE_FLAGS = {"--protocol", "-n", "-f", "--device-key-buckets", "--device-batch",
               "--device-pending"}


def test_configurations_run_at_the_programs_defaults_and_mark_what_they_assume():
    """A configuration passes the deployment's shape and nothing else (no
    tuning flag pinned around a fault of the program), and whatever the cited
    source does not bear out is named under ``assumed``, in the mix too."""
    spec = bench()
    for entry in spec["configs"]:
        config = run._load(os.path.join(ROOT, entry["file"]))
        flags = {word for word in config["server_flags"] if word.startswith("-")}
        assert flags == SHAPE_FLAGS, flags
        assert {"f", "keys_per_command", "payload_bytes", "write_share"} <= set(config["assumed"])
        assert "f=1" not in entry["source"] and "1 key" not in entry["source"]
    for traffic in {cell["traffic"] for cell in spec["workloads"]}:
        mix = run._load(os.path.join(ROOT, spec["paths"][0], "traffic", traffic + ".json"))
        assert {"key_gen.coefficient", "clients", "read_share"} <= set(mix["assumed"])
        if mix["loop"] == "open":
            assert "arrivals" in mix["assumed"]


def test_per_layer_metrics_follow_what_they_move_and_list_no_cells():
    """A later cell is traced without an edit to any list: a per-layer metric
    is reported wherever the end-to-end metric it moves is."""
    spec = bench()
    assert all("workloads" not in metric for metric in spec["per_layer"])
    for cell in cells():
        reported = {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}
        expected = {m["name"] for m in spec["per_layer"] if m["moves"] in reported}
        assert {m["name"] for m in run.load_cell(ROOT, cell)["per_layer"]} == expected
        assert expected  # every cell reports at least one per-layer metric
    for metric in spec["per_layer"]:  # the metric's own file says the same as its entry
        own = run._load(os.path.join(ROOT, spec["paths"][0], "layer_metrics", metric["name"] + ".json"))
        assert {key: own[key] for key in metric} == metric, metric["name"]
        assert own["reads"] and own["reader"]


def test_the_harness_holds_no_cell_protocol_or_metric_name():
    with open(os.path.join(ROOT, "benchmark", "run.py")) as fh:
        source = fh.read()
    spec = bench()
    for word in [m["name"] for m in spec["end_to_end"] + spec["per_layer"]] + cells() + \
            [c["name"] for c in spec["configs"]] + ["epaxos", "newt", "tempo", "zipf"]:
        assert word not in source, word
