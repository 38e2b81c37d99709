"""The benchmark's contract as rules: each is a function of a tree (its
``BENCHMARK.json`` and the files it names) and holds for any later cell, not
for today's five.  What the tests say of an entry the tree has, they say by
its name and never by its place (``HELD_BY_NAME``), so the lists can grow.
The tests apply all of them to the tree itself, and to copies to which a
later PR's cell was added as new files and appended entries (``next_cell.py``)."""

import json
import os
import re

import pytest

from benchmark import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# what a configuration's flags may be: the deployment's shape, every one of
# these, and of the further ones those whose value the deployment states
SHAPE_FLAGS = {"--protocol", "-n", "-f", "--device-key-buckets", "--device-batch",
               "--device-pending"}
FURTHER_SHAPE_FLAGS = {"--shard-count": "shards", "--device-key-width": "keys_per_command"}


def bench(root):
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def cells(root):
    return [cell["name"] for cell in bench(root)["workloads"]]


def flags_of(config):
    """flag -> the word after it, for the words of ``server_flags`` that are flags."""
    words = config["server_flags"]
    return {word: words[at + 1] for at, word in enumerate(words) if word.startswith("-")}


def the_file_has_the_contracts_keys_and_forms(root):
    spec = bench(root)
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= spec["run_seconds"] <= 51 and isinstance(spec["run_seconds"], int)
    assert all(os.path.isdir(os.path.join(root, path)) for path in spec["paths"])
    assert any(word.startswith(spec["paths"][0] + "/") for word in spec["command"])
    for entry in spec["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(entry["name"]) and 1 <= len(entry["source"]) <= 200
        assert entry["file"].startswith(spec["paths"][0] + "/")
        with open(os.path.join(root, entry["file"])) as fh:
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
            assert cell in moved.get("workloads", cells(root))
    for metric in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
        assert metric["better"] in ("lower", "higher")
        assert set(metric.get("workloads", [])) <= set(cells(root))


def a_cell_finds_its_files_by_name(root, workload):
    cell = run.load_cell(root, workload)
    config = cell["config"]
    # one chip, or four where the deployment is laid out over four
    assert cell["chips"] in (1, 4) and cell["chips"] == config["deployment"]["chips"]
    assert config["device_batch"] == int(flags_of(config)["--device-batch"])
    assert "--device-step" not in config["server_flags"]  # the harness adds it
    assert os.path.exists(os.path.join(cell["base"], "generators", cell["mix"]["generator"] + ".py"))
    assert cell["mix"]["loop"] in ("open", "closed")
    if cell["mix"]["loop"] == "open":
        assert cell["mix"]["rate_per_s"] > 0  # the cell's own number, 0.8 of its knee
        assert cell["mix"]["rate_per_s"] == pytest.approx(0.8 * cell["mix"]["knee_per_s"])
    end_to_end = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in end_to_end and len(end_to_end) >= 2 and cell["per_layer"]
    for metric in cell["end_to_end"] + cell["per_layer"]:
        assert os.path.exists(os.path.join(cell["base"], "readers", metric["reader"] + ".py"))
    assert {m["moves"] for m in cell["per_layer"]} <= end_to_end


def configurations_pass_their_shape_and_mark_what_they_assume(root):
    """A configuration passes the deployment's shape and nothing else (no
    tuning flag pinned around a fault of the program): the six flags every
    deployment has, and of the further shape flags those whose value its
    ``deployment`` states, at that value.  Whatever the cited source does not
    bear out is named under ``assumed``, in the mix too."""
    spec = bench(root)
    for entry in spec["configs"]:
        config = run._load(os.path.join(root, entry["file"]))
        flags = flags_of(config)
        assert SHAPE_FLAGS <= set(flags), flags
        assert set(flags) - SHAPE_FLAGS <= set(FURTHER_SHAPE_FLAGS), flags
        for flag, states in FURTHER_SHAPE_FLAGS.items():  # the program's default for each is 1
            assert int(flags.get(flag, 1)) == config["deployment"][states], flag
        assert {"f", "keys_per_command", "payload_bytes", "write_share"} <= set(config["assumed"])
        assert "f=1" not in entry["source"] and "1 key" not in entry["source"]
    for traffic in {cell["traffic"] for cell in spec["workloads"]}:
        mix = run._load(os.path.join(root, spec["paths"][0], "traffic", traffic + ".json"))
        # every parameter of the key generator but the key space, which the source names
        drawn_by = {f"key_gen.{name}" for name in mix["key_gen"]} - {"key_gen.kind", "key_gen.keys_per_shard"}
        assert drawn_by | {"clients", "read_share"} <= set(mix["assumed"])
        if mix["loop"] == "open":
            assert "arrivals" in mix["assumed"]
    for cell in spec["workloads"]:  # the mix sends what the deployment is laid out for
        loaded = run.load_cell(root, cell["name"])
        config, mix = loaded["config"], loaded["mix"]
        assert int(mix.get("keys_per_command", 1)) == config["deployment"]["keys_per_command"]
        assert int(mix.get("shard_count", 1)) == config["deployment"]["shards"]


def per_layer_metrics_follow_what_they_move(root):
    """A per-layer metric without a list of cells is reported wherever the
    end-to-end metric it moves is, so a later cell is traced without an edit
    to it; one with a list (a mechanism that lives in some cells only) names
    cells that report what it moves, and is reported in those alone."""
    spec = bench(root)
    for cell in cells(root):
        reported = {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}
        expected = {m["name"] for m in spec["per_layer"]
                    if m["moves"] in reported and cell in m.get("workloads", [cell])}
        assert {m["name"] for m in run.load_cell(root, cell)["per_layer"]} == expected
        assert expected  # every cell reports at least one per-layer metric
    for metric in spec["per_layer"]:
        moved = next(m for m in spec["end_to_end"] if m["name"] == metric["moves"])
        assert set(metric.get("workloads", [])) <= set(moved.get("workloads", cells(root)))
        assert metric.get("workloads", True)  # a list, where there is one, names a cell
        # the metric's own file says the same as its entry
        own = run._load(os.path.join(root, spec["paths"][0], "layer_metrics", metric["name"] + ".json"))
        assert {key: own[key] for key in metric} == metric, metric["name"]
        assert own["reads"] and own["reader"]


def the_harness_holds_no_cell_protocol_or_metric_name(root):
    with open(os.path.join(root, "benchmark", "run.py")) as fh:
        source = fh.read()
    spec = bench(root)
    for word in [m["name"] for m in spec["end_to_end"] + spec["per_layer"]] + cells(root) + \
            [c["name"] for c in spec["configs"]] + ["epaxos", "newt", "tempo", "zipf"]:
        assert word not in source, word


# --- what the tree has, held by name ------------------------------------------------

FOUR_CHIP_CONFIG, FOUR_CHIP_TRAFFIC = "tempo_n5_4shard_2key", "ycsbt_zipf07_sat"
FOUR_CHIP_CELL = f"{FOUR_CHIP_CONFIG}.{FOUR_CHIP_TRAFFIC}"
FOUR_CHIP_FIVE = ["cross_shard_share.sat", "shard_replies_per_cmd.sat", "collective_share.sat",
                  "precompile_ms", "device_round_hbm_share.sat"]


def the_four_chip_cell_is_there_once_with_its_five_metrics_in_their_order(root):
    """PR 27's entries, wherever they stand: the configuration and the cell
    once each, the cell among those on four chips (how many of those there may
    be is ``the_file_has_the_contracts_keys_and_forms``'s to hold), the five
    per-layer metrics all there, in the order they were appended in."""
    spec = bench(root)
    assert [c["name"] for c in spec["configs"]].count(FOUR_CHIP_CONFIG) == 1
    ours = [c for c in spec["workloads"] if c["name"] == FOUR_CHIP_CELL]
    assert len(ours) == 1 and ours[0]["chips"] == 4
    assert (ours[0]["config"], ours[0]["traffic"]) == (FOUR_CHIP_CONFIG, FOUR_CHIP_TRAFFIC)
    names = [m["name"] for m in spec["per_layer"]]
    assert [name for name in names if name in FOUR_CHIP_FIVE] == FOUR_CHIP_FIVE


RULES = [the_file_has_the_contracts_keys_and_forms,
         configurations_pass_their_shape_and_mark_what_they_assume,
         per_layer_metrics_follow_what_they_move,
         the_harness_holds_no_cell_protocol_or_metric_name]
HELD_BY_NAME = [the_four_chip_cell_is_there_once_with_its_five_metrics_in_their_order]
