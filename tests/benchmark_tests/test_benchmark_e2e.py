"""One cell end to end through the functions `benchmark/run.py` calls, at
small sizes on the CPU (the sizes and the platform the run must find are
arguments; the command line itself takes only the TPU), with tracing off and
on, with the timed path broken underneath, and with a configuration, a mix, a
layer metric, a reader and a cell added as new files only."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from benchmark import run

ROOT = run.ROOT
TINY_CONFIG = {
    "server_flags": ["--protocol", "epaxos", "-n", "5", "-f", "1", "--device-key-buckets", "1024",
                     "--device-batch", "64", "--device-pending", "64"],
    "device_batch": 64,
}
TINY_MIX = {
    "clients": 32, "generator_processes": 2, "warmup_s": 0.5, "drain_limit_s": 15.0,
    "readback_keys": 64, "key_gen": {"kind": "zipf", "coefficient": 1.0, "keys_per_shard": 400},
}


def tiny(workload, trace, root=ROOT, **more):
    overrides = {**TINY_MIX, **more.pop("mix", {})}
    if "open" in workload:
        overrides.setdefault("rate_per_s", 300.0)
    return run.run_cell(workload, 2**31 + 77, 3.0, trace, root=root, platform="cpu",
                        overrides=overrides, config_overrides=TINY_CONFIG,
                        started=time.monotonic(), **more)


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def names(section, workload, spec=None):
    """The metrics a cell reports: those that list it, and a per-layer metric
    with no list of its own wherever the cell reports the metric it moves."""
    spec = spec or bench()
    end_to_end = {m["name"] for m in spec["end_to_end"]
                  if workload in m.get("workloads", [workload])}
    if section == "end_to_end":
        return end_to_end
    return {m["name"] for m in spec["per_layer"]
            if workload in m.get("workloads", [workload]) and m["moves"] in end_to_end}


@pytest.mark.parametrize("workload", ["epaxos_n5_1m.zipf_open80", "tempo_n5_1m.zipf_sat"])
def test_an_untraced_run_reports_the_cells_end_to_end_metrics(workload):
    config = {**TINY_CONFIG, "server_flags": ["--protocol", "newt"] + TINY_CONFIG["server_flags"][2:]} \
        if workload.startswith("tempo") else TINY_CONFIG
    result = run.run_cell(workload, 5, 3.0, False, platform="cpu", started=time.monotonic(),
                          overrides={**TINY_MIX, "rate_per_s": 300.0}, config_overrides=config)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 500
    assert set(result["metrics"]) == names("end_to_end", workload)
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] >= 1
    assert "breakdown" not in result and "busy_s" not in result["device"]
    out = os.path.join(ROOT, "benchmark_out", workload, "trace0")
    assert os.path.exists(os.path.join(out, "history.npz"))
    assert not os.path.exists(os.path.join(out, "witness.json"))


def test_a_traced_run_carries_the_same_traffic_and_check_and_adds_the_trace(capsys):
    workload = "epaxos_n5_1m.zipf_open80"
    plain = tiny(workload, False)
    traced = tiny(workload, True)
    assert plain["correct"] is True and traced["correct"] is True
    assert traced["attempted"] == plain["attempted"]  # the same seed, the same schedule
    assert set(traced["metrics"]) == names("per_layer", workload)
    assert traced["device"]["busy_s"] > 0 and traced["device"]["window_s"] > 0.9
    assert 1 <= len(traced["breakdown"]["device_ops"]) <= 10
    assert traced["metrics"]["recompiles_in_window"]["value"] == 0
    printed = capsys.readouterr().out
    for line in ("# host cores", "generator processes", "# offered", "# lateness ms",
                 "# latency ms p50/p95/p99/max", "# server counter deltas", "# check: violations 0"):
        assert line in printed


def test_a_broken_timed_path_comes_out_incorrect_with_a_witness(capsys):
    workload = "epaxos_n5_1m.zipf_sat"
    result = tiny(workload, False, server_module="tests.benchmark_tests.broken_server")
    assert result["correct"] is False
    assert result["failed"] == 0  # nothing failed: only the answers are wrong
    with open(os.path.join(ROOT, "benchmark_out", workload, "trace0", "witness.json")) as fh:
        witness = json.load(fh)[0]
    assert witness["check"] in ("fork", "stale_read", "real_time") and witness["key"] >= 1
    assert len(witness["ops"]) >= 2 and "returned" in witness["ops"][0]
    assert "# WITNESS" in capsys.readouterr().out


def test_a_run_that_finds_another_platform_than_it_must_fails_and_prints_no_result():
    with pytest.raises(run.RunFailed, match="does not serve from tpu"):
        run.run_cell("epaxos_n5_1m.zipf_sat", 1, 1.0, False, platform="tpu",
                     overrides=TINY_MIX, config_overrides=TINY_CONFIG,
                     started=time.monotonic())
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "epaxos_n5_1m.zipf_sat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)
    assert done.returncode != 0 and done.stdout == ""


def test_a_configuration_mix_metric_reader_and_cell_are_added_as_new_files_only(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "fantoch_tpu"), os.path.join(root, "fantoch_tpu"))
    before = {path: open(path, "rb").read()
              for folder, _, files in os.walk(os.path.join(root, "benchmark"))
              for path in (os.path.join(folder, name) for name in files)}
    base = os.path.join(root, "benchmark")
    with open(os.path.join(base, "configs", "atlas_n3.json"), "w") as fh:
        json.dump({"name": "atlas_n3", "source": "a later PR's", "payload_bytes": 16,
                   "device_batch": 32, "reduced": [],
                   "server_flags": ["--protocol", "atlas", "-n", "3", "-f", "1",
                                    "--device-key-buckets", "256", "--device-batch", "32",
                                    "--device-pending", "32"]}, fh)
    with open(os.path.join(base, "traffic", "conflict50_small.json"), "w") as fh:
        json.dump({"generator": "kv_loop", "loop": "closed", "clients": 12,
                   "generator_processes": 1, "key_gen": {"kind": "conflict_rate", "rate": 50},
                   "keys_per_command": 1, "read_share": 0.2, "warmup_s": 0.3,
                   "drain_limit_s": 15.0, "readback_keys": 8}, fh)
    with open(os.path.join(base, "readers", "reads_share.py"), "w") as fh:
        fh.write("def read(ctx, scale):\n"
                 "    ops = ctx['measured']['op']\n"
                 "    return float(scale * (ops == 1).mean()) if len(ops) else None\n")
    with open(os.path.join(base, "layer_metrics", "read_share_seen.json"), "w") as fh:
        json.dump({"name": "read_share_seen", "reader": "reads_share", "args": {"scale": 100.0}}, fh)
    spec = bench()
    spec["configs"].append({"name": "atlas_n3", "source": "a later PR's",
                            "file": "benchmark/configs/atlas_n3.json", "reduced": [], "why": "w"})
    spec["workloads"].append({"name": "atlas_n3.conflict50_small", "config": "atlas_n3",
                              "traffic": "conflict50_small", "chips": 1, "why": "w"})
    for metric in spec["end_to_end"]:
        if metric["name"] == "goodput_cmds_s":
            metric["workloads"].append("atlas_n3.conflict50_small")
    spec["per_layer"].append({"name": "read_share_seen", "unit": "%", "better": "higher",
                              "source": "host_clock", "layer": "client plane",
                              "moves": "goodput_cmds_s", "workloads": ["atlas_n3.conflict50_small"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(spec, fh)

    result = run.run_cell("atlas_n3.conflict50_small", 11, 2.0, True, root=root, platform="cpu",
                          started=time.monotonic())
    assert result["correct"] is True and result["attempted"] > 50
    assert 5 < result["metrics"]["read_share_seen"]["value"] < 40  # reads are a fifth of the mix
    # the new cell reports its own metric and, with no edit to any list, every
    # metric there is that moves the end-to-end metric it reports
    assert set(result["metrics"]) == names("per_layer", "atlas_n3.conflict50_small", spec)
    assert len(result["metrics"]) > 1
    plain = run.run_cell("atlas_n3.conflict50_small", 11, 2.0, False, root=root, platform="cpu",
                         started=time.monotonic())
    assert set(plain["metrics"]) == {"goodput_cmds_s", "setup_s"}
    assert all(open(path, "rb").read() == content for path, content in before.items())
