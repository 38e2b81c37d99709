"""The YCSB workload B cell, `epaxos_n5_1m_ycsb.ycsb_b_sat`, end to end
through `run.run_cell` on a copy of the tree, small, on the CPU: a store
loaded first, then 95% reads of whole records, every one of them held to the
latest acknowledged write.  Once with the program's server, once with one
that answers a read from a lagging copy (`stale_read_server.py`), and the
good run's history once more with one read set back by one write."""

import contextlib
import io
import json
import os
import time

import numpy as np
import pytest

from benchmark import run
from benchmark.check import check_history
from benchmark.generators import kv_loop, kv_ycsb
from tests.benchmark_tests import next_cell
from tests.benchmark_tests.test_benchmark_e2e import TINY_CONFIG, names

CELL = "epaxos_n5_1m_ycsb.ycsb_b_sat"
RECORDS = 400
SMALL = {"clients": 32, "generator_processes": 2, "warmup_s": 0.5, "drain_limit_s": 15.0,
         "readback_keys": 64, "load_records": RECORDS,
         "key_gen": {"kind": "zipf", "coefficient": 0.99, "keys_per_shard": RECORDS}}
SEED = 2**31 + 32


def small_cell(folder, trace, **more):
    root = next_cell.copy_tree(str(folder))
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        result = run.run_cell(CELL, SEED, 3.0, trace, root=root, platform="cpu", overrides=SMALL,
                              config_overrides=TINY_CONFIG, started=time.monotonic(), **more)
    out = os.path.join(root, "benchmark_out", CELL, f"trace{int(trace)}")
    return result, out, printed.getvalue(), root


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One traced run of the cell with the program's server."""
    result, out, printed, root = small_cell(tmp_path_factory.mktemp("ycsb"), True)
    return {"result": result, "out": out, "printed": printed, "root": root,
            "history": dict(np.load(os.path.join(out, "history.npz")))}


def test_the_cell_is_served_correct_with_nothing_failed(served):
    result = served["result"]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 500
    assert result["compared"]["violations"] == {"value": 0, "limit": 0}
    assert not os.path.exists(os.path.join(served["out"], "witness.json"))
    assert run.load_cell(run.ROOT, CELL)["config"]["payload_bytes"] == 1000


def test_every_record_is_loaded_exactly_once_across_the_processes_before_the_window(served):
    history = served["history"]
    order = np.lexsort((history["seq"], history["client"]))
    by_client = {}
    for row in order.tolist():
        by_client.setdefault(int(history["client"][row]), []).append(row)
    loaded = []
    for proc in range(SMALL["generator_processes"]):
        own = kv_loop.own_clients(SMALL["clients"], proc, SMALL["generator_processes"])
        share = kv_ycsb.load_keys(RECORDS, proc, SMALL["generator_processes"])
        for client, keys in kv_ycsb.client_keys(share, own).items():
            rows = by_client[client][: len(keys)]  # its first requests are its load
            assert history["key"][rows].tolist() == keys
            assert np.all(history["op"][rows] == kv_loop.PUT)
            assert np.all(history["phase"][rows] == kv_loop.WARM)
            assert np.all(history["status"][rows] == kv_loop.OK)
            # the record was not there before: a load, not an update
            assert np.all(history["ret_client"][rows] == kv_loop.NONE_VALUE)
            loaded += keys
    assert sorted(loaded) == list(range(1, RECORDS + 1))
    reports = json.loads(served["printed"].split("# generators ")[1].splitlines()[0])
    assert sum(report["loaded"] for report in reports) == RECORDS
    # the store holds the loaded records and no other, to the end
    with open(os.path.join(served["out"], "snapshot.json")) as fh:
        assert json.load(fh)["store_records"] == RECORDS


def test_the_windows_reads_are_95_percent_and_every_one_returns_a_record(served):
    history = served["history"]
    window = history["phase"] == kv_loop.MEASURED
    reads = window & (history["op"] == kv_loop.GET)
    assert 0.93 < reads.sum() / window.sum() < 0.97
    assert np.all(history["status"][reads] == kv_loop.OK)
    assert np.all(history["ret_client"][reads] >= 1)  # neither nothing nor a foreign value
    stats = json.loads(served["printed"].split("# check: violations 0 (limit 0); ")[1]
                       .split(" ; server")[0])
    assert stats["gets_checked"] >= 0.9 * reads.sum()
    assert stats["acked_writes"] >= RECORDS and stats["check_seconds"] > 0


def test_a_traced_run_reports_the_four_new_metrics_beside_a_saturated_cells(served):
    metrics = served["result"]["metrics"]
    with open(os.path.join(served["root"], "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    assert set(metrics) == names("per_layer", CELL, spec)
    assert set(metrics) - names("per_layer", "epaxos_n5_1m.zipf_sat", spec) == {
        "read_share.sat", "value_bytes_per_get.sat"}
    assert 93 < metrics["read_share.sat"]["value"] < 97
    assert metrics["value_bytes_per_get.sat"]["value"] == 1000  # every read hit a loaded record
    assert 1000 < metrics["reply_bytes_per_cmd.sat"]["value"] < 1200
    assert metrics["reply_flush_ms.sat"]["value"] >= 0


def test_a_server_that_reads_from_a_lagging_copy_comes_out_incorrect_with_a_stale_read(tmp_path):
    result, out, printed, _ = small_cell(
        tmp_path, False, server_module="tests.benchmark_tests.stale_read_server")
    assert result["correct"] is False
    assert result["failed"] == 0  # nothing failed and no write is lost: only reads are old
    assert result["compared"]["violations"]["value"] >= 1
    with open(os.path.join(out, "witness.json")) as fh:
        witnesses = json.load(fh)
    assert {w["check"] for w in witnesses} == {"stale_read"}
    read, newer = witnesses[0]["ops"][:2]
    assert "get" in read and "write" in newer and newer["acked"] < read["sent"]
    assert "# WITNESS" in printed


def test_one_read_of_the_window_set_back_by_one_write_is_rejected(served):
    history = {name: col.copy() for name, col in served["history"].items()}
    strays = history.pop("strays")
    assert check_history(history, strays)["correct"] is True
    rifl = (history["client"].astype(np.int64) << 32) | history["seq"]
    wrote = (history["ret_client"].astype(np.int64) << 32) | history["ret_seq"]
    row_of = {int(r): row for row, r in enumerate(rifl.tolist())}
    reads = np.flatnonzero((history["phase"] == kv_loop.MEASURED) & (history["op"] == kv_loop.GET))
    # a read whose value was written, and acknowledged, before the read was sent
    read = next(r for r in reads.tolist()
                if history["acked"][row_of[int(wrote[r])]] < history["sent"][r])
    write = row_of[int(wrote[read])]
    history["ret_client"][read] = history["ret_client"][write]  # what that write overwrote
    history["ret_seq"][read] = history["ret_seq"][write]
    verdict = check_history(history, strays)
    assert verdict["correct"] is False and verdict["stats"]["violations"] == 1
    assert verdict["witnesses"][0]["check"] == "stale_read"
    assert verdict["witnesses"][0]["key"] == int(history["key"][read])
