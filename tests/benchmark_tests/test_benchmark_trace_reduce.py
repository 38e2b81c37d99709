"""The reduction from a profiler capture to the device's numbers."""

import gzip
import os

import pytest

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmark", "testdata",
                        "trace_small.xplane.pb.gz")


def planes(ops, modules, host=((0.0, 100.0),)):
    return [
        ("/host:CPU", [("python", [("$loop", s, d) for s, d in host])]),
        ("/device:TPU:0", [
            ("XLA Ops", [(name, s, d) for name, s, d in ops]),
            ("XLA Modules", [(name, s, d) for name, s, d in modules]),
            ("Steps", [("1", 0.0, 1000.0)]),
        ]),
    ]


def test_busy_is_the_union_of_device_op_intervals_and_gaps_are_the_rest():
    ops = [("fusion.1", 10e9, 2e9), ("while", 11e9, 4e9), ("copy.2", 20e9, 1e9)]
    modules = [("jit_round", 10e9, 5e9), ("jit_round", 20e9, 1e9), ("jit_other", 30e9, 0.1e9)]
    out = trace_reduce.reduce_planes(planes(ops, modules, host=((0.0, 40e9),)), "tpu")
    assert out["window_s"] == pytest.approx(40.0)
    assert out["busy_s"] == pytest.approx(6.0)       # [10, 15] and [20, 21]
    assert out["idle_share"] == pytest.approx(1 - 6.0 / 40.0)
    assert out["rounds"] == 2 and out["busy_per_round_s"] == pytest.approx(3.0)
    assert out["device_ops"][0] == ["while", pytest.approx(4.0)]
    assert [gap for _, gap in out["idle_gaps"]] == pytest.approx([19.0, 10.0, 5.0])
    assert {who for who, _ in out["idle_gaps"]} == {"host"}


def test_a_capture_without_a_device_plane_reads_nothing():
    out = trace_reduce.reduce_planes([("/host:CPU", [("python", [("$x", 0.0, 5e9)])])], "tpu")
    assert "busy_s" not in out and out["devices"] == 0
    assert trace_reduce.reduce_planes([], "tpu") == {}


def test_an_unknown_device_is_an_error():
    assert trace_reduce.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        trace_reduce.peaks_for("TPU v9")
    with pytest.raises(KeyError):
        trace_reduce.reduce_trace("/nonexistent", "tpu", "cpu")


def test_the_recorded_trace_from_the_chip(tmp_path):
    """A capture of the served path on the v5e, cut to its device plane and a
    short stretch (PR 23's chip run); the reduction reads it with jax alone."""
    path = tmp_path / "recorded.xplane.pb"
    with gzip.open(RECORDED, "rb") as src:
        path.write_bytes(src.read())
    out = trace_reduce.reduce_trace(str(path), "tpu", "TPU v5 lite")
    assert out["devices"] == 1
    assert 0 < out["busy_s"] < out["window_s"]
    assert 0 < out["idle_share"] < 1
    assert out["rounds"] >= 1 and out["busy_per_round_s"] > 0
    assert 1 <= len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert all(seconds > 0 for _, seconds in out["device_ops"])
