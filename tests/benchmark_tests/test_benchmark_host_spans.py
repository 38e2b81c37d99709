"""The reduction from a capture's host stage annotations to where the
device's idle time went, the two readers that came with it, and the files
of the per-layer metrics that read the program's round-stage spans."""

import gzip
import json
import os

import pytest

from benchmark import host_spans, run, trace_reduce

ROOT = run.ROOT
RECORDED = os.path.join(ROOT, "benchmark", "testdata", "trace_spans_small.xplane.pb.gz")
MS = 1e6  # ns


def planes(ops, loop=(), pool=(), other=((0.0, 1.0),)):
    """One device with ``ops``; stage annotations on two host threads."""
    return [
        ("/host:CPU", [
            ("python3", [("fantoch/" + name, s * MS, d * MS) for name, s, d in loop]),
            ("python3", [(f"fantoch/{name}#round=7#", s * MS, d * MS) for name, s, d in pool]),
            ("runtime", [("Wait for donation holds", s * MS, d * MS) for s, d in other]),
        ]),
        ("/device:TPU:0", [
            ("XLA Ops", [("%fusion.1 = s32[8]", s * MS, d * MS) for s, d in ops]),
            ("XLA Modules", [("jit_round", s * MS, d * MS) for s, d in ops]),
        ]),
    ]


def test_each_instant_of_a_gap_goes_to_the_innermost_stage_open():
    # device busy [10, 20] and [70, 80] of a window [0, 100] ms
    capture = planes(
        ops=[(10, 10), (70, 10)], other=[(0, 100)],
        loop=[("round", 22, 54), ("collect", 22, 2), ("deliver", 50, 20), ("publish", 70, 5),
              ("idle_wait", 90, 5)],
        pool=[("step", 26, 20), ("assemble", 27, 5), ("enqueue", 32, 3), ("fetch", 36, 4),
              ("execute", 40, 5)],
    )
    spans = host_spans.stage_spans(capture)
    assert ("step" in {name for _, _, name in spans}) and len(spans) == 10  # "#round=7#" is cut off
    gaps = host_spans.device_gaps(capture, "tpu")
    assert gaps == [(0, 10 * MS), (20 * MS, 70 * MS), (80 * MS, 100 * MS)]
    out = host_spans.attribute(gaps, spans)
    assert out["idle_s"] == pytest.approx(0.080)
    by_stage = {stage: seconds * 1e3 for stage, seconds in out["by_stage"].items()}
    assert by_stage == pytest.approx({
        "unnamed": 10 + 2 + 5 + 5 + 5,  # before the round, [20, 22], after it around the wait
        "collect": 2, "handoff": 2,      # [24, 26]: only `round` open, before its step
        "step": 1 + 1 + 1,               # the step's own time between its children
        "assemble": 5, "enqueue": 3, "fetch": 4, "execute": 5,
        "resume": 4,                     # [46, 50]: only `round` open, after its step
        "deliver": 20, "idle_wait": 5,
    })
    assert out["unnamed_share"] == pytest.approx(27 / 80)
    # no step open: everything but [26, 46]
    assert out["off_step_share"] == pytest.approx(60 / 80)
    assert out["longest"][0] == ["deliver", pytest.approx(0.050)]  # named by its largest part
    assert [round(seconds, 3) for _, seconds in out["longest"]] == [0.05, 0.02, 0.01]


def test_a_capture_that_starts_inside_a_step_still_counts_it_as_the_step():
    capture = planes(ops=[(30, 10)], other=[(0, 40)], pool=[("execute", 0, 10)],
                     loop=[("deliver", 10, 20)])
    out = host_spans.attribute(host_spans.device_gaps(capture, "tpu"),
                               host_spans.stage_spans(capture))
    assert out["off_step_share"] == pytest.approx(20 / 30) and out["unnamed_share"] == 0


def test_a_capture_without_stages_or_without_a_device_reads_nothing(tmp_path):
    assert host_spans.reduce_capture(str(tmp_path), "tpu") == {}
    bare = planes(ops=[(10, 10)])
    assert host_spans.stage_spans(bare) == []
    no_device = [bare[0]]
    assert host_spans.attribute(host_spans.device_gaps(no_device, "tpu"),
                                [(0.0, 1.0, "round")]) == {}


def test_the_recorded_capture_from_the_chip(tmp_path):
    """115 ms of a traced open-loop run on the v5e (PR 24's first chip
    call): its device plane, and the stage annotations of the host plane."""
    path = tmp_path / "recorded.xplane.pb"
    with gzip.open(RECORDED, "rb") as src:
        path.write_bytes(src.read())
    captured = trace_reduce.load_planes(str(path))
    spans = host_spans.stage_spans(captured)
    assert {name for _, _, name in spans} == {
        "round", "collect", "step", "assemble", "enqueue", "fetch", "execute", "deliver",
        "publish"}
    gaps = host_spans.device_gaps(captured, "tpu")
    first = min(gaps)  # from the capture's start to the first run of the round's program
    assert (first[1] - first[0]) / MS == pytest.approx(49.84, abs=0.01)
    parts = {stage: seconds * 1e3 for stage, seconds in
             host_spans.attribute([first], spans)["by_stage"].items()}
    # the loop was delivering the last round's replies for 22.3 ms of it;
    # the 17.4 ms before lie under no stage: the round they belong to opened
    # before the capture did, so its span is not in it
    assert parts["deliver"] == pytest.approx(22.285, abs=0.01)
    assert parts["unnamed"] == pytest.approx(17.394, abs=0.01)
    assert parts["assemble"] == pytest.approx(2.553, abs=0.01)
    whole = host_spans.reduce_capture(str(path), "tpu")
    reduced = trace_reduce.reduce_trace(str(path), "tpu", "TPU v5 lite")
    assert whole["idle_s"] == pytest.approx(reduced["window_s"] - reduced["busy_s"])
    assert sum(whole["by_stage"].values()) == pytest.approx(whole["idle_s"])
    assert whole["longest"][0][0] == "deliver" and len(whole["longest"]) <= 10
    assert whole["by_stage"]["resume"] > 0 and whole["by_stage"]["handoff"] > 0
    assert 0.1 < whole["unnamed_share"] < 0.12 and 0.7 < whole["off_step_share"] < 0.8


# --- the readers ---


def _reader(name):
    return run._module(os.path.join(ROOT, "benchmark"), "readers", name)


def test_round_span_percentile_reads_the_ring_inside_the_counted_stretch(tmp_path):
    reader = _reader("round_span_percentile")
    t0 = 1000.0  # s, the window's start on the monotonic clock
    rows = [["round", int((t0 + i) * 1e9), int((t0 + i) * 1e9 + (i + 1) * 1e6), i, 1, None]
            for i in range(-5, 40)]  # one a second, the i-th taking i+1 ms
    rows += [["deliver", int(t0 * 1e9), int(t0 * 1e9 + 5e8), 0, 1, "round"]]
    ring = {"clock": "monotonic_ns", "spans": rows,
            "columns": ["name", "t0_ns", "t1_ns", "round", "thread", "parent"]}
    (tmp_path / "round_spans.json").write_text(json.dumps(ring))
    ctx = {"t0": t0, "counted_s": 30.5, "snapshot_end": {"profile_dir": str(tmp_path)}}
    # rounds 0..30 end inside [t0, t0 + 30.5]: 1..31 ms, the 95th percentile 30 ms
    assert reader.read(ctx, "round", 95) == pytest.approx(30.0)
    assert reader.read(ctx, "round", 50) == pytest.approx(16.0)
    assert reader.read({**ctx, "counted_s": 10.0}, "round", 95) is None  # 10 rounds: too few
    assert reader.read({**ctx, "counted_s": 10.0}, "round", 95, at_least=5) == pytest.approx(10.0)
    assert reader.read({**ctx, "snapshot_end": {}}, "round", 95) is None  # an older server
    assert reader.read({**ctx, "snapshot_end": {"profile_dir": str(tmp_path / "x")}},
                       "round", 95) is None


def test_host_spans_share_reads_nothing_without_a_trace_or_a_profile_dir(capsys):
    reader = _reader("host_spans_share")
    base = os.path.join(ROOT, "benchmark")
    backend = {"platform": "cpu"}
    assert reader.read({"trace": None, "base": base, "snapshot_end": {
        "profile_dir": "/nonexistent", "backend": backend}}, "unnamed_share") is None
    assert reader.read({"trace": {"busy_s": 1.0}, "base": base, "snapshot_end": {
        "backend": backend}}, "unnamed_share") is None
    ctx = {"trace": {"busy_s": 1.0}, "base": base,
           "snapshot_end": {"profile_dir": "/nonexistent", "backend": backend}}
    assert reader.read(ctx, "unnamed_share") is None and ctx[reader.KEPT] == {}
    assert reader.read(ctx, "off_step_share") is None  # reduced once a run
    assert "# idle gaps by stage" not in capsys.readouterr().out


# --- the metrics' own files ---

NEW = ["session_us_per_cmd", "queue_wait_ms", "gate_wait_ms", "assemble_us_per_cmd", "enqueue_ms",
       "fetch_wait_ms", "execute_us_per_cmd", "deliver_us_per_cmd", "loop_handoff_ms",
       "loop_stall_ms", "round_p95_ms", "idle_unnamed_share", "idle_off_step_share"]


def _bench(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name", [base + kind for base in NEW for kind in (".open", ".sat")
                                  if base + kind != "gate_wait_ms.sat"])
def test_each_round_stage_metrics_file_says_what_its_entry_says(name):
    spec = _bench()
    entry = next(m for m in spec["per_layer"] if m["name"] == name)
    own = run._load(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json"))
    assert {key: own[key] for key in entry} == entry and "workloads" not in entry
    assert entry["moves"] == ("commit_p50_ms" if name.endswith(".open") else "goodput_cmds_s")
    assert os.path.exists(os.path.join(ROOT, "benchmark", "readers", own["reader"] + ".py"))
    assert own["reads"] and entry["layer"] in {m["layer"] for m in spec["per_layer"]
                                                if m["name"] != name}
    # every counter it reads is one the served path publishes from its first snapshot
    from fantoch_tpu.observability.device import ROUND_STAGES

    published = {f"stage_{stage}_ms" for stage in ROUND_STAGES} | {
        "session_decode_ms", "session_admit_ms", "submitted", "queue_wait_ms", "queue_released",
        "rounds", "device_dispatched_rows", "device_dispatches", "device_fetch_ms", "executed",
        "replied", "loop_stall_ms"}
    args = own["args"]
    assert set(args.get("num", []) + args.get("den", []) + [args.get("key", "rounds")]) - {
        "unnamed_share", "off_step_share"} <= published


def test_the_new_entries_come_after_those_the_benchmark_had(root):
    """Entries are appended: what PR 23 had comes before what PR 24 added,
    and that before anything later (a later PR adds at the end, `root`'s
    copies among them; retiring an entry, as PR 26 did, moves none of the
    others)."""
    names = [m["name"] for m in _bench(root)["per_layer"]]
    first, second = names.index("slow_path_share.sat"), names.index("session_us_per_cmd.open")
    assert first + 1 == second and names[second:second + 25] == [
        base + kind for base in NEW for kind in (".open", ".sat") if base + kind != "gate_wait_ms.sat"]
    assert "gate_wait_ms.sat" not in names
