"""Run-layer observability: metrics snapshots, execution log + replay, the
prof histogram registry (fantoch/src/run/task/{metrics_logger,
execution_logger,tracer}.rs + fantoch_prof/src/lib.rs analogs), and the
dot-lifecycle tracing plane (fantoch_tpu/observability — span schema
roundtrip, deterministic sampling, same-seed trace equality, stage
coverage and stage-sum-equals-client-latency on sim and localhost runs)."""

import asyncio
import glob
import json
import time

from fantoch_tpu.client import ConflictRateKeyGen, Workload
from fantoch_tpu.core import Config
from fantoch_tpu.protocol import EPaxos
from fantoch_tpu.run.harness import run_localhost_cluster
from fantoch_tpu.run.observe import (
    ProcessMetrics,
    read_execution_log,
    read_metrics_snapshot,
    replay_execution_log,
    write_metrics_snapshot,
)
from fantoch_tpu.utils import prof


def test_metrics_snapshot_roundtrip(tmp_path):
    from fantoch_tpu.core.metrics import Metrics

    m = Metrics()
    m.aggregate("fast", 7)
    m.collect("lat", 3)
    path = str(tmp_path / "metrics.gz")
    write_metrics_snapshot(path, ProcessMetrics([m], [Metrics()]))
    out = read_metrics_snapshot(path)
    assert out.workers[0].get_aggregated("fast") == 7
    assert out.workers[0].get_collected("lat").count == 1


def test_prof_registry():
    prof.reset()

    @prof.profiled
    def work():
        time.sleep(0.001)

    for _ in range(3):
        work()
    with prof.elapsed("region"):
        time.sleep(0.001)
    snap = prof.snapshot()
    names = set(snap)
    assert any("work" in n for n in names) and "region" in names
    hist = next(v for k, v in snap.items() if "work" in k)
    assert hist.count == 3 and hist.mean() >= 1000  # microseconds
    assert "region" in prof.format_snapshot()


def test_cluster_observability_and_replay(tmp_path):
    """A runner run produces metrics files and a replayable execution log
    (VERDICT r2 item 7 done-criterion)."""
    config = Config(
        n=3,
        f=1,
        gc_interval_ms=50,
        executor_executed_notification_interval_ms=50,
        executor_monitor_execution_order=True,
    )
    workload = Workload(
        shard_count=1,
        key_gen=ConflictRateKeyGen(50),
        keys_per_command=2,
        commands_per_client=5,
        payload_size=1,
    )
    runtimes, clients = asyncio.run(
        run_localhost_cluster(
            EPaxos,
            config,
            workload,
            clients_per_process=1,
            extra_run_time_ms=600,
            observe_dir=str(tmp_path),
        )
    )
    assert all(c.issued_commands == 5 for c in clients.values())

    # metrics snapshots exist and carry the commit accounting
    snaps = sorted(glob.glob(str(tmp_path / "metrics_p*.gz")))
    assert len(snaps) == 3
    from fantoch_tpu.protocol import ProtocolMetricsKind

    total_commits = 0
    for path in snaps:
        snap = read_metrics_snapshot(path)
        worker = snap.workers[0]
        total_commits += worker.get_aggregated(ProtocolMetricsKind.FAST_PATH) or 0
        total_commits += worker.get_aggregated(ProtocolMetricsKind.SLOW_PATH) or 0
    assert total_commits == 15  # 3 clients x 5 commands

    # execution logs replay through a fresh executor with the same results
    logs = sorted(glob.glob(str(tmp_path / "execution_p*.log")))
    assert len(logs) == 3
    for pid, path in zip(sorted(runtimes), logs):
        batches = list(read_execution_log(path))
        assert batches, "execution log must not be empty"
        summary = replay_execution_log(path, EPaxos, pid, 0, config)
        # every key of every command produces one executor result
        assert summary["results"] == 15 * 2  # keys_per_command = 2


def test_prof_auto_instrument_spans():
    """The span-subscriber analog (fantoch_prof/src/lib.rs:78-136):
    auto_instrument wraps the hot-path methods of every protocol/executor
    subclass; driving a whole sim populates per-function histograms with
    no call-site edits; uninstrument restores the originals."""
    from fantoch_tpu.core.config import Config
    from fantoch_tpu.protocol import EPaxos
    from fantoch_tpu.utils import prof

    import sys as _sys, os as _os
    _sys.path.insert(0, _os.path.dirname(_os.path.abspath(__file__)))
    from harness import sim_test

    prof.reset()
    count = prof.auto_instrument()
    try:
        assert count > 0
        sim_test(EPaxos, Config(3, 1))
        snap = prof.snapshot()
        protocol_spans = [k for k in snap if k.endswith(".handle")]
        executor_spans = [k for k in snap if "handle_batch" in k]
        assert protocol_spans, sorted(snap)
        assert executor_spans, sorted(snap)
        assert all(snap[k].count > 0 for k in protocol_spans)
        formatted = prof.format_snapshot()
        assert "p99" in formatted
    finally:
        prof.uninstrument()
        prof.reset()
    # originals restored: no double-wrapping markers left behind
    from fantoch_tpu.protocol.graph_protocol import GraphProtocol

    assert not getattr(GraphProtocol.handle, "_prof_wrapped", False)


# --- prof registry scoping (the global-registry-bleed fix) ---


def test_prof_registry_isolation():
    """Two concurrent scopes (the localhost harness pattern: several
    ProcessRuntimes in one Python process, each calling set_registry
    before spawning its tasks) record into their own registries; the
    default scope stays clean."""
    from fantoch_tpu.core.metrics import Metrics

    prof.reset()

    async def scenario():
        r1, r2 = Metrics(), Metrics()

        async def work(registry, name):
            prof.set_registry(registry)
            for _ in range(3):
                with prof.elapsed(name):
                    await asyncio.sleep(0)
            return set(prof.snapshot())

        # gather wraps each coroutine in a task with its own context copy
        s1, s2 = await asyncio.gather(work(r1, "one"), work(r2, "two"))
        return r1, r2, s1, s2

    r1, r2, s1, s2 = asyncio.run(scenario())
    assert set(r1.collected) == {"one"} and r1.collected["one"].count == 3
    assert set(r2.collected) == {"two"} and r2.collected["two"].count == 3
    # each task's snapshot() saw only its own registry
    assert s1 == {"one"} and s2 == {"two"}
    # the default (module-level) registry never saw either scope
    assert "one" not in prof.snapshot() and "two" not in prof.snapshot()


def test_prof_scoped_registry_context_manager():
    with prof.scoped_registry() as reg:
        with prof.elapsed("inner"):
            pass
        assert "inner" in prof.snapshot()
    assert "inner" not in prof.snapshot()
    assert reg.collected["inner"].count == 1


# --- metrics snapshot: device-counter field (backward-compatible) ---


def test_metrics_snapshot_device_counters_roundtrip(tmp_path):
    from fantoch_tpu.core.metrics import Metrics

    m = Metrics()
    m.aggregate("fast", 2)
    device = {"table_plane_dispatches": 3, "jax_recompiles": 1}
    path = str(tmp_path / "metrics.gz")
    write_metrics_snapshot(path, ProcessMetrics([m], [Metrics()], device))
    out = read_metrics_snapshot(path)
    assert out.device == device


def test_metrics_snapshot_reads_pre_device_snapshots(tmp_path):
    """A snapshot pickled before the ``device`` field existed (its
    __dict__ simply lacks the key) reads back with device=None."""
    from fantoch_tpu.core.metrics import Metrics

    old = ProcessMetrics([Metrics()], [Metrics()])
    del old.__dict__["device"]  # exactly what an old pickle restores to
    path = str(tmp_path / "metrics_old.gz")
    write_metrics_snapshot(path, old)
    out = read_metrics_snapshot(path)
    assert out.device is None
    assert len(out.workers) == 1


def test_table_plane_device_counters():
    """The resident votes-table plane tallies per-dispatch counters
    (occupancy, kernel wall-ms, residual runs) that the snapshot fold and
    the bench rows consume."""
    import numpy as np

    from fantoch_tpu.executor.table import TableExecutor
    from fantoch_tpu.executor.table_plane import DeviceTablePlane

    plane = DeviceTablePlane(3, 2, key_buckets=4)
    plane.bucket("a")
    plane.commit_votes(
        np.zeros(3, np.int64),
        np.array([1, 2, 3], np.int64),
        np.ones(3, np.int64),
        np.ones(3, np.int64),
    )
    assert plane.dispatches == 1
    assert plane.stats["vote_rows"] == 3
    assert plane.stats["row_capacity"] >= 3
    assert plane.stats["kernel_ms"] > 0

    config = Config(3, 1, batched_table_executor=True, device_table_plane=True)
    ex = TableExecutor(1, 0, config)
    counters = ex.device_counters()
    assert counters == {
        "table_plane_dispatches": 0,
        "table_plane_grows": 0,
        "table_plane_vote_rows": 0,
        "table_plane_row_capacity": 0,
        "table_plane_residual_runs": 0,
        "table_plane_kernel_ms": 0,
        "table_plane_resident_uploads": 0,
        # the fault-tolerance tallies (failovers/rebuilds/degraded wall
        # + the severity-ordered health gauge) ride the same surface
        "table_plane_failovers": 0,
        "table_plane_rebuilds": 0,
        "table_plane_degraded_ms": 0.0,
        "table_plane_health": 0,
    }
    # plane off -> no counters contributed
    assert TableExecutor(1, 0, Config(3, 1)).device_counters() is None


def test_idle_frac_fold_semantics():
    """``device_idle_frac`` is a ratio: the fold must never sum it
    across executors; ``derive_idle_frac`` recomputes it from the folded
    busy/span wall totals (clamped to [0, 1])."""
    from fantoch_tpu.observability.device import derive_idle_frac, merge_counters

    a = {"device_busy_ms": 30.0, "device_span_ms": 100.0,
         "device_idle_frac": 0.7, "device_pipeline_depth": 2}
    b = {"device_busy_ms": 50.0, "device_span_ms": 100.0,
         "device_idle_frac": 0.5, "device_pipeline_depth": 2}
    folded = merge_counters(merge_counters({}, a), b)
    assert "device_idle_frac" not in folded  # ratios never sum
    assert folded["device_pipeline_depth"] == 2  # gauges fold by max
    derive_idle_frac(folded)
    assert abs(folded["device_idle_frac"] - (1 - 80.0 / 200.0)) < 1e-9
    # busy > span (overlapping spans after a fold) clamps at 0, and a
    # missing/zero span derives nothing
    assert derive_idle_frac(
        {"device_busy_ms": 5.0, "device_span_ms": 1.0}
    )["device_idle_frac"] == 0.0
    assert "device_idle_frac" not in derive_idle_frac({"device_busy_ms": 5.0})


def test_obs_summarize_prints_overlap(capsys):
    """bin/obs.py summarize surfaces the dispatch/drain overlap line
    from the per-dispatch device counters."""
    from fantoch_tpu.bin.obs import _print_overlap

    _print_overlap(
        {
            "device_dispatch_ms": 12.5,
            "device_drain_ms": 40.0,
            "device_fetch_ms": 33.0,
            "device_busy_ms": 45.0,
            "device_span_ms": 60.0,
            "device_pipeline_depth": 2,
            "device_pipelined_rounds": 7,
        }
    )
    line = capsys.readouterr().out
    assert "device overlap:" in line
    assert "idle_frac 0.250" in line
    assert "depth 2" in line and "pipelined_rounds 7" in line
    # no overlap counters -> silent (plane-only traces)
    _print_overlap({"table_plane_dispatches": 3})
    assert capsys.readouterr().out == ""


# --- dot-lifecycle tracing plane (fantoch_tpu/observability) ---


def _traced_sim(trace_path, seed=3, sample_rate=1.0, commands_per_client=4,
                clients_per_process=2, n=3, reorder=False,
                ingest_deadline_ms=None):
    """A tiny 3-process EPaxos sim at 50% conflict with tracing on;
    returns the runner's (metrics, monitors, latencies) tuple."""
    from fantoch_tpu.core import Planet
    from fantoch_tpu.sim import Runner

    config = Config(
        n=n,
        f=1,
        gc_interval_ms=100,
        executor_executed_notification_interval_ms=100,
        trace_sample_rate=sample_rate,
        ingest_deadline_ms=ingest_deadline_ms,
    )
    planet = Planet.new("gcp")
    regions = sorted(planet.regions())[:n]
    workload = Workload(
        shard_count=1,
        key_gen=ConflictRateKeyGen(50),
        keys_per_command=2,
        commands_per_client=commands_per_client,
        payload_size=1,
    )
    runner = Runner(
        EPaxos,
        planet,
        config,
        workload,
        clients_per_process=clients_per_process,
        process_regions=list(regions),
        client_regions=list(regions),
        seed=seed,
        trace_path=str(trace_path),
    )
    if reorder:
        runner.reorder_messages()
    return runner.run(extra_sim_time_ms=1000)


def test_span_schema_roundtrip(tmp_path):
    """Emit -> JSONL -> read -> Perfetto JSON validates; counter events
    ride along; a torn final line is dropped, not fatal."""
    from fantoch_tpu.core.timing import SimTime
    from fantoch_tpu.observability.perfetto import to_perfetto, validate_perfetto
    from fantoch_tpu.observability.report import assemble_spans
    from fantoch_tpu.observability.tracer import Tracer, read_trace

    clock = SimTime()
    path = str(tmp_path / "t.jsonl")
    tracer = Tracer(clock, path, sample_rate=1.0)
    rifl, dot = (7, 1), (2, 9)
    tracer.span("submit", rifl, cid=7)
    clock.add_millis(5)
    tracer.span("payload", rifl, dot=dot, pid=2)
    tracer.span("path", rifl, dot=dot, pid=2, meta={"path": "fast"})
    clock.add_millis(5)
    tracer.span("commit", rifl, dot=dot, pid=2)
    tracer.span("ready", rifl, pid=2, meta={"batch": 1})
    tracer.span("executed", rifl, pid=2)
    clock.add_millis(5)
    tracer.span("reply", rifl, cid=7)
    tracer.counter("table_plane_dispatches", 4, pid=2)
    tracer.close()

    events = read_trace(path)
    # 8 emitted events + the clock-domain header line
    assert len(events) == 9
    assert events[0] == {"k": "hdr", "clock": "virtual", "v": 1}
    spans = assemble_spans(events)
    assert len(spans) == 1
    span = spans[rifl]
    assert span["dot"] == dot
    assert list(span["stages"]) == [
        "submit", "payload", "path", "commit", "ready", "executed", "reply"
    ]
    assert span["meta"]["path"] == {"path": "fast"}

    perfetto = to_perfetto(events)
    validate_perfetto(perfetto)
    # survives a real serialize/parse round trip (what the viewer loads)
    validate_perfetto(json.loads(json.dumps(perfetto)))
    names = {ev["name"] for ev in perfetto["traceEvents"]}
    assert "submit->payload" in names and "table_plane_dispatches" in names

    # crash consistency: a torn final line is dropped on read
    with open(path, "a") as fh:
        fh.write('{"k":"span","stage":"reply","rifl":[7,')
    assert len(read_trace(path)) == 9


def test_span_assembly_survives_crashed_coordinator():
    """Stages the coordinator never emitted (it crashed; recovery
    committed the dot elsewhere) fall back to the earliest replica
    observation instead of vanishing, and the out-of-chain recovery
    stage is kept whatever pid emitted it — while on the healthy path
    the coordinator's timeline still beats replica re-observations."""
    from fantoch_tpu.observability.report import assemble_spans

    rifl, dot = [7, 1], [1, 5]

    def ev(stage, t, pid=None, cid=None, meta=None):
        e = {"k": "span", "stage": stage, "rifl": rifl, "t": t}
        if pid is not None:
            e["pid"] = pid
        if cid is not None:
            e["cid"] = cid
        if meta is not None:
            e["m"] = meta
        return e

    # coordinator p1 emitted payload then crashed; p2 recovered the dot
    crashed = [
        ev("submit", 0, cid=7),
        {**ev("payload", 10, pid=1), "dot": dot},
        ev("recovery", 30, pid=2, meta={"ballot": 12}),
        ev("commit", 40, pid=2),
        ev("commit", 45, pid=3),  # later replica: earliest fallback wins
        ev("ready", 50, pid=2),
        ev("executed", 60, pid=2),
        ev("reply", 80, cid=7),
    ]
    span = assemble_spans(crashed)[tuple(rifl)]
    assert span["stages"] == {
        "submit": 0, "payload": 10, "recovery": 30, "commit": 40,
        "ready": 50, "executed": 60, "reply": 80,
    }
    assert span["meta"]["recovery"] == {"ballot": 12}
    assert span["pid"] == 1  # the span still lives on the dot's home track

    # healthy path: the coordinator's commit replaces a replica's even
    # when the replica's landed first in the log
    healthy = [
        {**ev("payload", 10, pid=1), "dot": dot},
        ev("commit", 38, pid=2),
        ev("commit", 40, pid=1),
        ev("commit", 39, pid=3),
    ]
    span = assemble_spans(healthy)[tuple(rifl)]
    assert span["stages"]["commit"] == 40


def test_deterministic_sampling(tmp_path):
    """Same seed => same sampled dot set, at any rate; the sampled set is
    exactly the span_hash threshold set (no RNG involved)."""
    from fantoch_tpu.observability.report import assemble_spans
    from fantoch_tpu.observability.tracer import (
        Tracer,
        read_trace,
        span_hash,
    )

    _traced_sim(tmp_path / "a.jsonl", seed=5, sample_rate=0.5)
    _traced_sim(tmp_path / "b.jsonl", seed=5, sample_rate=0.5)
    _traced_sim(tmp_path / "full.jsonl", seed=5, sample_rate=1.0)

    sampled_a = set(assemble_spans(read_trace(tmp_path / "a.jsonl")))
    sampled_b = set(assemble_spans(read_trace(tmp_path / "b.jsonl")))
    full = set(assemble_spans(read_trace(tmp_path / "full.jsonl")))
    assert sampled_a == sampled_b
    assert sampled_a <= full
    # the sampled set is exactly what the hash threshold predicts
    threshold = int(0.5 * (1 << 32))
    assert sampled_a == {r for r in full if span_hash(*r) < threshold}
    # rate edges
    from fantoch_tpu.core.timing import SimTime

    off = Tracer(SimTime(), str(tmp_path / "off.jsonl"), sample_rate=0.0)
    assert not off.sample((1, 1))
    on = Tracer(SimTime(), str(tmp_path / "on.jsonl"), sample_rate=1.0)
    assert all(on.sample((s, q)) for s in range(1, 5) for q in range(1, 50))


def test_sim_same_seed_traces_identical(tmp_path):
    """Two same-seed sim runs produce byte-identical span logs and an
    empty obs diff (the acceptance-criterion determinism property)."""
    from fantoch_tpu.observability.report import diff_events
    from fantoch_tpu.observability.tracer import read_trace

    _traced_sim(tmp_path / "a.jsonl", seed=11)
    _traced_sim(tmp_path / "b.jsonl", seed=11)
    with open(tmp_path / "a.jsonl", "rb") as fa, \
            open(tmp_path / "b.jsonl", "rb") as fb:
        assert fa.read() == fb.read()
    assert diff_events(
        read_trace(tmp_path / "a.jsonl"), read_trace(tmp_path / "b.jsonl")
    ) == []
    # the diff is not vacuously empty: reorder jitter (drawn from the
    # runner RNG) shifts delivery times, so span timestamps change —
    # while two same-seed reordered runs still match byte for byte.
    # (a bare seed change is NOT trace-visible here: it only picks which
    # keys conflict, and this closed-loop workload never overlaps
    # conflicting commands in flight, so timing is identical)
    _traced_sim(tmp_path / "c.jsonl", seed=11, reorder=True)
    _traced_sim(tmp_path / "d.jsonl", seed=11, reorder=True)
    assert diff_events(
        read_trace(tmp_path / "a.jsonl"), read_trace(tmp_path / "c.jsonl")
    )
    with open(tmp_path / "c.jsonl", "rb") as fc, \
            open(tmp_path / "d.jsonl", "rb") as fd:
        assert fc.read() == fd.read()


def test_sim_same_seed_traces_identical_with_ingest_batching(tmp_path):
    """r16: the adaptive ingest batcher rides the sim's virtual clock
    (run/ingest.py injects time), so two same-seed runs with batching ON
    stay byte-identical — span logs included — and every span still
    covers the full canonical chain with monotonic stages.  The batched
    trace is not vacuously equal to the unbatched one: held commands
    shift their ingest (and later) stamps."""
    from fantoch_tpu.observability.report import (
        assemble_spans,
        diff_events,
        monotonic_violations,
    )
    from fantoch_tpu.observability.tracer import STAGES, read_trace

    _traced_sim(tmp_path / "a.jsonl", seed=11, ingest_deadline_ms=5.0)
    _traced_sim(tmp_path / "b.jsonl", seed=11, ingest_deadline_ms=5.0)
    with open(tmp_path / "a.jsonl", "rb") as fa, \
            open(tmp_path / "b.jsonl", "rb") as fb:
        assert fa.read() == fb.read()
    events = read_trace(tmp_path / "a.jsonl")
    assert diff_events(events, read_trace(tmp_path / "b.jsonl")) == []
    spans = assemble_spans(events)
    assert len(spans) == 3 * 2 * 4  # one span per committed command
    assert monotonic_violations(spans) == []
    for span in spans.values():
        assert set(span["stages"]) == set(STAGES)
    # ...and batching is observably ON vs the legacy run: a nonzero
    # payload->ingest hold exists somewhere, or at minimum the event
    # streams differ (the closed-loop trickle may release everything
    # via the cold-target fast path, but never silently diverge)
    _traced_sim(tmp_path / "off.jsonl", seed=11)
    off_spans = assemble_spans(read_trace(tmp_path / "off.jsonl"))
    assert set(off_spans) == set(spans)


def test_sim_trace_stage_breakdown_matches_client_latency(tmp_path):
    """The acceptance criterion: with trace_sample_rate=1.0, a 3-process
    EPaxos sim at 50%% conflict yields a span per committed command with
    monotonic stage timestamps, and the per-stage segments sum exactly to
    the client-observed latency histogram."""
    from fantoch_tpu.observability.report import (
        assemble_spans,
        monotonic_violations,
        span_segments,
        summarize,
    )
    from fantoch_tpu.observability.tracer import STAGES, read_trace

    _metrics, _monitors, latencies = _traced_sim(
        tmp_path / "t.jsonl", seed=21, commands_per_client=5,
        clients_per_process=2,
    )
    events = read_trace(tmp_path / "t.jsonl")
    spans = assemble_spans(events)
    committed = 3 * 2 * 5
    assert len(spans) == committed, "one span per committed command"
    assert monotonic_violations(spans) == []

    # every span covers the full canonical chain, and its segments
    # telescope exactly to reply - submit
    span_ms = []
    for span in spans.values():
        assert set(span["stages"]) == set(STAGES), span
        segments = span_segments(span)
        total = sum(tb - ta for _name, ta, tb in segments)
        end_to_end = span["stages"]["reply"] - span["stages"]["submit"]
        assert total == end_to_end
        span_ms.append(end_to_end // 1000)

    # ...and the end-to-end set IS the client-observed latency histogram
    client_ms = []
    for _region, (_commands, hist) in latencies.items():
        client_ms.extend(hist.all_values())
    assert sorted(span_ms) == sorted(client_ms)

    report = summarize(events)
    assert report["spans"] == committed
    assert report["end_to_end"]["count"] == committed
    assert all(count == committed for count in report["stage_coverage"].values())
    # per-stage percentile means sum to at most the end-to-end mean
    seg_mean = sum(row["mean_us"] for row in report["segments"].values())
    assert abs(seg_mean - report["end_to_end"]["mean_us"]) < 1.0


def test_localhost_trace_covers_lifecycle(tmp_path):
    """A real localhost EPaxos run with tracing on produces spans covering
    every lifecycle stage, readable across the per-process + client span
    logs (the run half of the shared-schema property)."""
    from fantoch_tpu.observability.report import (
        assemble_spans,
        monotonic_violations,
    )
    from fantoch_tpu.observability.tracer import STAGES, read_trace

    config = Config(
        n=3,
        f=1,
        gc_interval_ms=50,
        executor_executed_notification_interval_ms=50,
        trace_sample_rate=1.0,
    )
    workload = Workload(
        shard_count=1,
        key_gen=ConflictRateKeyGen(50),
        keys_per_command=2,
        commands_per_client=5,
        payload_size=1,
    )
    runtimes, clients = asyncio.run(
        run_localhost_cluster(
            EPaxos,
            config,
            workload,
            clients_per_process=1,
            extra_run_time_ms=400,
            observe_dir=str(tmp_path),
        )
    )
    assert all(c.issued_commands == 5 for c in clients.values())
    paths = sorted(glob.glob(str(tmp_path / "trace_*.jsonl")))
    assert len(paths) == 4, paths  # 3 process logs + the client plane
    events = []
    for path in paths:
        events.extend(read_trace(path))
    spans = assemble_spans(events)
    assert len(spans) == 15
    for span in spans.values():
        assert set(span["stages"]) == set(STAGES), span
    assert monotonic_violations(spans) == []


def test_snapshot_names_the_backend_that_served(tmp_path):
    """A process whose executors dispatch to a device says which device
    in every snapshot (``backend``: platform, device_kind, device_count,
    mesh_shape) — a host-only process carries neither ``device`` nor
    ``backend``."""
    workload = Workload(
        shard_count=1, key_gen=ConflictRateKeyGen(50), keys_per_command=1,
        commands_per_client=5, payload_size=1,
    )
    for device_pred_plane in (True, False):
        config = Config(
            n=3, f=1, gc_interval_ms=50,
            executor_executed_notification_interval_ms=50,
            device_pred_plane=device_pred_plane,
        )
        out_dir = tmp_path / f"plane_{device_pred_plane}"
        out_dir.mkdir()
        from fantoch_tpu.protocol import Caesar

        asyncio.run(
            run_localhost_cluster(
                Caesar, config, workload, clients_per_process=1,
                extra_run_time_ms=400, observe_dir=str(out_dir),
            )
        )
        snaps = sorted(glob.glob(str(out_dir / "metrics_p*.gz")))
        assert len(snaps) == 3
        for path in snaps:
            snap = read_metrics_snapshot(path)
            if device_pred_plane:
                assert snap.device["pred_plane_dispatches"] > 0
                assert snap.backend["platform"] == "cpu"
                assert set(snap.backend) == {
                    "platform", "device_kind", "device_count", "mesh_shape"
                }
            else:
                assert snap.device is None and snap.backend is None


def test_a_capture_holds_the_round_stages_and_no_python_frames(tmp_path):
    """``capture_device_profile`` runs without the python tracer (which
    slows the host it measures several times over) and with the host
    tracer, so the served round's ``fantoch/*`` stage annotations land in
    the capture beside the runtime's own events."""
    from jax.profiler import ProfileData

    from fantoch_tpu.observability.exposition import capture_device_profile
    from fantoch_tpu.run.harness import run_device_server

    config = Config(3, 1, shard_count=1, serving_pipeline_depth=1)  # overlap, on the CPU too
    workload = Workload(
        shard_count=1, key_gen=ConflictRateKeyGen(50), keys_per_command=1,
        commands_per_client=10, payload_size=1,
    )

    async def serve():
        return await run_device_server(
            config, workload, client_count=4, batch_size=8,
            open_loop_interval_ms=1,
        )

    async def go():
        await serve()  # the first run compiles (or loads) the round
        capture = asyncio.ensure_future(capture_device_profile(str(tmp_path), 1500))
        await asyncio.sleep(0.05)
        await serve()
        return await capture

    reply = asyncio.run(go())
    assert reply.get("ms") == 1500 and reply["path"].startswith(str(tmp_path)), reply
    found = glob.glob(reply["path"] + "/**/*.xplane.pb", recursive=True)
    assert len(found) == 1
    names = [
        event.name
        for plane in ProfileData.from_file(found[0]).planes
        for line in plane.lines
        for event in line.events
    ]
    stages = {name.split("#", 1)[0] for name in names if name.startswith("fantoch/")}
    assert {"fantoch/step", "fantoch/deliver", "fantoch/round", "fantoch/fetch",
            "fantoch/execute", "fantoch/assemble", "fantoch/enqueue"} <= stages
    # the python tracer names its frames "$file:line function"
    assert not [name for name in names if name.startswith("$")]
    assert len(names) > len([n for n in names if n.startswith("fantoch/")])  # the runtime's own
