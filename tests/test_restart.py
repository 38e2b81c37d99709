"""Restart & rejoin plane: replicas return to service instead of staying
dead.

PR 3 made crashes heal by routing *around* the corpse — every crash
permanently burned one unit of the n-f budget.  These tests drive the
restart plane through the stronger claim:

* **Restored tolerance** (the acceptance rows) — crash p_a with a
  scheduled restart, let it rejoin (durable image + MSync catch-up +
  vote backfill; MSlotSync slot streaming for FPaxos), then crash p_b
  *forever*.  Without the restart the combined failures exceed ``f``
  and the run must stall; with it, every client not attached to the
  dead-forever replica completes and the execution-order monitors agree
  (exactly-once across the restart: a re-executed command would break
  write-order agreement).  All five protocols run these rows — Caesar
  and FPaxos joined in PR 12.
* **Restart determinism** — same seed twice => byte-identical nemesis
  traces AND byte-identical span logs through crash, durable-image
  capture, restore, and rejoin.
* **Device planes rebuild** — a TableExecutor with the device table
  plane restores from its pickled host mirror: ONE re-upload
  (``resident_uploads``), bit-for-bit KV parity with an uncrashed run.
* **Pipelined serving** — rounds in flight in a depth-2 pipeline at
  crash time are re-fed from the log on recovery and come out
  exactly-once, in order.
* **Run layer** — a killed ProcessRuntime restarts from its WAL
  (snapshot + tail), peers detect it (``on_peer_up``: incarnation-keyed
  link-dedup reset, writer revival), MSync pulls the commits it missed,
  and it serves clients again; monitors agree across all three lives.
"""

import asyncio
import hashlib
import os

import pytest

from fantoch_tpu.client import ConflictRateKeyGen, Workload
from fantoch_tpu.core import Command, Config, Dot, KVOp, Planet, Rifl
from fantoch_tpu.core.planet import Region
from fantoch_tpu.core.timing import SimTime
from fantoch_tpu.protocol import Atlas, Caesar, EPaxos, FPaxos, Newt
from fantoch_tpu.sim import Runner
from fantoch_tpu.sim.faults import FaultPlan

from harness import check_monitors

pytestmark = [pytest.mark.chaos, pytest.mark.restart]

COMMANDS_PER_CLIENT = 10 if os.environ.get("CI") else 15
CLIENTS_PER_PROCESS = 2


def flat_planet(n):
    """Near-equidistant regions: every crashed replica sits inside live
    fast quorums (the recovery rows' far=0 topology)."""
    regions = [Region(f"r{i}") for i in range(n)]
    latencies = {
        a: {b: (0 if i == j else 10 + abs(i - j)) for j, b in enumerate(regions)}
        for i, a in enumerate(regions)
    }
    return regions, Planet.from_latencies(latencies)


def restart_sim(
    protocol_cls,
    config: Config,
    plan: FaultPlan,
    commands_per_client: int = COMMANDS_PER_CLIENT,
    seed: int = 0,
    trace_path=None,
):
    n = config.n
    regions, planet = flat_planet(n)
    config = config.with_(
        executor_monitor_execution_order=True,
        executor_monitor_pending_interval_ms=500,
        gc_interval_ms=100,
        executor_executed_notification_interval_ms=100,
        shard_count=1,
    )
    workload = Workload(
        shard_count=1,
        key_gen=ConflictRateKeyGen(100),
        keys_per_command=1,
        commands_per_client=commands_per_client,
        payload_size=1,
    )
    runner = Runner(
        protocol_cls,
        planet,
        config,
        workload,
        CLIENTS_PER_PROCESS,
        process_regions=regions,
        client_regions=list(regions),
        seed=seed,
        fault_plan=plan,
        trace_path=trace_path,
    )
    metrics, monitors, _latencies = runner.run(extra_sim_time_ms=2000)
    return runner, monitors


def assert_restored_tolerance(runner, monitors, restarted, dead_forever, commands):
    """Every client not attached to a dead-forever replica — including
    the restarted one's — completed; surviving monitors agree (a command
    re-executed across the restart would break write-order agreement)."""
    kinds = {kind for _t, kind, _d in runner.nemesis.trace}
    assert {"crash", "durable-image", "restart"} <= kinds
    dead = set(dead_forever)
    for client_id, client in runner._simulation.clients():
        if client.targets() & dead:
            continue
        assert client.issued_commands == commands, (
            f"client {client_id} (targets {client.targets()}) finished "
            f"{client.issued_commands}/{commands} after p{sorted(dead)} died"
        )
    check_monitors({pid: m for pid, m in monitors.items() if pid not in dead})


# --- acceptance rows: restart restores the tolerance budget ---

RESTART_33 = Config(3, 1, recovery_delay_ms=1000)
# p2 crashes and restarts; p3 then dies for good.  Without the restart
# this is 2 > f=1 dead (test_recovery_below_quorum_is_still_bounded's
# stall); with it the mesh is back to full strength when p3 dies.
PLAN_33 = (
    FaultPlan(seed=1, max_sim_time_ms=300_000)
    .with_loss(0.1)
    .with_crash(2, at_ms=150, restart_at_ms=2500)
    .with_crash(3, at_ms=3200)
)


@pytest.mark.parametrize(
    "protocol_cls,config",
    [
        (EPaxos, RESTART_33),
        (Atlas, RESTART_33),
        (Newt, RESTART_33.with_(newt_detached_send_interval_ms=100)),
        # Caesar: snapshot/restore + MSync rejoin over the (clock, preds)
        # commit records (PR 12 closed the restart carve-out)
        (Caesar, RESTART_33.with_(executor_monitor_pending_interval_ms=500)),
    ],
    ids=["epaxos", "atlas", "newt", "caesar"],
)
def test_restart_restores_tolerance_33(protocol_cls, config):
    runner, monitors = restart_sim(protocol_cls, config, PLAN_33)
    assert_restored_tolerance(
        runner, monitors, restarted=[2], dead_forever=[3],
        commands=COMMANDS_PER_CLIENT,
    )


def test_fpaxos_restart_restores_tolerance_33():
    """FPaxos: the LEADER crash-restarts (followers elect, the stale
    restored leader is demoted by the higher-ballot heartbeat and its
    stranded commanders re-forward), MSlotSync pulls the chosen slots it
    missed, and a follower then dies for good — survivable only because
    the restarted replica is back in the write quorum."""
    config = Config(3, 1, leader=1, fpaxos_leader_timeout_ms=400)
    plan = (
        FaultPlan(seed=1, max_sim_time_ms=300_000)
        .with_loss(0.1)
        .with_crash(1, at_ms=150, restart_at_ms=2500)
        .with_crash(3, at_ms=3200)
    )
    runner, monitors = restart_sim(FPaxos, config, plan)
    assert_restored_tolerance(
        runner, monitors, restarted=[1], dead_forever=[3],
        commands=COMMANDS_PER_CLIENT,
    )


def test_fpaxos_follower_restart_inflight_accepts_redriven():
    """A write-quorum FOLLOWER crash-restarts: the MAccepts that
    evaporated during its downtime are re-driven by the leader's
    periodic in-flight sweep (no failure detector ever fires for a
    restarting peer), so the stuck slots — and everything ordered after
    them — complete (the fuzzer-found follower-restart stall)."""
    config = Config(3, 1, leader=1, fpaxos_leader_timeout_ms=400)
    plan = (
        FaultPlan(seed=3, max_sim_time_ms=300_000)
        .with_crash(2, at_ms=200, restart_at_ms=900)
    )
    runner, monitors = restart_sim(FPaxos, config, plan)
    assert_restored_tolerance(
        runner, monitors, restarted=[2], dead_forever=[],
        commands=COMMANDS_PER_CLIENT,
    )


def test_restart_restores_tolerance_52():
    """n=5/f=2: p2 crash-restarts, then p4 AND p5 die for good — three
    crashed processes overall, survivable only because p2 came back."""
    plan = (
        FaultPlan(seed=13, max_sim_time_ms=600_000)
        .with_loss(0.1)
        .with_crash(2, at_ms=150, restart_at_ms=3000)
        .with_crash(4, at_ms=4500)
        .with_crash(5, at_ms=4500)
    )
    runner, monitors = restart_sim(EPaxos, Config(5, 2, recovery_delay_ms=1500), plan)
    assert_restored_tolerance(
        runner, monitors, restarted=[2], dead_forever=[4, 5],
        commands=COMMANDS_PER_CLIENT,
    )


@pytest.mark.slow
@pytest.mark.parametrize("loss", [0.1, 0.3])
@pytest.mark.parametrize(
    "protocol_cls,config",
    [
        (EPaxos, Config(5, 2, recovery_delay_ms=1500)),
        (Atlas, Config(5, 2, recovery_delay_ms=1500)),
        (
            Newt,
            Config(5, 2, recovery_delay_ms=1500, newt_detached_send_interval_ms=100),
        ),
        (
            Caesar,
            Config(
                5, 2, recovery_delay_ms=1500,
                executor_monitor_pending_interval_ms=500,
            ),
        ),
    ],
    ids=["epaxos", "atlas", "newt", "caesar"],
)
def test_restart_matrix_52(protocol_cls, config, loss):
    """Acceptance matrix: crash-restart + subsequent double crash at
    n=5/f=2 under 10-30% loss, across EPaxos/Atlas/Newt."""
    plan = (
        FaultPlan(seed=13, max_sim_time_ms=600_000)
        .with_loss(loss)
        .with_crash(2, at_ms=150, restart_at_ms=3000)
        .with_crash(4, at_ms=4500)
        .with_crash(5, at_ms=4500)
    )
    runner, monitors = restart_sim(protocol_cls, config, plan)
    assert_restored_tolerance(
        runner, monitors, restarted=[2], dead_forever=[4, 5],
        commands=COMMANDS_PER_CLIENT,
    )


# --- determinism: restart decisions replay byte-identically ---


def test_critpath_blame_survives_crash_restart(tmp_path):
    """Critical-path satellite: the PR 5 restart rows assert span-log
    byte identity, but never ASSEMBLE attribution across a crash.  This
    row does: the span log spans all three lives (crash, durable image,
    restore + rejoin) and every assembled blame vector still telescopes
    EXACTLY to reply - submit, with cross-process quorum edges resolved
    for the stitched spans."""
    from fantoch_tpu.observability.critpath import critpath_report
    from fantoch_tpu.observability.tracer import read_trace

    # recovery on, like every restored-tolerance row: a dot whose
    # MCollect was in flight at the crash instant only commits via
    # recovery consensus
    config = Config(3, 1, recovery_delay_ms=1000, trace_sample_rate=1.0)
    plan = FaultPlan(max_sim_time_ms=300_000).with_crash(
        1, at_ms=150, restart_at_ms=700
    )
    path = str(tmp_path / "restart.jsonl")
    runner, _monitors = restart_sim(EPaxos, config, plan, trace_path=path)
    kinds = {kind for _t, kind, _d in runner.nemesis.trace}
    assert {"crash", "durable-image", "restart"} <= kinds
    report = critpath_report(read_trace(path))
    assert report["spans"] > 0
    # exactness survives the crash: no vector may mis-telescope, even
    # ones whose stages straddle the restart
    assert report["telescoping_violations"] == 0
    # most spans still stitch (in-flight hops dropped AT the crash
    # instant legitimately lose their recv half)
    assert report["stitch_rate"] >= 0.9
    assert report["quorum_blame"]


def test_critpath_names_recovery_stage_for_crashed_coordinator(tmp_path):
    """A crashed-forever coordinator's in-flight dots heal by recovery
    consensus — and the blame vector must NAME that detour: the span
    keeps the out-of-chain recovery stage and the attribution carries
    ``blame["recovery"]`` with the entry point and the detour-to-commit
    wall."""
    from fantoch_tpu.observability.critpath import (
        OffsetTable,
        attribute_span,
        commit_times,
        estimate_client_offsets,
        match_edges,
    )
    from fantoch_tpu.observability.report import assemble_spans
    from fantoch_tpu.observability.tracer import read_trace

    config = Config(
        3, 1, recovery_delay_ms=300,
        trace_sample_rate=1.0,
    )
    plan = FaultPlan(max_sim_time_ms=300_000).with_crash(1, at_ms=120)
    path = str(tmp_path / "recover.jsonl")
    restart_sim(EPaxos, config, plan, trace_path=path)
    events = read_trace(path)
    spans = assemble_spans(events)
    recovered = [
        span for span in spans.values() if "recovery" in span["stages"]
    ]
    assert recovered, "a crashed-coordinator dot must enter recovery"
    dot_edges, client_edges = match_edges(events)
    offsets = OffsetTable(events, wall=False)
    client_off = estimate_client_offsets(spans, client_edges, wall=False)
    commits = commit_times(events)
    for span in recovered:
        vector = attribute_span(
            span, dot_edges, client_edges, offsets, client_off, commits
        )
        detour = vector["blame"]["recovery"]
        assert detour["entered_us"] == span["stages"]["recovery"]
        if "commit" in span["stages"]:
            assert detour["to_commit_us"] >= 0


def test_restart_determinism_and_trace_byte_identity(tmp_path):
    """Same seed twice through crash + durable image + restore + rejoin
    => identical nemesis traces, identical committed orders, and
    byte-identical span logs (the tracer survives the restart because
    restore() reattaches it and virtual time is shared)."""
    config = Config(
        3, 1, recovery_delay_ms=1000, newt_detached_send_interval_ms=100,
        trace_sample_rate=1.0,
    )
    plan = (
        FaultPlan(seed=1, max_sim_time_ms=300_000)
        .with_loss(0.1)
        .with_crash(2, at_ms=150, restart_at_ms=2500)
        .with_crash(3, at_ms=3000)
    )

    def one(tag):
        path = str(tmp_path / f"trace_{tag}.jsonl")
        runner, monitors = restart_sim(
            Newt, config, plan, commands_per_client=10, trace_path=path
        )
        committed = {pid: repr(m) for pid, m in monitors.items()}
        with open(path, "rb") as fh:
            blob = fh.read()
        return (
            runner.nemesis.trace_digest(),
            committed,
            hashlib.sha256(blob).hexdigest(),
            {kind for _t, kind, _d in runner.nemesis.trace},
        )

    digest_a, committed_a, trace_a, kinds = one("a")
    digest_b, committed_b, trace_b, _ = one("b")
    assert digest_a == digest_b
    assert committed_a == committed_b
    assert trace_a == trace_b
    # non-vacuous: the restart machinery actually ran ("defer-restart"
    # depends on a client submit being in flight at the crash instant,
    # which this workload shape does not guarantee)
    assert {"durable-image", "restart"} <= kinds


@pytest.mark.parametrize(
    "protocol_cls,config,plan",
    [
        (
            Caesar,
            Config(
                3, 1, recovery_delay_ms=1000,
                executor_monitor_pending_interval_ms=500,
                trace_sample_rate=1.0,
            ),
            PLAN_33,
        ),
        (
            FPaxos,
            Config(
                3, 1, leader=1, fpaxos_leader_timeout_ms=400,
                trace_sample_rate=1.0,
            ),
            FaultPlan(seed=1, max_sim_time_ms=300_000)
            .with_loss(0.1)
            .with_crash(1, at_ms=150, restart_at_ms=2500),
        ),
    ],
    ids=["caesar", "fpaxos"],
)
def test_new_protocol_restart_byte_identity(tmp_path, protocol_cls, config, plan):
    """The PR 7 determinism invariant, extended to the two protocols that
    joined the restart matrix in PR 12: same seed twice through Caesar
    crash + (clock, preds) recovery + restart, and FPaxos leader
    crash-restart + MSlotSync catch-up => byte-identical nemesis traces,
    committed orders, AND span logs."""

    def one(tag):
        path = str(tmp_path / f"trace_{protocol_cls.__name__}_{tag}.jsonl")
        runner, monitors = restart_sim(
            protocol_cls, config, plan, commands_per_client=10, trace_path=path
        )
        committed = {pid: repr(m) for pid, m in monitors.items()}
        with open(path, "rb") as fh:
            blob = fh.read()
        return (
            runner.nemesis.trace_digest(),
            committed,
            hashlib.sha256(blob).hexdigest(),
            {kind for _t, kind, _d in runner.nemesis.trace},
        )

    digest_a, committed_a, trace_a, kinds = one("a")
    digest_b, committed_b, trace_b, _ = one("b")
    assert digest_a == digest_b
    assert committed_a == committed_b
    assert trace_a == trace_b
    # non-vacuous: the restart machinery actually ran
    assert {"crash", "durable-image", "restart"} <= kinds


def test_fpaxos_on_peer_up_refreshes_targets():
    """Protocol-level on_peer_up: the returned peer re-enters the
    election candidate ring and pending forwards are re-sent to the
    leader (frames queued while it was declared dead were dropped)."""
    from fantoch_tpu.protocol.fpaxos import MForwardSubmit

    time = SimTime()
    config = Config(3, 1, leader=1, fpaxos_leader_timeout_ms=400, gc_interval_ms=100)
    follower, _ = FPaxos.new(2, 0, config)
    ok, _ = follower.discover([(2, 0), (1, 0), (3, 0)])
    assert ok
    cmd = Command.from_single(Rifl(7, 1), 0, "k", KVOp.put("v"))
    follower.submit(None, cmd, time)
    first = [a for a in follower.to_processes_iter()]
    assert any(isinstance(a.msg, MForwardSubmit) for a in first)
    follower.on_peer_down(3, time)
    assert 3 in follower._down
    follower.on_peer_up(3, time)
    assert 3 not in follower._down
    reforwards = [
        a for a in follower.to_processes_iter() if isinstance(a.msg, MForwardSubmit)
    ]
    assert len(reforwards) == 1, "the pending forward must be re-sent"
    assert reforwards[0].target == {1}


# --- device planes rebuild from the restored host mirror ---


def test_device_table_plane_rebuilds_after_restore():
    """Acceptance: restart costs the table plane exactly ONE host->device
    re-upload (``resident_uploads``), and the restored executor's KV
    state is bit-for-bit the uncrashed run's."""
    from fantoch_tpu.core import RunTime
    from fantoch_tpu.executor.table import TableExecutor, TableVotes
    from fantoch_tpu.protocol.common.table_clocks import VoteRange

    n = 3
    config = Config(
        n, 1, device_table_plane=True, executor_monitor_execution_order=True
    )
    time = RunTime()

    def rounds():
        out = []
        seq = 0
        for r in range(6):
            infos = []
            for k in range(3):
                seq += 1
                clock = r + 1
                infos.append(
                    TableVotes(
                        Dot(1, seq), clock, Rifl(1, seq), f"key{k}",
                        (KVOp.put(f"v{seq}"),),
                        [VoteRange(p, 1, clock) for p in range(1, n + 1)],
                    )
                )
            out.append(infos)
        return out

    # uncrashed reference
    reference = TableExecutor(1, 0, config)
    for infos in rounds():
        reference.handle_batch(list(infos), time)
    ref_results = sorted((r.rifl, r.key, r.op_results) for r in reference.to_clients_iter())

    # crashed run: snapshot mid-stream, restore, continue
    executor = TableExecutor(1, 0, config)
    all_rounds = rounds()
    results = []
    for infos in all_rounds[:3]:
        executor.handle_batch(list(infos), time)
    results.extend(executor.to_clients_iter())
    uploads_before = executor._plane.resident_uploads
    assert uploads_before == 1, "steady state is one initial upload"
    blob = executor.snapshot()
    restored = TableExecutor.restore(blob)
    assert restored._plane.resident_uploads == uploads_before
    for infos in all_rounds[3:]:
        restored.handle_batch(list(infos), time)
    results.extend(restored.to_clients_iter())
    assert restored._plane.resident_uploads == uploads_before + 1, (
        "recovery must cost exactly one re-upload, not one per batch"
    )
    assert sorted((r.rifl, r.key, r.op_results) for r in results) == ref_results
    # bit-for-bit final state parity
    assert restored._store._store == reference._store._store
    import numpy as np

    np.testing.assert_array_equal(
        restored._plane.frontiers(), reference._plane.frontiers()
    )


# --- depth-2 pipelined serving: in-flight rounds replay exactly-once ---


def test_pipelined_in_flight_rounds_replay_exactly_once():
    """Crash with two rounds dispatched-but-undrained in a depth-2
    pipeline: recovery rebuilds the driver and re-feeds the logged
    rounds; results come out exactly-once and in order (the WAL's
    append-before-dispatch discipline at the pipeline seam)."""
    from fantoch_tpu.run.pipeline import PipelineCore

    class Driver(PipelineCore):
        def __init__(self):
            self.batch_size = 8
            self._init_pipeline()
            self._round = 0
            self.executed = []

        def dispatch(self, batch):
            token = (self._round, list(batch))
            self._round += 1
            return token

        def drain(self, token):
            round_index, batch = token
            results = []
            for item in batch:
                if item in self.executed:
                    continue  # the rifl-dedup seam
                self.executed.append(item)
                results.append((round_index, item))
            return results

    wal_log = []  # (round items) appended BEFORE dispatch, like the WAL

    live = Driver()
    live.pipeline_depth = 2
    emitted = []
    for round_items in (["a1", "a2"], ["b1"], ["c1", "c2"], ["d1"]):
        wal_log.append(round_items)
        emitted.extend(live.serve([round_items], overlap=True))
    # depth 2: the last two rounds are still in flight — crash now
    assert len(live._inflight) == 2
    drained_rifls = [item for _r, item in emitted]

    recovered = Driver()
    recovered.pipeline_depth = 2
    recovered.executed = list(drained_rifls)  # the durable executed log
    replayed = []
    for round_items in wal_log:
        replayed.extend(recovered.serve([round_items], overlap=True))
    replayed.extend(recovered.flush_pipeline())
    replayed_rifls = [item for _r, item in replayed]
    # exactly-once: every command executes once across both lives,
    # including the two rounds that were in flight at the crash
    assert drained_rifls + replayed_rifls == ["a1", "a2", "b1", "c1", "c2", "d1"]
    assert recovered.executed == ["a1", "a2", "b1", "c1", "c2", "d1"]


def test_recovery_replay_advances_horizon_and_computes_lease_gap(tmp_path):
    """Boot-time recovery invariants, unit-level: (1) replayed tail
    commit dots fold into the restored protocol's committed clock (the
    rejoin horizon), and (2) the dot-lease's unissued remainder is
    computed as the gap recovery must commit (as noops) on rejoin — an
    unfilled own-source gap would freeze the mesh's contiguous stable
    frontier (and therefore GC) forever."""
    from fantoch_tpu.executor.graph.executor import GraphAdd
    from fantoch_tpu.run.harness import free_port
    from fantoch_tpu.run.process_runner import ProcessRuntime
    from fantoch_tpu.run.wal import DOT_LEASE_BATCH, Wal

    wal_dir = tmp_path / "p3"
    wal = Wal(str(wal_dir), sync="always")
    wal.recover()
    for sequence in (1, 2):
        cmd = Command.from_single(
            Rifl(9, sequence), 0, f"k{sequence}", KVOp.put("v")
        )
        wal.append("info", GraphAdd(Dot(3, sequence), cmd, set()))
    wal.append_lease(2 + DOT_LEASE_BATCH)
    wal.close()

    config = Config(3, 1, recovery_delay_ms=500, gc_interval_ms=50)
    runtime = ProcessRuntime(
        EPaxos, 3, 0, config,
        listen_addr=("127.0.0.1", free_port()),
        client_addr=("127.0.0.1", free_port()),
        peers={},
        sorted_processes=[(3, 0), (1, 0), (2, 0)],
        wal_dir=str(wal_dir),
    )
    assert runtime._recovered
    assert runtime.wal_replayed_infos == 2
    # (1) the horizon covers the replayed commits — MSync must not
    # re-fetch them (re-applying would execute twice)
    assert runtime.process._gc_track.contains(Dot(3, 1))
    assert runtime.process._gc_track.contains(Dot(3, 2))
    # (2) the lease gap is exactly the unissued/uncommitted remainder
    gap = runtime._lease_gap_dots
    assert gap == [Dot(3, s) for s in range(3, 2 + DOT_LEASE_BATCH + 1)]
    # and the allocator resumes above the lease
    assert runtime.next_dot().sequence == 2 + DOT_LEASE_BATCH + 1


def test_sync_backfill_barrier_holds_until_records_applied():
    """The rejoin backfill barrier (fuzzer-found, soak seed 99): a peer's
    frontier backfill arriving BEFORE its own record chunks (delivery
    reorders under fault plans) must be HELD — releasing the consumed
    ranges before the records' ops land lets timestamp stability overtake
    a commit at the rejoiner, which then executes a higher-clock command
    around a lower-clock one and diverges from live history."""
    from fantoch_tpu.core.timing import SimTime
    from fantoch_tpu.protocol.sync import MSyncBackfill, MSyncReply
    from fantoch_tpu.protocol.common.table_clocks import VoteRange, Votes
    from fantoch_tpu.protocol.newt import MDetached, Newt

    time = SimTime()
    config = Config(
        3, 1, gc_interval_ms=100, newt_detached_send_interval_ms=100,
        recovery_delay_ms=1000,
    )
    rejoiner, _ = Newt.new(3, 0, config)
    ok, _ = rejoiner.discover([(3, 0), (1, 0), (2, 0)])
    assert ok
    rejoiner.rejoin(time)
    list(rejoiner.to_processes_iter())

    backfill = Votes()
    backfill.add("K", VoteRange(1, 1, 8))
    # the backfill overtakes the records: it must be held, not applied
    rejoiner.handle(1, 0, MSyncBackfill(backfill, records=2), time)
    assert list(rejoiner.to_executors_iter()) == []
    assert rejoiner._held_backfills[1][1] == 2

    # one record applied (a committed noop — simplest valid record):
    # still below the barrier — and a DUPLICATED delivery of the same
    # chunk must not inflate the counter past it (distinct records, not
    # chunk lengths)
    rejoiner.handle(1, 0, MSyncReply([(Dot(1, 50), None, 0)]), time)
    rejoiner.handle(1, 0, MSyncReply([(Dot(1, 50), None, 0)]), time)
    drained = list(rejoiner.to_executors_iter())
    assert rejoiner._held_backfills, "one of two records is not the barrier"

    # the second record releases the backfill into the detached channel
    rejoiner.handle(1, 0, MSyncReply([(Dot(1, 51), None, 0)]), time)
    from fantoch_tpu.executor.table import TableDetachedVotes

    released = [
        info for info in rejoiner.to_executors_iter()
        if isinstance(info, TableDetachedVotes)
    ]
    assert released and not rejoiner._held_backfills
    assert any(
        any(v.start == 1 and v.end == 8 for v in info.votes)
        for info in released
    )
    # a fresh rejoin round resets the barrier state (a restored counter
    # would release a NEW backfill early)
    rejoiner.rejoin(time)
    assert rejoiner._sync_records_seen == {} and rejoiner._held_backfills == {}
    list(rejoiner.to_processes_iter())

    # the buffered-commit gate (the live-peer variant): a backfill with
    # no record stream (records=0) must still hold while a payload-less
    # buffered commit could own the covered ranges, and release once it
    # resolves (the periodic SendDetached sweep)
    from fantoch_tpu.protocol.newt import MCommit as NewtMCommit, SendDetachedEvent

    rejoiner.handle(1, 0, NewtMCommit(Dot(1, 60), 9, Votes()), time)
    assert Dot(1, 60) in rejoiner._buffered_mcommits
    rejoiner.handle(2, 0, MSyncBackfill(backfill, records=0), time)
    assert rejoiner._held_backfills, "buffered commit must gate the backfill"
    # the commit resolves (chosen-reply piggybacks the payload)
    rejoiner.handle(
        1, 0,
        NewtMCommit(
            Dot(1, 60), 9, Votes(), recovered=True,
            cmd=Command.from_single(Rifl(9, 60), 0, "K", KVOp.put("v")),
        ),
        time,
    )
    rejoiner.handle_event(SendDetachedEvent(), time)
    assert not rejoiner._held_backfills, "resolved commit must release it"


def test_caesar_wal_tail_replay_advances_horizon(tmp_path):
    """Caesar WAL tail replay: logged PredecessorsExecutionInfo records
    re-apply to the executor and their dots fold into the restored
    rejoin horizon (``note_durable_commits``) — MSync must not re-stream
    them (a second application would execute twice)."""
    from fantoch_tpu.executor.pred import PredecessorsExecutionInfo
    from fantoch_tpu.protocol.common.pred_clocks import Clock
    from fantoch_tpu.run.harness import free_port
    from fantoch_tpu.run.process_runner import ProcessRuntime
    from fantoch_tpu.run.wal import Wal

    wal_dir = tmp_path / "p3"
    wal = Wal(str(wal_dir), sync="always")
    wal.recover()
    for sequence in (1, 2):
        cmd = Command.from_single(
            Rifl(9, sequence), 0, f"k{sequence}", KVOp.put("v")
        )
        wal.append(
            "info",
            PredecessorsExecutionInfo(
                Dot(3, sequence), cmd, Clock(sequence, 3), set()
            ),
        )
    wal.close()

    config = Config(3, 1, recovery_delay_ms=500, gc_interval_ms=50)
    runtime = ProcessRuntime(
        Caesar, 3, 0, config,
        listen_addr=("127.0.0.1", free_port()),
        client_addr=("127.0.0.1", free_port()),
        peers={},
        sorted_processes=[(3, 0), (1, 0), (2, 0)],
        wal_dir=str(wal_dir),
    )
    assert runtime._recovered
    assert runtime.wal_replayed_infos == 2
    # the replayed dots settle through the durable-tail OVERLAY, not the
    # GC clock: Caesar's handle_executed REPLACES that clock with the
    # executor's executed clock, which would drop a replayed commit
    # still pending on a dependency — the overlay keeps the straggler
    # guards (and the rejoin record latch) covering them regardless
    assert runtime.process._gc_straggler(Dot(3, 1))
    assert runtime.process._gc_straggler(Dot(3, 2))
    # the effects reached the restored executor (its executed clock is
    # what drives Caesar's executed-everywhere GC after rejoin)
    executed = runtime.executors[0].executed(None)
    assert executed.contains(3, 1) and executed.contains(3, 2)
    # once the executor reports, the overlay ages out into the GC clock
    runtime.process.handle_executed(executed, None)
    assert not runtime.process._durable_tail
    assert runtime.process._gc_track.contains(Dot(3, 1))


def test_fpaxos_wal_tail_replay_advances_slot_floor(tmp_path):
    """FPaxos WAL tail replay: logged SlotExecutionInfo records fold into
    the restored chosen log + committed watermark
    (``note_durable_chosen``), so the rejoin MSlotSync floor covers them
    — peers must not re-stream slots the executor replay already
    applied.  Also pins the lease-gap guard: SlotGCTrack has no dot
    clock, and recovery must not crash computing a dot lease gap."""
    from fantoch_tpu.executor.slot import SlotExecutionInfo
    from fantoch_tpu.run.harness import free_port
    from fantoch_tpu.run.process_runner import ProcessRuntime
    from fantoch_tpu.run.wal import Wal

    wal_dir = tmp_path / "p2"
    wal = Wal(str(wal_dir), sync="always")
    wal.recover()
    cmds = {}
    for slot in (1, 2):
        cmd = Command.from_single(Rifl(9, slot), 0, f"k{slot}", KVOp.put("v"))
        cmds[slot] = cmd
        wal.append("info", SlotExecutionInfo(slot, cmd))
    wal.append_lease(10)  # a stale dot lease must not crash slot-GC recovery
    wal.close()

    config = Config(
        3, 1, leader=1, fpaxos_leader_timeout_ms=2000, gc_interval_ms=50
    )
    runtime = ProcessRuntime(
        FPaxos, 2, 0, config,
        listen_addr=("127.0.0.1", free_port()),
        client_addr=("127.0.0.1", free_port()),
        peers={},
        sorted_processes=[(2, 0), (1, 0), (3, 0)],
        wal_dir=str(wal_dir),
    )
    assert runtime._recovered
    assert runtime.wal_replayed_infos == 2
    process = runtime.process
    # the rejoin floor covers the replayed slots...
    assert process._slot_sync_floor() >= 2
    # ...and the chosen log can serve them to OTHER rejoiners
    records = process._slot_sync_records(0)
    assert [(slot, cmd.rifl) for slot, cmd in records] == [
        (1, Rifl(9, 1)), (2, Rifl(9, 2))
    ]
    assert process._slot_sync_records(2) == []
    # the executor replay advanced the slot frontier exactly once
    assert runtime.executors[0]._next_slot == 3


# --- run layer: WAL recovery + rejoin over real TCP ---


@pytest.mark.parametrize(
    "snapshot_interval_ms", [500, 600_000], ids=["snapshot+tail", "tail-only"]
)
def test_run_restart_from_wal_and_rejoin(tmp_path, snapshot_interval_ms):
    """Kill a runtime mid-mesh, restart it from its WAL dir: it recovers
    (snapshot + tail), peers revive it (incarnation-keyed dedup reset +
    on_peer_up), MSync pulls the commits it missed, and it serves clients
    again.  Monitors across all three lives agree (exactly-once).

    The ``tail-only`` variant pins the snapshot interval past the run so
    recovery is a pure log replay: the replayed commit dots must fold
    into the rejoin horizon (``note_durable_commits``) — without that,
    MSync re-streams the tail and the replica executes it twice."""
    from fantoch_tpu.run.client_runner import run_clients
    from fantoch_tpu.run.harness import free_port
    from fantoch_tpu.run.links import ReconnectPolicy
    from fantoch_tpu.run.process_runner import ProcessRuntime

    commands = 10

    def make_runtime(pid, peer_ports, client_ports, config):
        return ProcessRuntime(
            EPaxos,
            pid,
            0,
            config,
            listen_addr=("127.0.0.1", peer_ports[pid]),
            client_addr=("127.0.0.1", client_ports[pid]),
            peers={p: ("127.0.0.1", peer_ports[p]) for p in (1, 2, 3) if p != pid},
            sorted_processes=[(pid, 0)] + [(p, 0) for p in (1, 2, 3) if p != pid],
            reconnect_policy=ReconnectPolicy(attempts=10, base_s=0.02, cap_s=0.2),
            # wide silence window: every runtime shares one cooperative
            # loop here, so load stalls must not read as peer death
            heartbeat_interval_s=0.2,
            heartbeat_misses=25,
            wal_dir=str(tmp_path / f"p{pid}"),
            wal_snapshot_interval_ms=snapshot_interval_ms,
        )

    async def scenario():
        config = Config(
            3, 1, executor_monitor_execution_order=True,
            gc_interval_ms=50, executor_executed_notification_interval_ms=50,
        )
        peer_ports = {pid: free_port() for pid in (1, 2, 3)}
        client_ports = {pid: free_port() for pid in (1, 2, 3)}
        runtimes = {
            pid: make_runtime(pid, peer_ports, client_ports, config)
            for pid in (1, 2, 3)
        }
        await asyncio.gather(*(r.start() for r in runtimes.values()))
        workload = Workload(
            shard_count=1, key_gen=ConflictRateKeyGen(50), keys_per_command=2,
            commands_per_client=commands, payload_size=1,
        )
        loop = asyncio.get_running_loop()

        # phase 1: p3 serves (its WAL sees commits), then crashes
        phase1 = await asyncio.wait_for(
            run_clients([1, 2], {0: ("127.0.0.1", client_ports[3])}, workload,
                        open_loop_interval_ms=10),
            60,
        )
        await asyncio.sleep(1.0)  # let a periodic snapshot land
        await runtimes[3].stop()

        # phase 2: commits p3 misses while dead
        phase2 = await asyncio.wait_for(
            run_clients([3, 4], {0: ("127.0.0.1", client_ports[1])}, workload,
                        open_loop_interval_ms=10),
            60,
        )
        deadline = loop.time() + 30
        while loop.time() < deadline:
            if all(3 in runtimes[p].dead_peers for p in (1, 2)):
                break
            await asyncio.sleep(0.1)
        assert all(3 in runtimes[p].dead_peers for p in (1, 2))

        # restart p3 from its WAL
        runtimes[3] = make_runtime(3, peer_ports, client_ports, config)
        assert runtimes[3]._recovered, "the WAL dir must drive a recovery"
        assert runtimes[3].incarnation == 2
        if snapshot_interval_ms > 10_000:
            # tail-only: the log replay itself must have done the work,
            # and the replayed horizon must already cover phase 1
            assert runtimes[3].wal_replayed_infos > 0
            clock = runtimes[3].process._gc_track.my_clock()
            own = clock.get(3)
            assert own is not None and own.frontier >= 2 * commands
        await runtimes[3].start()

        # revival + MSync catch-up: p3's horizon reaches phase-2 commits
        caught_up = False
        deadline = loop.time() + 30
        while loop.time() < deadline:
            clock = runtimes[3].process._gc_track.my_clock()
            events = clock.get(1)
            if (
                events is not None
                and events.frontier >= 2 * commands
                and all(3 not in runtimes[p].dead_peers for p in (1, 2))
            ):
                caught_up = True
                break
            await asyncio.sleep(0.2)
        assert caught_up, "MSync catch-up past the WAL horizon timed out"

        # phase 3: the restarted replica serves again
        phase3 = await asyncio.wait_for(
            run_clients([5, 6], {0: ("127.0.0.1", client_ports[3])}, workload,
                        open_loop_interval_ms=10),
            60,
        )
        failures = {pid: runtimes[pid].failure for pid in (1, 2, 3)}
        monitors = {pid: runtimes[pid].executors[0].monitor() for pid in (1, 2, 3)}
        await asyncio.gather(*(r.stop() for r in runtimes.values()))
        return phase1, phase2, phase3, failures, monitors

    phase1, phase2, phase3, failures, monitors = asyncio.run(scenario())
    for group in (phase1, phase2, phase3):
        for client_id, client in group.items():
            assert client.issued_commands == commands, (client_id, client.issued_commands)
    assert failures == {1: None, 2: None, 3: None}
    check_monitors(monitors)


def test_fpaxos_run_leader_restart_from_wal_and_rejoin(tmp_path):
    """FPaxos over real TCP, three phases: (1) the leader p1 serves (its
    WAL logs chosen slots), then is killed; the failure detector fires
    ``on_peer_down`` and the ring successor p2 elects itself; (2) clients
    complete against the new leader while p1 is down; (3) p1 restarts
    from its WAL, peers revive it, the higher-ballot heartbeat demotes
    its stale leadership, MSlotSync streams the chosen slots it missed,
    and it serves clients again (forwarding to p2).  Monitors across all
    three lives agree — exactly-once across the restart."""
    from fantoch_tpu.run.client_runner import run_clients
    from fantoch_tpu.run.harness import free_port
    from fantoch_tpu.run.links import ReconnectPolicy
    from fantoch_tpu.run.process_runner import ProcessRuntime

    commands = 10

    def make_runtime(pid, peer_ports, client_ports, config):
        return ProcessRuntime(
            FPaxos,
            pid,
            0,
            config,
            listen_addr=("127.0.0.1", peer_ports[pid]),
            client_addr=("127.0.0.1", client_ports[pid]),
            peers={p: ("127.0.0.1", peer_ports[p]) for p in (1, 2, 3) if p != pid},
            sorted_processes=[(pid, 0)] + [(p, 0) for p in (1, 2, 3) if p != pid],
            reconnect_policy=ReconnectPolicy(attempts=10, base_s=0.02, cap_s=0.2),
            heartbeat_interval_s=0.2,
            heartbeat_misses=25,
            wal_dir=str(tmp_path / f"p{pid}"),
            wal_snapshot_interval_ms=500,
        )

    async def scenario():
        config = Config(
            3, 1, leader=1, fpaxos_leader_timeout_ms=2000,
            executor_monitor_execution_order=True,
            gc_interval_ms=50,
        )
        peer_ports = {pid: free_port() for pid in (1, 2, 3)}
        client_ports = {pid: free_port() for pid in (1, 2, 3)}
        runtimes = {
            pid: make_runtime(pid, peer_ports, client_ports, config)
            for pid in (1, 2, 3)
        }
        await asyncio.gather(*(r.start() for r in runtimes.values()))
        workload = Workload(
            shard_count=1, key_gen=ConflictRateKeyGen(50), keys_per_command=2,
            commands_per_client=commands, payload_size=1,
        )
        loop = asyncio.get_running_loop()

        # phase 1: the leader serves (its WAL sees chosen slots), then dies
        phase1 = await asyncio.wait_for(
            run_clients([1, 2], {0: ("127.0.0.1", client_ports[1])}, workload,
                        open_loop_interval_ms=10),
            60,
        )
        await asyncio.sleep(1.0)  # let a periodic snapshot land
        await runtimes[1].stop()

        # followers detect the dead leader; p2 (ring successor) elects
        deadline = loop.time() + 30
        while loop.time() < deadline:
            if all(1 in runtimes[p].dead_peers for p in (2, 3)):
                break
            await asyncio.sleep(0.1)
        assert all(1 in runtimes[p].dead_peers for p in (2, 3))

        # phase 2: the new leader serves while p1 is down
        phase2 = await asyncio.wait_for(
            run_clients([3, 4], {0: ("127.0.0.1", client_ports[2])}, workload,
                        open_loop_interval_ms=10),
            60,
        )
        assert runtimes[2].process._multi_synod.is_leader

        # restart p1 from its WAL
        runtimes[1] = make_runtime(1, peer_ports, client_ports, config)
        assert runtimes[1]._recovered, "the WAL dir must drive a recovery"
        assert runtimes[1].incarnation == 2
        await runtimes[1].start()

        # revival + MSlotSync catch-up: p1's slot floor reaches every
        # chosen slot (2 phases x 2 clients x `commands`), and the stale
        # restored leadership is demoted by p2's higher-ballot heartbeat
        total_slots = 4 * commands
        caught_up = False
        deadline = loop.time() + 30
        while loop.time() < deadline:
            if (
                runtimes[1].process._slot_sync_floor() >= total_slots
                and not runtimes[1].process._multi_synod.is_leader
                and runtimes[1].process._leader == 2
                and all(1 not in runtimes[p].dead_peers for p in (2, 3))
            ):
                caught_up = True
                break
            await asyncio.sleep(0.2)
        assert caught_up, (
            "MSlotSync catch-up timed out: floor "
            f"{runtimes[1].process._slot_sync_floor()}/{total_slots}"
        )

        # phase 3: the restarted replica serves again (forwards to p2)
        phase3 = await asyncio.wait_for(
            run_clients([5, 6], {0: ("127.0.0.1", client_ports[1])}, workload,
                        open_loop_interval_ms=10),
            60,
        )
        failures = {pid: runtimes[pid].failure for pid in (1, 2, 3)}
        monitors = {pid: runtimes[pid].executors[0].monitor() for pid in (1, 2, 3)}
        await asyncio.gather(*(r.stop() for r in runtimes.values()))
        return phase1, phase2, phase3, failures, monitors

    phase1, phase2, phase3, failures, monitors = asyncio.run(scenario())
    for group in (phase1, phase2, phase3):
        for client_id, client in group.items():
            assert client.issued_commands == commands, (client_id, client.issued_commands)
    assert failures == {1: None, 2: None, 3: None}
    check_monitors(monitors)
