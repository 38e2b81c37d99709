"""Oracle parity suite for the device-resident predecessors plane
(executor/pred_plane.DevicePredPlane) against the host PredecessorsGraph
twin, plus the both-planes-on-one-base regression rows for the extracted
DevicePlane (executor/device_plane.py) and the memoized watchdog walk.

The parity contract is the agreement contract conflicting commands care
about: identical executed set and identical per-key execution order,
across shuffled delivery, noop commits, recovery-adjusted clocks,
multi-feed residuals, capacity compaction, and snapshot/restore with the
single-re-upload invariant.
"""

import pickle
import random

import numpy as np
import pytest

from fantoch_tpu.core.command import Command
from fantoch_tpu.core.config import Config
from fantoch_tpu.core.ids import Dot, Rifl
from fantoch_tpu.core.kvs import KVOp
from fantoch_tpu.executor.device_plane import DevicePlane, resolve_threshold
from fantoch_tpu.executor.pred import (
    PredArraysBuilder,
    PredecessorsExecutionInfo,
    PredecessorsExecutor,
    PredecessorsGraph,
    PredecessorsNoop,
)
from fantoch_tpu.executor.pred_plane import DevicePredPlane
from fantoch_tpu.executor.table_plane import ClockOverflowError, DeviceTablePlane
from fantoch_tpu.protocol.common.pred_clocks import Clock

SHARD = 0


def cmd(seq: int, keys) -> Command:
    return Command.from_keys(
        Rifl(9, seq), SHARD, {k: (KVOp.put(str(seq)),) for k in keys}
    )


def _plane_executor(**cfg) -> PredecessorsExecutor:
    return PredecessorsExecutor(
        1, SHARD,
        Config(3, 1, device_pred_plane=True,
               executor_monitor_execution_order=True, **cfg),
    )


def _host_executor(**cfg) -> PredecessorsExecutor:
    return PredecessorsExecutor(
        1, SHARD,
        Config(3, 1, executor_monitor_execution_order=True, **cfg),
    )


def _assert_parity(ex_plane, ex_host, expect_executed=None):
    got = sorted(r.rifl for r in ex_plane.to_clients_iter())
    want = sorted(r.rifl for r in ex_host.to_clients_iter())
    assert got == want
    if expect_executed is not None:
        assert len(want) == expect_executed
    mon_p, mon_h = ex_plane.monitor(), ex_host.monitor()
    assert set(mon_p.keys()) == set(mon_h.keys())
    for key in mon_p.keys():
        assert mon_p.get_order(key) == mon_h.get_order(key)


def _conflict_workload(rng, count=60, keys=("Ka", "Kb", "Kc")):
    per_key = {k: [] for k in keys}
    infos = []
    for i in range(count):
        src = rng.randrange(1, 4)
        dot = Dot(src, i + 1)
        ks = rng.sample(list(keys), rng.randrange(1, 3))
        deps = set()
        for k in ks:
            deps.update(per_key[k])
            per_key[k].append(dot)
        infos.append(
            PredecessorsExecutionInfo(dot, cmd(i + 1, ks), Clock(i + 1, src), deps)
        )
    return infos


def test_pred_plane_oracle_parity_multi_feed_residuals():
    """Bit-for-bit per-key execution order vs the host twin across
    shuffled delivery and batch boundaries that leave missing-blocked
    residues resident on device for several feeds."""
    rng = random.Random(5)
    for _trial in range(5):
        infos = _conflict_workload(rng)
        shuffled = infos[:]
        rng.shuffle(shuffled)
        batches = []
        at = 0
        while at < len(shuffled):
            size = rng.randrange(1, 9)
            batches.append(shuffled[at : at + size])
            at += size
        ex_p, ex_h = _plane_executor(), _host_executor()
        for batch in batches:
            ex_p.handle_batch(batch, None)
            for info in batch:
                ex_h.handle(info, None)
        total_keys = sum(i.cmd.key_count(SHARD) for i in infos)
        _assert_parity(ex_p, ex_h, expect_executed=total_keys)


def test_pred_plane_noop_and_recovery_adjusted_clock_parity():
    """Recovered noops resolve dependents in both phases, and a
    dependency whose consensus-decided clock ends up HIGHER than its
    dependent's (the recovery free-choice lift) stops blocking phase 2
    exactly like the host twin."""
    m = Dot(3, 7)  # recovered as a noop below
    a, b, c = Dot(1, 1), Dot(1, 2), Dot(2, 1)
    infos = [
        # a blocked on the never-payloaded m (phase 1)
        PredecessorsExecutionInfo(a, cmd(1, ["K"]), Clock(2, 1), {m}),
        # b blocked on a (lower clock), m, and the yet-uncommitted c
        PredecessorsExecutionInfo(b, cmd(2, ["K"]), Clock(4, 1), {a, m, c}),
    ]
    ex_p, ex_h = _plane_executor(), _host_executor()
    ex_p.handle_batch(infos, None)
    for info in infos:
        ex_h.handle(info, None)
    assert not list(ex_p.to_clients_iter()) and not list(ex_h.to_clients_iter())
    # c commits with a RECOVERY-LIFTED clock above b's: b does not wait
    # for it (phase 2 ignores higher-clock deps) even though b lists it
    late = PredecessorsExecutionInfo(c, cmd(3, ["K"]), Clock(9, 2), set())
    ex_p.handle_batch([late], None)
    ex_h.handle(late, None)
    # the noop unblocks everything
    ex_p.handle_batch([PredecessorsNoop(m)], None)
    ex_h.handle(PredecessorsNoop(m), None)
    _assert_parity(ex_p, ex_h, expect_executed=3)
    # executed clock covers the noop dot on both (drives Caesar GC)
    assert ex_p.executed(None).contains(3, 7)
    assert ex_h.executed(None).contains(3, 7)


def test_pred_plane_arrays_seam_matches_object_feed():
    """The column feed (PredArraysBuilder -> add_arrays, the Caesar
    commit seam) is behaviorally identical to the object feed."""
    rng = random.Random(11)
    infos = _conflict_workload(rng, count=40)
    builder = PredArraysBuilder()
    noop = Dot(3, 99)
    infos[10].deps.add(noop)  # a dep resolved only by the noop row below
    for info in infos[:20]:
        builder.add_commit(info.dot, info.cmd, info.clock, info.deps)
    first = builder.take()
    builder.add_noop(noop)
    for info in infos[20:]:
        builder.add_commit(info.dot, info.cmd, info.clock, info.deps)
    second = builder.take()
    assert builder.take() is None

    ex_arrays, ex_objects = _plane_executor(), _plane_executor()
    ex_arrays.handle_batch([first], None)
    ex_arrays.handle_batch([second], None)
    ex_objects.handle_batch(infos[:20], None)
    ex_objects.handle_batch([PredecessorsNoop(noop)] + infos[20:], None)
    _assert_parity(ex_arrays, ex_objects)


def test_pred_plane_snapshot_restore_single_reupload():
    """The restart seam: a pickled executor re-materializes its resident
    window from the host mirror on the FIRST dispatch after restore —
    exactly one counted re-upload — and pending residuals survive with
    bit-for-bit parity."""
    m = Dot(2, 1)
    a, b = Dot(1, 1), Dot(1, 2)
    ex = _plane_executor()
    ex.handle_batch(
        [
            PredecessorsExecutionInfo(a, cmd(1, ["K"]), Clock(2, 1), {m}),
            PredecessorsExecutionInfo(b, cmd(2, ["K"]), Clock(3, 1), {a, m}),
        ],
        None,
    )
    assert not list(ex.to_clients_iter())
    blob = ex.snapshot()
    restored = PredecessorsExecutor.restore(blob)
    plane = restored._graph
    assert isinstance(plane, DevicePredPlane)
    uploads = plane.resident_uploads
    # the missing dep commits: the restored window wakes the chain
    restored.handle_batch(
        [PredecessorsExecutionInfo(m, cmd(3, ["K"]), Clock(1, 2), set())], None
    )
    got = [r.rifl for r in restored.to_clients_iter()]
    assert got == [Rifl(9, 3), Rifl(9, 1), Rifl(9, 2)]
    assert plane.resident_uploads - uploads == 1, (
        "restore must cost exactly ONE re-upload"
    )
    # a second pickle round-trip with nothing pending still works
    again = PredecessorsExecutor.restore(restored.snapshot())
    assert again.executed(None).contains(1, 2)


def test_pred_plane_compaction_and_growth_preserve_blocked_rows():
    """Window exhaustion re-packs pending rows to the bottom (dep cells
    remapped, waiter cells following): a missing-blocked row must
    survive arbitrarily many compactions and execute when its dep
    finally commits."""
    ex = _plane_executor()
    plane = ex._graph
    plane._cap = 8
    for name in ("_slot_src", "_slot_seq", "_slot_start", "_slot_cseq",
                 "_slot_csrc"):
        setattr(plane, name, getattr(plane, name)[:8].copy())
    missing = Dot(3, 1)
    blocked = Dot(1, 100)
    ex.handle_batch(
        [PredecessorsExecutionInfo(blocked, cmd(100, ["B"]), Clock(200, 1), {missing})],
        None,
    )
    per = []
    for i in range(40):
        dot = Dot(1, i + 1)
        deps = set(per[-2:])
        per.append(dot)
        ex.handle_batch(
            [PredecessorsExecutionInfo(dot, cmd(i + 1, ["K"]), Clock(i + 1, 1), deps)],
            None,
        )
    assert sum(1 for _ in ex.to_clients_iter()) == 40
    assert plane.stats["compactions"] >= 2
    assert plane.pending_count == 1
    ex.handle_batch(
        [PredecessorsExecutionInfo(missing, cmd(101, ["B"]), Clock(150, 3), set())],
        None,
    )
    got = [r.rifl for r in ex.to_clients_iter()]
    assert got == [Rifl(9, 101), Rifl(9, 100)]


def test_pred_plane_wide_dep_sets_grow_width():
    """Dep fan-out beyond the resident width re-pads the window columns
    (a counted grow), preserving earlier state."""
    ex = _plane_executor()
    plane = ex._graph
    start_width = plane._width
    deps = set()
    infos = []
    for i in range(start_width + 3):
        dot = Dot(1, i + 1)
        infos.append(
            PredecessorsExecutionInfo(
                dot, cmd(i + 1, ["K"]), Clock(i + 1, 1), set(deps)
            )
        )
        deps.add(dot)
    ex.handle_batch(infos[: start_width], None)
    ex.handle_batch(infos[start_width:], None)  # widest row exceeds width
    assert plane._width > start_width
    assert sum(1 for _ in ex.to_clients_iter()) == len(infos)


def test_pred_plane_clock_overflow_rejected():
    ex = _plane_executor()
    with pytest.raises(ClockOverflowError):
        ex.handle_batch(
            [
                PredecessorsExecutionInfo(
                    Dot(1, 1), cmd(1, ["K"]), Clock((1 << 31) - 1, 1), set()
                )
            ],
            None,
        )


def test_pred_plane_watchdog_reports_missing_and_fails_bounded():
    """The liveness watchdog on the plane: the missing frontier surfaces
    for nudge_recovery below the bound, a typed StalledExecutionError
    fires past Config.executor_pending_fail_ms, and the exactly-once /
    no-pending-without-missing invariants hold."""
    from fantoch_tpu.core.timing import SimTime
    from fantoch_tpu.errors import StalledExecutionError

    ex = _plane_executor(executor_pending_fail_ms=5000)
    ex.handle_batch(
        [
            PredecessorsExecutionInfo(
                Dot(2, 1), cmd(2, ["K"]), Clock(5, 2), {Dot(1, 1)}
            )
        ],
        SimTime(0),
    )
    assert ex.monitor_pending(SimTime(2000)) == {Dot(1, 1)}
    with pytest.raises(StalledExecutionError) as err:
        ex.monitor_pending(SimTime(6000))
    assert Dot(1, 1) in err.value.missing[Dot(2, 1)]


def test_pred_plane_duplicate_commit_trips_after_compaction():
    """Exactly-once must hold across compactions: a duplicate commit of
    a dot that executed BEFORE the last compaction (which clears the
    recent-executed probe set) still trips the loud assert, like the
    host twin's committed-clock assert — never a silent re-install and
    double execution."""
    ex = _plane_executor()
    plane = ex._graph
    plane._cap = 8
    for name in ("_slot_src", "_slot_seq", "_slot_start", "_slot_cseq",
                 "_slot_csrc"):
        setattr(plane, name, getattr(plane, name)[:8].copy())
    dup = Dot(1, 1)
    ex.handle_batch(
        [PredecessorsExecutionInfo(dup, cmd(1, ["K"]), Clock(1, 1), set())],
        None,
    )
    for i in range(2, 20):  # run the window through >= 1 compaction
        ex.handle_batch(
            [PredecessorsExecutionInfo(Dot(1, i), cmd(i, ["K"]), Clock(i, 1), set())],
            None,
        )
    assert plane.stats["compactions"] >= 1
    assert dup not in plane._exec_recent  # compaction cleared the probe set
    with pytest.raises(AssertionError, match="exactly once"):
        ex.handle_batch(
            [PredecessorsExecutionInfo(dup, cmd(1, ["K"]), Clock(99, 1), set())],
            None,
        )


def test_pred_plane_watchdog_nudges_only_overdue_missing():
    """The missing frontier also holds dots of healthy in-flight
    commits; the watchdog must only nudge dots missing PAST the pending
    threshold, or one stalled row would start recovery consensus against
    every live coordinator."""
    from fantoch_tpu.core.timing import SimTime

    ex = _plane_executor()
    old_missing, young_missing = Dot(3, 1), Dot(3, 2)
    ex.handle_batch(
        [PredecessorsExecutionInfo(Dot(1, 1), cmd(1, ["K"]), Clock(5, 1), {old_missing})],
        SimTime(0),
    )
    ex.handle_batch(
        [PredecessorsExecutionInfo(Dot(1, 2), cmd(2, ["J"]), Clock(6, 1), {young_missing})],
        SimTime(900),
    )
    # at t=1100: both rows' dots are in the frontier, but only the one
    # missing past the 1000ms threshold is actionable
    assert ex.monitor_pending(SimTime(1100)) == {old_missing}
    # once the young one matures it joins the nudge set
    assert ex.monitor_pending(SimTime(2000)) == {old_missing, young_missing}


def test_pred_plane_device_counters_seam():
    """The Executor.device_counters() seam (the table plane's contract):
    dispatch/occupancy/residual/kernel tallies present and sane, None
    when the plane is off."""
    ex = _plane_executor()
    infos = _conflict_workload(random.Random(3), count=20)
    ex.handle_batch(infos, None)
    counters = ex.device_counters()
    assert counters["pred_plane_dispatches"] == 1
    assert counters["pred_plane_new_rows"] == 20
    assert counters["pred_plane_update_capacity"] >= 20
    assert counters["pred_plane_resident_uploads"] == 1
    assert counters["pred_plane_kernel_ms"] > 0
    assert counters["pred_plane_slot_capacity"] == ex._graph._cap
    assert _host_executor().device_counters() is None
    # counters fold into the process-level snapshot like the table's
    from fantoch_tpu.observability.device import merge_counters

    folded = merge_counters({}, counters)
    folded = merge_counters(folded, counters)
    assert folded["pred_plane_dispatches"] == 2
    # capacity is a gauge: max-folded, never summed
    assert folded["pred_plane_slot_capacity"] == ex._graph._cap


def test_caesar_sim_with_device_pred_plane():
    """End-to-end Caesar over the sim with the plane + arrays commit
    seam on: same client histories as the host-executor runs (the
    sim_test harness checks per-key agreement across replicas)."""
    from harness import sim_test

    from fantoch_tpu.protocol import Caesar

    sim_test(
        Caesar,
        Config(
            n=3, f=1, caesar_wait_condition=True, gc_interval_ms=100,
            device_pred_plane=True,
        ),
    )


def test_caesar_set_commit_arrays_flushes_pending():
    """The runner hook: disabling the arrays seam flushes the
    accumulated column batch so no commit is lost (the Newt
    set_commit_arrays contract)."""
    from fantoch_tpu.protocol import Caesar
    from fantoch_tpu.core.timing import SimTime
    from fantoch_tpu.protocol.caesar import MCommit, MPropose

    config = Config(
        n=3, f=1, gc_interval_ms=100, device_pred_plane=True,
    )
    caesar = Caesar(1, SHARD, config)
    assert caesar.discover([(pid, SHARD) for pid in range(1, 4)])[0]
    time = SimTime()
    dot = Dot(2, 1)
    caesar.handle(2, SHARD, MPropose(dot, cmd(1, ["K"]), Clock(1, 2)), time)
    list(caesar.to_processes_iter())
    caesar.handle(2, SHARD, MCommit(dot, Clock(1, 2), set()), time)
    assert len(caesar._commit_arrays) == 1
    caesar.set_commit_arrays(False)
    assert caesar._commit_arrays is None
    infos = list(caesar.to_executors_iter())
    assert len(infos) == 1, "the pending column batch must flush"
    ex = _plane_executor()
    ex.handle_batch(infos, time)
    assert [r.rifl for r in ex.to_clients_iter()] == [Rifl(9, 1)]


def test_run_caesar_localhost_through_pred_plane():
    """The serving path (ROADMAP item 4's remainder): a 3-process
    localhost TCP Caesar cluster whose executor path orders through the
    resident pred plane (process_runner -> PredArraysBuilder column
    drains -> PredecessorsExecutor -> DevicePredPlane), with
    cross-replica per-key agreement and the plane counters visible
    through the runtime's device-counter fold."""
    from test_run_localhost import run_cluster

    from fantoch_tpu.core.config import Config as _Config
    from fantoch_tpu.protocol import Caesar

    _slow, runtimes = run_cluster(
        Caesar,
        _Config(n=3, f=1, device_pred_plane=True),
        keys_per_command=1,
        return_runtimes=True,
    )
    for runtime in runtimes.values():
        counters = runtime._device_counters()
        assert counters["pred_plane_dispatches"] > 0
        assert (
            counters["pred_plane_resident_uploads"]
            <= 1
            + counters["pred_plane_compactions"]
            + counters["pred_plane_grows"]
        )


# ---------------------------------------------------------------------------
# both-planes-on-one-base (the DevicePlane extraction)
# ---------------------------------------------------------------------------


def test_both_planes_share_the_device_plane_base():
    """The ROADMAP item-5 extraction: the votes-table plane and the
    predecessors plane are the SAME machinery — one base owning buffer
    lifecycle, durability, and counters — not two hand-rolled copies."""
    assert issubclass(DeviceTablePlane, DevicePlane)
    assert issubclass(DevicePredPlane, DevicePlane)
    for klass in (DeviceTablePlane, DevicePredPlane):
        for member in (
            "_materialize", "_grow", "_upload", "_fetch_state",
            "__getstate__", "__setstate__", "_count_dispatch",
        ):
            # lifecycle methods resolve to the shared base implementation
            assert getattr(klass, member) is getattr(DevicePlane, member), (
                f"{klass.__name__}.{member} forked from the base"
            )


def test_table_plane_on_base_keeps_oracle_behavior():
    """A focused re-run of the table plane's core contract on the
    extracted base (the full oracle suite lives in test_table_plane.py):
    frontier math, residual re-feed, pickle round trip with the single
    re-upload."""
    plane = DeviceTablePlane(3, 2, key_buckets=4)
    k = plane.bucket("x")
    stable = plane.commit_votes(
        np.array([k, k], dtype=np.int64),
        np.array([1, 2], dtype=np.int64),
        np.array([1, 1], dtype=np.int64),
        np.array([3, 2], dtype=np.int64),
    )
    assert stable[k] == 2  # 2-of-3 threshold over frontiers (3, 2, 0)
    # beyond-gap run buffers as residual and re-feeds
    stable = plane.commit_votes(
        np.array([k], dtype=np.int64),
        np.array([3], dtype=np.int64),
        np.array([5], dtype=np.int64),
        np.array([6], dtype=np.int64),
    )
    assert plane.residual_count == 1 and stable[k] == 2
    blob = pickle.dumps(plane)
    restored = pickle.loads(blob)
    uploads = restored.resident_uploads
    stable = restored.commit_votes(
        np.array([k], dtype=np.int64),
        np.array([3], dtype=np.int64),
        np.array([1], dtype=np.int64),
        np.array([4], dtype=np.int64),
    )
    # the gap filled: the buffered 5..6 residual coalesces onto voter
    # 3's 1..4, frontiers (3, 2, 6) -> 2-of-3 stable clock 3
    assert restored.residual_count == 0 and stable[k] == 3
    assert restored.resident_uploads - uploads == 1


def test_resolve_threshold_precedence():
    """The shared kernel-threshold switch: the Config field where it is
    set, else the plane's default; there is no variable to name."""
    assert resolve_threshold(None, 7) == 7
    assert resolve_threshold(13, 7) == 13
    assert resolve_threshold(0, 7) == 0  # 0 is a value, not "unset"
    with pytest.raises(TypeError):
        resolve_threshold(None, "FANTOCH_TEST_THRESHOLD", 7)


# ---------------------------------------------------------------------------
# the memoized watchdog walk (host twin)
# ---------------------------------------------------------------------------


def test_host_watchdog_memoizes_across_ticks():
    """monitor_pending's transitive-missing walk is computed once per
    commit-state generation: idle ticks reuse the memo (no re-walk), and
    any commit invalidates it — at 1M pending the per-tick re-walk was
    the recovery nudge's cost (ISSUE r13 small fix)."""
    from fantoch_tpu.core.timing import SimTime

    ex = _host_executor(executor_pending_fail_ms=None)
    graph = ex._graph
    assert isinstance(graph, PredecessorsGraph)
    missing = Dot(3, 1)
    per = []
    for i in range(10):
        dot = Dot(1, i + 1)
        deps = set(per[-1:]) | {missing}
        per.append(dot)
        ex.handle(
            PredecessorsExecutionInfo(
                dot, cmd(i + 1, ["K"]), Clock(i + 1, 1), deps
            ),
            SimTime(0),
        )
    # a healthy tick (nothing past the threshold yet) walks NOTHING:
    # the map is built lazily on the first long-pending vertex
    assert ex.monitor_pending(SimTime(100)) == set()
    assert graph._memo_gen != graph._gen, "no walk on a healthy tick"
    assert ex.monitor_pending(SimTime(2000)) == {missing}
    memo_gen = graph._memo_gen
    assert memo_gen == graph._gen
    # idle tick: same generation, memo reused (not recomputed)
    memo_before = graph._memo
    assert ex.monitor_pending(SimTime(3000)) == {missing}
    assert graph._memo is memo_before and graph._memo_gen == memo_gen
    # a commit invalidates the memo; with everything executed the next
    # tick has no long-pending vertex and again walks nothing
    ex.handle(
        PredecessorsExecutionInfo(missing, cmd(99, ["K"]), Clock(99, 3), set()),
        SimTime(3000),
    )
    assert graph._memo_gen != graph._gen
    assert ex.monitor_pending(SimTime(4000)) == set()
    assert sum(1 for _ in ex.to_clients_iter()) == 11


def test_host_watchdog_memo_matches_unmemoized_walk():
    """The memoized bottom-up pass computes the same transitive-missing
    sets as a reference per-vertex walk over a random pending graph."""
    rng = random.Random(7)
    ex = _host_executor()
    graph = ex._graph
    committed = []
    missing_pool = [Dot(3, s) for s in range(1, 6)]
    for i in range(60):
        dot = Dot(1, i + 1)
        deps = set(rng.sample(committed, min(len(committed), rng.randrange(0, 3))))
        if rng.random() < 0.4:
            deps.add(rng.choice(missing_pool))
        committed.append(dot)
        ex.handle(
            PredecessorsExecutionInfo(dot, cmd(i + 1, ["K"]), Clock(i + 1, 1), deps),
            None,
        )
    memo = graph._missing_map()

    def reference_walk(vertex):
        missing, visited, stack = set(), {vertex.dot}, [vertex]
        while stack:
            current = stack.pop()
            for dep in current.deps:
                if dep in visited:
                    continue
                if graph._executed_clock.contains(dep.source, dep.sequence):
                    continue
                if not graph._committed_clock.contains(dep.source, dep.sequence):
                    missing.add(dep)
                    continue
                visited.add(dep)
                dep_vertex = graph._vertices.get(dep)
                if dep_vertex is not None and dep_vertex.clock < current.clock:
                    stack.append(dep_vertex)
        return missing

    for vertex in graph._vertices.values():
        assert memo[vertex.dot] == reference_walk(vertex), vertex.dot
