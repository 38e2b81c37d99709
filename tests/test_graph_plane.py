"""Oracle parity suite for the device-resident graph plane
(executor/graph/graph_plane.DeviceGraphPlane) against the host-column
``BatchedDependencyGraph`` twin, plus the three-planes-on-one-base
regression rows for the shared DevicePlane and the unified kernel-size
gate (Config.graph_kernel_threshold).

The parity contract is the agreement contract conflicting commands care
about: identical executed set and identical per-key execution order,
across shuffled multi-feed delivery with MISSING deps, cycles,
noop/executed notifications, capacity compaction, pow2 growth, and
snapshot/restore with the single-re-upload invariant.  The depth-K rows
prove the serving claim: feeds pipelined K deep drain bit-for-bit the
depth-1 order, with ``resident_uploads == 1`` (only new-row deltas
travel host->device after warmup).
"""

import itertools
import pickle
import random

import numpy as np
import pytest

from fantoch_tpu.core import Command, Config, Dot, KVOp, Rifl, RunTime
from fantoch_tpu.core.ids import process_ids
from fantoch_tpu.executor.device_plane import DevicePlane
from fantoch_tpu.executor.graph.batched import BatchedDependencyGraph, key_hash
from fantoch_tpu.executor.graph.graph_plane import DeviceGraphPlane
from fantoch_tpu.executor.pred_plane import DevicePredPlane
from fantoch_tpu.executor.table_plane import DeviceTablePlane
from fantoch_tpu.protocol.common.graph_deps import Dependency

TIME = RunTime()
SHARD = 0

HOST_CFG = Config(3, 1, host_native_resolver=False)
PLANE_CFG = Config(
    3, 1, host_native_resolver=False, batched_graph_executor=True,
    device_graph_plane=True,
)


def dep(dot):
    return Dependency(dot, frozenset({SHARD}))


def make_cmd(dot, keys):
    rifl = Rifl(dot.source, dot.sequence)
    return Command.from_keys(rifl, SHARD, {k: (KVOp.put(""),) for k in keys})


def run_feeds(config, feeds, batch_feed=True):
    """Drive (dot, keys, dep_dots) feeds through a fresh graph; returns
    the per-key rifl execution order (the agreement contract)."""
    graph = BatchedDependencyGraph(1, SHARD, config)
    order = {}
    pending = set()

    def drain():
        for ready in graph.commands_to_execute():
            pending.remove(ready.rifl)
            for key in ready.keys(SHARD):
                order.setdefault(key, []).append(ready.rifl)

    for feed in feeds:
        adds = []
        for dot, keys, dep_dots in feed:
            cmd = make_cmd(dot, keys)
            pending.add(cmd.rifl)
            adds.append((dot, cmd, [dep(d) for d in dep_dots]))
        if batch_feed:
            graph.handle_add_batch(adds, TIME)
        else:
            for dot, cmd, deps in adds:
                graph.handle_add(dot, cmd, deps, TIME)
        drain()
    assert not pending, f"not all commands executed: {pending}"
    return order


def random_adds(n, events_per_process, rng):
    """Random dep graphs with non-transitive conflicts and 2-cycles (the
    test_graph_executor generator)."""
    possible_keys = ["A", "B", "C", "D"]
    dots = [
        Dot(pid, seq)
        for pid in process_ids(SHARD, n)
        for seq in range(1, events_per_process + 1)
    ]
    keys = {}
    deps = {dot: set() for dot in dots}
    for dot in dots:
        keys[dot] = set(rng.sample(possible_keys, 2))
    for left, right in itertools.combinations(dots, 2):
        if not (keys[left] & keys[right]):
            continue
        if left.source == right.source:
            if left.sequence < right.sequence:
                deps[right].add(left)
            else:
                deps[left].add(right)
        else:
            choice = rng.randrange(3)
            if choice in (0, 2):
                deps[left].add(right)
            if choice in (1, 2):
                deps[right].add(left)
    return [(dot, sorted(keys[dot]), deps[dot]) for dot in dots]


def chop(rng, args):
    """Shuffle and split into random feed batches (multi-feed residuals:
    deps routinely arrive after their dependents, leaving missing-blocked
    rows resident across feeds)."""
    shuffled = args[:]
    rng.shuffle(shuffled)
    feeds = []
    at = 0
    while at < len(shuffled):
        size = rng.randrange(1, 6)
        feeds.append(shuffled[at : at + size])
        at += size
    return feeds


def test_graph_plane_oracle_parity_multi_feed_residuals():
    """Identical per-key execution order vs the host-column twin across
    shuffled multi-feed schedules with MISSING deps and (mutual) cycles —
    batched and per-add delivery both."""
    rng = random.Random(3)
    for _trial in range(6):
        args = random_adds(2, 3, rng)
        feeds = chop(rng, args)
        host = run_feeds(HOST_CFG, feeds)
        plane_batched = run_feeds(PLANE_CFG, feeds)
        plane_scalar = run_feeds(PLANE_CFG, feeds, batch_feed=False)
        assert plane_batched == host
        assert plane_scalar == host


def test_graph_plane_arrays_seam_matches_tuple_feed():
    """handle_add_arrays (the protocol commit-buffer seam) is
    behaviorally identical to per-command adds on the plane, and the
    array drain (take_order_arrays) matches the object drain."""
    batch = 48
    src = np.ones(batch, dtype=np.int64)
    seq = np.arange(1, batch + 1, dtype=np.int64)
    key = np.fromiter(
        (key_hash(f"k{i % 4}") for i in range(batch)), np.int32, batch
    )
    last = {}
    dd = np.full((batch, 1), -1, dtype=np.int64)
    for i in range(batch):
        prev = last.get(int(key[i]))
        if prev is not None:
            dd[i, 0] = (1 << 32) | prev
        last[int(key[i])] = i + 1
    cmds = [make_cmd(Dot(1, i + 1), [f"k{i % 4}"]) for i in range(batch)]

    g_arrays = BatchedDependencyGraph(1, SHARD, PLANE_CFG)
    g_arrays.handle_add_arrays(src, seq, key, dd, cmds, TIME)
    got = [c.rifl for c in g_arrays.commands_to_execute()]

    g_tuple = BatchedDependencyGraph(1, SHARD, PLANE_CFG)
    for i in range(batch):
        deps = (
            [dep(Dot(1, int(dd[i, 0]) & 0xFFFFFFFF))] if dd[i, 0] >= 0 else []
        )
        g_tuple.handle_add(Dot(1, i + 1), make_cmd(Dot(1, i + 1), [f"k{i % 4}"]), deps, TIME)
    want = [c.rifl for c in g_tuple.commands_to_execute()]
    # per-key orders must agree (whole-batch interleaving may differ)
    by_key_got = {}
    by_key_want = {}
    for r in got:
        by_key_got.setdefault((r.sequence - 1) % 4, []).append(r)
    for r in want:
        by_key_want.setdefault((r.sequence - 1) % 4, []).append(r)
    assert by_key_got == by_key_want

    g_order = BatchedDependencyGraph(1, SHARD, PLANE_CFG)
    g_order.record_order_arrays = True
    g_order.handle_add_arrays(src, seq, key, dd, cmds, TIME)
    g_order.resolve_now(TIME)
    o_src, o_seq = g_order.take_order_arrays()
    assert sorted(o_seq.tolist()) == list(range(1, batch + 1))
    assert not g_order.commands_to_execute()  # no object mirror kept


def test_graph_plane_noop_unblocks_waiters():
    """A recovered-noop commit patches every MISSING cell waiting on the
    dot to TERMINAL — dependents drain exactly like the host twin."""
    ghost = Dot(2, 5)
    for config in (HOST_CFG, PLANE_CFG):
        g = BatchedDependencyGraph(1, SHARD, config)
        g.handle_add(Dot(1, 1), make_cmd(Dot(1, 1), ["a"]), [dep(ghost)], TIME)
        g.handle_add(
            Dot(1, 2), make_cmd(Dot(1, 2), ["a"]), [dep(Dot(1, 1))], TIME
        )
        assert g.commands_to_execute() == []
        g.handle_noop(ghost, TIME)
        got = [c.rifl for c in g.commands_to_execute()]
        assert got == [Rifl(1, 1), Rifl(1, 2)]
        # the noop dot counts as executed (GraphExecuted/GC seam)
        assert g._frontier.contains(2, 5)


def test_graph_plane_stuck_cycle_host_oracle_parity():
    """A one-directional 3-cycle (no mutual edges) surfaces as a stuck
    residue; the plane's host-oracle follow-up emits it and wakes
    dependents — same order as the host-column twin."""
    d1, d2, d3, d4 = Dot(1, 1), Dot(2, 1), Dot(3, 1), Dot(1, 2)
    feeds = [
        [(d1, ["a", "b"], {d3})],
        [(d2, ["a", "b"], {d1})],
        # d4 waits on the whole cycle (emits via the follow-up dispatch)
        [(d3, ["a", "b"], {d2}), (d4, ["a", "b"], {d1, d2, d3})],
    ]
    host = run_feeds(HOST_CFG, feeds)
    plane = run_feeds(PLANE_CFG, feeds)
    assert plane == host
    assert [r.source for r in host["a"]] == [1, 2, 3, 1]


def test_graph_plane_snapshot_restore_single_reupload():
    """The restart seam: a pickled graph re-materializes its resident
    backlog from the host mirror on the FIRST dispatch after restore —
    exactly one counted re-upload — and missing-blocked residents
    survive with their waiter cells intact."""
    g = BatchedDependencyGraph(1, SHARD, PLANE_CFG)
    ghost = Dot(2, 1)
    g.handle_add(Dot(1, 1), make_cmd(Dot(1, 1), ["K"]), [dep(ghost)], TIME)
    g.handle_add(Dot(1, 2), make_cmd(Dot(1, 2), ["K"]), [dep(Dot(1, 1))], TIME)
    assert g.commands_to_execute() == []
    restored = pickle.loads(pickle.dumps(g))
    plane = restored._plane
    assert isinstance(plane, DeviceGraphPlane)
    uploads = plane.resident_uploads
    restored.handle_add(ghost, make_cmd(ghost, ["K"]), [], TIME)
    got = [c.rifl for c in restored.commands_to_execute()]
    assert got == [Rifl(2, 1), Rifl(1, 1), Rifl(1, 2)]
    assert plane.resident_uploads - uploads == 1, (
        "restore must cost exactly ONE re-upload"
    )
    # the restored plane shares the graph's frontier/metrics objects
    # (pickle preserves the aliasing within one snapshot)
    assert plane._frontier is restored._frontier
    assert plane._metrics is restored._metrics


def _shrink_plane(plane, cap):
    """Shrink a fresh plane's window so compaction paths exercise at
    test scale (the pred-plane test move)."""
    assert plane._next_slot == 0 and plane._resident is None
    plane._cap = cap
    for name in ("_slot_src", "_slot_seq", "_slot_tms", "_slot_key",
                 "_slot_general", "_exec_host"):
        setattr(plane, name, getattr(plane, name)[:cap].copy())
    plane._slot_deps = plane._slot_deps[:cap].copy()


def test_graph_plane_compaction_preserves_blocked_rows():
    """Window exhaustion re-packs pending rows to the bottom (dep cells
    and waiter cells remapped through the LUT): a missing-blocked row
    survives arbitrarily many compactions and executes when its dep
    finally commits; a duplicate commit of a long-executed dot still
    trips the loud assert after the re-pack."""
    g = BatchedDependencyGraph(1, SHARD, PLANE_CFG)
    plane = g._plane
    _shrink_plane(plane, 16)
    ghost = Dot(3, 1)
    g.handle_add(Dot(1, 1000), make_cmd(Dot(1, 1000), ["B"]), [dep(ghost)], TIME)
    assert g.commands_to_execute() == []
    last = None
    for i in range(50):
        d = Dot(1, i + 1)
        deps = [dep(last)] if last else []
        last = d
        g.handle_add(d, make_cmd(d, ["K"]), deps, TIME)
        assert [c.rifl for c in g.commands_to_execute()] == [Rifl(1, i + 1)]
    assert plane.stats["compactions"] >= 2
    assert plane.pending_count == 1
    assert plane.resident_uploads == 1 + plane.stats["compactions"] + plane.grows
    g.handle_add(ghost, make_cmd(ghost, ["B"]), [], TIME)
    got = [c.rifl for c in g.commands_to_execute()]
    assert got == [Rifl(3, 1), Rifl(1, 1000)]
    with pytest.raises(AssertionError, match="duplicate"):
        g.handle_add(Dot(1, 5), make_cmd(Dot(1, 5), ["K"]), [], TIME)
        g.commands_to_execute()


def test_graph_plane_width_growth_keeps_pending_state():
    """Dep fan-out beyond the resident width re-pads the dep matrix from
    the host mirrors (a counted grow) without losing blocked rows;
    already-executed deps encode to nothing and never widen."""
    g = BatchedDependencyGraph(1, SHARD, PLANE_CFG)
    plane = g._plane
    # executed deps: no widening
    prev = []
    for i in range(6):
        d = Dot(1, i + 1)
        g.handle_add(d, make_cmd(d, ["W"]), [], TIME)
        prev.append(d)
        g.commands_to_execute()
    g.handle_add(Dot(2, 1), make_cmd(Dot(2, 1), ["W"]), [dep(x) for x in prev], TIME)
    assert [c.rifl for c in g.commands_to_execute()] == [Rifl(2, 1)]
    assert plane._width == 4 and plane.grows == 0

    # pending deps: widen and survive
    g2 = BatchedDependencyGraph(1, SHARD, PLANE_CFG)
    plane2 = g2._plane
    ghost = Dot(3, 9)
    prev = []
    for i in range(6):
        d = Dot(1, i + 1)
        g2.handle_add(d, make_cmd(d, ["W"]), [dep(ghost)], TIME)
        prev.append(d)
    assert g2.commands_to_execute() == []
    g2.handle_add(Dot(2, 1), make_cmd(Dot(2, 1), ["W"]), [dep(x) for x in prev], TIME)
    assert g2.commands_to_execute() == []
    assert plane2._width == 8 and plane2.grows >= 1
    g2.handle_add(ghost, make_cmd(ghost, ["W"]), [], TIME)
    assert len(g2.commands_to_execute()) == 8


def _serving_rows(total=1024, keys=32, seed=7):
    """Single-key latest-per-key chains in commit order: the EPaxos
    serving shape (one dep per command, arrival mostly backward)."""
    rng = np.random.default_rng(seed)
    last = {}
    rows = []
    for i in range(total):
        k = int(rng.integers(0, keys))
        prev = last.get(k)
        last[k] = i + 1
        rows.append((1, i + 1, key_hash(f"sk{k}"), ((1 << 32) | prev) if prev else -1))
    return rows


def _serve_pipelined(depth, total=1024, feed=64):
    """The depth-K pipelined EPaxos serving loop through the plane:
    feeds dispatched up to K-1 rounds ahead, the order arrays drained as
    rounds retire, the tail flushed at end-of-stream."""
    g = BatchedDependencyGraph(1, SHARD, PLANE_CFG)
    g.record_order_arrays = True
    g._plane.pipeline_depth = depth
    g._plane.reserve(total)
    rows = _serving_rows(total)
    chunks = []
    for at in range(0, total, feed):
        chunk = rows[at : at + feed]
        src = np.array([r[0] for r in chunk], np.int64)
        seq = np.array([r[1] for r in chunk], np.int64)
        key = np.array([r[2] for r in chunk], np.int32)
        dd = np.array([[r[3]] for r in chunk], np.int64)
        cmds = [make_cmd(Dot(1, int(s)), ["x"]) for s in seq]
        g.handle_add_arrays(src, seq, key, dd, cmds, TIME)
        g.resolve_now(TIME)
        chunks.append(g.take_order_arrays())
    g.flush_plane_pipeline(TIME)
    chunks.append(g.take_order_arrays())
    return (
        np.concatenate([c[0] for c in chunks]),
        np.concatenate([c[1] for c in chunks]),
        g._plane,
    )


def test_graph_plane_pipelined_depths_bit_for_bit():
    """The depth-K pipelined serving loop: depths 1/2/3 drain the
    bit-for-bit identical execution order, and steady-state residency
    holds — resolves issue ZERO backlog re-uploads after the lazy
    initial materialization (only new-row deltas travel host->device)."""
    s1, q1, p1 = _serve_pipelined(1)
    s2, q2, p2 = _serve_pipelined(2)
    s3, q3, p3 = _serve_pipelined(3)
    assert len(q1) == 1024
    assert (s1 == s2).all() and (q1 == q2).all()
    assert (s1 == s3).all() and (q1 == q3).all()
    for plane in (p1, p2, p3):
        assert plane.resident_uploads == 1, (
            "steady-state serving must never re-upload the backlog"
        )
        assert plane.stats["compactions"] == 0
        assert plane.dispatches >= 16


def test_graph_plane_nonstructure_modes_parity():
    """The large-window modes (the keyed fast kernel without structure
    metrics + the resident peel-and-compact general path), forced at
    test scale via the unified kernel-size gate: identical per-key
    orders vs the host twin on shuffled feeds with missing deps and
    multi-key rows."""
    low = Config(
        3, 1, host_native_resolver=False, batched_graph_executor=True,
        device_graph_plane=True,
        graph_kernel_threshold=64,  # < the 1024-slot window: no structure
    )
    rng = random.Random(11)
    for _trial in range(3):
        args = random_adds(2, 3, rng)
        feeds = chop(rng, args)
        assert run_feeds(low, feeds) == run_feeds(HOST_CFG, feeds)
    # single-key chains ride the non-structure keyed kernel
    rng2 = np.random.default_rng(3)
    last = {}
    chain = []
    for i in range(96):
        k = int(rng2.integers(0, 8))
        prev = last.get(k)
        last[k] = Dot(1, i + 1)
        chain.append(
            (Dot(1, i + 1), [f"sk{k}"], {prev} if prev is not None else set())
        )
    feeds = [chain[at : at + 16] for at in range(0, 96, 16)]
    assert run_feeds(low, feeds) == run_feeds(HOST_CFG, feeds)


@pytest.mark.parametrize("mode", ["keyed", "general", "general_resident"])
def test_graph_plane_step_non_pow2_capacity(mode):
    """The plane's program at a capacity no plane builds (DevicePlane
    rounds every window to a power of two): 48 slots, where the keyed
    mode's residual size no longer divides the window.  Per-key chains
    arrive shuffled over four feeds, so deps routinely point at later
    slots or are MISSING until a later feed patches them; every mode
    must execute exactly what a plain host fixpoint does, dependencies
    first."""
    import jax.numpy as jnp

    from fantoch_tpu.ops.graph_resolve import (
        MISSING,
        TERMINAL,
        resolve_graph_plane_step,
    )

    cap, width, feed_rows = 48, 4, 10
    rng = random.Random(48)
    chains = [(k, i) for k in range(4) for i in range(10)]
    rng.shuffle(chains)
    state = (
        jnp.full((cap, width), TERMINAL, jnp.int32),
        jnp.zeros((cap,), jnp.int32),
        jnp.zeros((cap,), jnp.int32),
        jnp.zeros((cap,), jnp.int32),
        jnp.zeros((cap,), jnp.bool_),
        jnp.zeros((cap,), jnp.bool_),
    )
    # the host twin of the resident window
    deps = np.full((cap, width), TERMINAL, np.int32)
    occ = np.zeros(cap, bool)
    done = np.zeros(cap, bool)
    slot_of, waiting = {}, {}
    for at in range(0, len(chains), feed_rows):
        u_row = np.full(16, cap, np.int32)
        u_deps = np.full((16, width), TERMINAL, np.int32)
        u_key = np.zeros(16, np.int32)
        u_src = np.zeros(16, np.int32)
        u_seq = np.zeros(16, np.int32)
        patches = []
        for i, (k, pos) in enumerate(chains[at : at + feed_rows]):
            slot = at + i
            slot_of[(k, pos)] = slot
            u_row[i], u_key[i], u_src[i], u_seq[i] = slot, k, k + 1, pos + 1
            if pos:
                u_deps[i, 0] = slot_of.get((k, pos - 1), MISSING)
                if u_deps[i, 0] == MISSING:
                    waiting[(k, pos - 1)] = slot
            if (k, pos) in waiting:
                patches.append((waiting.pop((k, pos)), slot))
        p_row = np.full(16, cap, np.int32)
        p_col = np.zeros(16, np.int32)
        p_val = np.full(16, TERMINAL, np.int32)
        for j, (row, val) in enumerate(patches):
            p_row[j], p_val[j] = row, val
        e_row = np.full(4, cap, np.int32)

        # host fixpoint: a pending row executes once every dep cell is
        # TERMINAL or points at an executed slot; MISSING blocks
        live = u_row < cap
        deps[u_row[live]] = u_deps[live]
        occ[u_row[live]] = True
        for row, val in patches:
            # a row installed by this same feed takes its patch too
            deps[row, 0] = val
        want = done.copy()
        changed = True
        while changed:
            ready = (
                (deps == TERMINAL) | ((deps >= 0) & want[np.maximum(deps, 0)])
            ).all(axis=1)
            new = occ & ready & ~want
            changed = bool(new.any())
            want |= new

        out = resolve_graph_plane_step(
            *state,
            *(jnp.asarray(a) for a in (
                u_row, u_deps, u_key, u_src, u_seq, p_row, p_col, p_val, e_row,
            )),
            mode=mode,
        )
        state = tuple(out[:6])
        newly = np.asarray(out.newly)
        assert np.array_equal(newly, want & ~done), (mode, at)
        assert np.array_equal(np.asarray(out.executed), want)
        assert not np.asarray(out.stuck).any()
        order = np.asarray(out.order)
        assert sorted(order.tolist()) == list(range(cap))
        emitted = [r for r in order.tolist() if newly[r]]
        place = {r: i for i, r in enumerate(emitted)}
        for r in emitted:
            d = int(deps[r, 0])
            assert d < 0 or done[d] or place[d] < place[r], (mode, r, d)
        done = want
    assert done[: len(chains)].all() and not waiting


def test_graph_plane_monitor_watchdog():
    """The liveness watchdog on the plane: overdue missing dots surface
    for nudge_recovery, a typed StalledExecutionError fires past
    Config.executor_pending_fail_ms, and a lost execution (a waiter dot
    executed in the frontier with no wake) panics as
    pending-without-missing — the host twin's contract."""
    from fantoch_tpu.core.timing import SimTime
    from fantoch_tpu.errors import StalledExecutionError

    cfg = PLANE_CFG.with_(executor_pending_fail_ms=5000)
    time = SimTime()
    g = BatchedDependencyGraph(1, SHARD, cfg)
    ghost = Dot(2, 7)
    g.handle_add(Dot(1, 1), make_cmd(Dot(1, 1), ["a"]), [dep(ghost)], time)
    assert g.commands_to_execute() == []
    # young: nothing to report yet
    assert not g.monitor_pending(SimTime(100))
    # old but missing-blocked: nudge the missing dot
    assert g.monitor_pending(SimTime(2000)) == {ghost}
    # past the fail bound: typed stall naming the missing dep
    with pytest.raises(StalledExecutionError) as err:
        g.monitor_pending(SimTime(6000))
    assert ghost in err.value.missing[Dot(1, 1)]

    # lost execution: the ghost lands in the frontier without a wake
    g2 = BatchedDependencyGraph(1, SHARD, PLANE_CFG)
    g2.handle_add(Dot(1, 1), make_cmd(Dot(1, 1), ["a"]), [dep(ghost)], SimTime(0))
    assert g2.commands_to_execute() == []
    g2._frontier.add(ghost.source, ghost.sequence)
    with pytest.raises(AssertionError, match="without missing"):
        g2.monitor_pending(SimTime(5000))


def test_graph_plane_device_counters_seam():
    """The Executor.device_counters() seam (the table/pred planes'
    contract): dispatch/occupancy/upload tallies present and sane, None
    when the plane is off, capacity max-folded as a gauge."""
    from fantoch_tpu.executor.graph.executor import GraphExecutor, GraphAdd
    from fantoch_tpu.observability.device import merge_counters

    ex = GraphExecutor(1, SHARD, PLANE_CFG)
    for i in range(4):
        ex.handle(
            GraphAdd(Dot(1, i + 1), make_cmd(Dot(1, i + 1), ["c"]), set()),
            TIME,
        )
    counters = ex.device_counters()
    assert counters["graph_plane_dispatches"] >= 1
    assert counters["graph_plane_new_rows"] == 4
    assert counters["graph_plane_resident_uploads"] == 1
    assert counters["graph_plane_kernel_ms"] > 0
    assert counters["graph_plane_slot_capacity"] == ex.graph._plane._cap
    host_ex = GraphExecutor(
        1, SHARD, HOST_CFG.with_(batched_graph_executor=True)
    )
    assert host_ex.device_counters() is None
    folded = merge_counters({}, counters)
    folded = merge_counters(folded, counters)
    assert folded["graph_plane_new_rows"] == 8
    # capacity is a gauge: max-folded, never summed
    assert folded["graph_plane_slot_capacity"] == counters["graph_plane_slot_capacity"]


def test_graph_kernel_threshold_precedence(monkeypatch):
    """The kernel-size gate: Config.graph_kernel_threshold, else the
    built-in 4096; the plane it is handed to holds the same value, and
    the environment moves neither."""
    monkeypatch.setenv("FANTOCH_GRAPH_KERNEL_THRESHOLD", "123")
    g = BatchedDependencyGraph(1, SHARD, HOST_CFG)
    assert g._structure_threshold == 4096
    g = BatchedDependencyGraph(
        1, SHARD, PLANE_CFG.with_(graph_kernel_threshold=77)
    )
    assert g._structure_threshold == 77
    assert g._plane._structure_threshold == 77


def test_graph_threshold_both_branches_agree():
    """Both sides of the kernel-size gate produce identical per-key
    orders on the same workload (the table_kernel_threshold both-branch
    agreement test applied to the graph gate): a threshold of 1 forces
    the above-threshold branches (arrival fast path / resident general /
    no-structure kernels) where the default keeps the exact-structure
    branches."""
    rng = random.Random(19)
    args = random_adds(2, 3, rng)
    feeds = chop(rng, args)
    above = Config(3, 1, host_native_resolver=False, graph_kernel_threshold=1)
    assert run_feeds(above, feeds) == run_feeds(HOST_CFG, feeds)


def test_graph_plane_multi_shard_rejected():
    with pytest.raises(ValueError, match="shard_count"):
        BatchedDependencyGraph(
            1, SHARD,
            Config(3, 1, shard_count=2, batched_graph_executor=True,
                   device_graph_plane=True),
        )


def test_three_planes_share_the_device_plane_base():
    """The ROADMAP item-5 completion: votes-table, predecessors AND the
    graph backlog are the SAME machinery — one base owning buffer
    lifecycle, durability and counters — not three hand-rolled copies."""
    for klass in (DeviceTablePlane, DevicePredPlane, DeviceGraphPlane):
        assert issubclass(klass, DevicePlane)
        for member in (
            "_materialize", "_grow", "_upload", "_fetch_state",
            "_count_dispatch",
        ):
            assert getattr(klass, member) is getattr(DevicePlane, member), (
                f"{klass.__name__}.{member} forked from the base"
            )
    # the graph plane drains its in-flight ring before pickling but
    # otherwise keeps the base's snapshot protocol
    assert DeviceGraphPlane.__setstate__ is DevicePlane.__setstate__


# ---------------------------------------------------------------------------
# serving-path wiring: the sim and the process_runner executor pools
# ---------------------------------------------------------------------------


def test_epaxos_sim_with_device_graph_plane():
    """End-to-end EPaxos over the sim with the plane on: same per-key
    agreement across replicas (the sim_test harness drives the real
    protocol/executor stack — commits cross the boundary as arrays and
    order through the resident backlog)."""
    from harness import sim_test

    from fantoch_tpu.protocol import EPaxos

    sim_test(
        EPaxos,
        Config(
            n=3, f=1, batched_graph_executor=True, device_graph_plane=True,
            host_native_resolver=False,
        ),
        keys_per_command=1,
    )


def test_atlas_sim_with_device_graph_plane():
    from harness import sim_test

    from fantoch_tpu.protocol import Atlas

    sim_test(
        Atlas,
        Config(
            n=3, f=1, batched_graph_executor=True, device_graph_plane=True,
            host_native_resolver=False,
        ),
        keys_per_command=1,
    )


def test_run_epaxos_localhost_through_graph_plane():
    """The serving path: a 3-process localhost TCP EPaxos cluster whose
    executor pools order through the resident graph plane
    (process_runner -> GraphExecutor -> BatchedDependencyGraph ->
    DeviceGraphPlane), with cross-replica per-key agreement and the
    plane counters visible through the runtime's device-counter fold."""
    from test_run_localhost import run_cluster

    from fantoch_tpu.protocol import EPaxos

    _slow, runtimes = run_cluster(
        EPaxos,
        Config(
            n=3, f=1, batched_graph_executor=True, device_graph_plane=True,
            host_native_resolver=False,
        ),
        keys_per_command=1,
        return_runtimes=True,
    )
    for runtime in runtimes.values():
        counters = runtime._device_counters()
        assert counters["graph_plane_dispatches"] > 0
        assert (
            counters["graph_plane_resident_uploads"]
            <= 1
            + counters["graph_plane_compactions"]
            + counters["graph_plane_grows"]
        )
