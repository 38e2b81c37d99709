"""Multi-chip SPMD protocol step on the virtual 8-device CPU mesh."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fantoch_tpu.ops.graph_resolve import TERMINAL
from fantoch_tpu.parallel import mesh_step


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest must force 8 virtual devices"
    return mesh_step.make_mesh(8)


def test_mesh_axes(mesh):
    assert set(mesh.axis_names) == {"replica", "batch"}
    assert mesh.shape["replica"] * mesh.shape["batch"] == 8


def test_intra_batch_chain():
    key = jnp.asarray([3, 5, 3, 3, 5, 9], dtype=jnp.int32)
    chain = mesh_step._intra_batch_chain(key[:, None])
    assert chain[:, 0].tolist() == [TERMINAL, TERMINAL, 0, 2, 1, TERMINAL]


@pytest.mark.parametrize("rows", (300, 40000))
def test_chain_of_one_class_by_a_loop_packed_or_too_long_to_pack(rows):
    """The latest earlier read, and write, of each slot's key against a
    loop over a dict: at 40000 slots a position and its slot no longer fit
    an int32 together and the chain gathers the slot instead."""
    rng = np.random.default_rng(rows)
    keys, read = rng.integers(0, rows // 6, (rows, 1)).astype(np.int32), rng.random(rows) < 0.5
    perm, head, read_at = mesh_step._key_runs(jnp.asarray(keys), jnp.asarray(read))
    assert read_at.tolist() == read[np.asarray(perm)].tolist()
    for member, wanted in ((read_at, True), (~read_at, False), (None, None)):
        latest, want = {}, []
        for row, (key, is_read) in enumerate(zip(keys[:, 0].tolist(), read.tolist())):
            want.append(latest.get(key, TERMINAL))
            if wanted is None or is_read == wanted:
                latest[key] = row
        assert mesh_step._chain_of_runs(perm, head, keys.shape, member)[:, 0].tolist() == want


def test_intra_batch_chain_multikey():
    # rows tagged with up to two keys; per-slot chains follow each key
    keys = jnp.asarray(
        [[3, 5], [5, 9], [3, 9], [9, 3]], dtype=jnp.int32
    )
    chain = mesh_step._intra_batch_chain(keys)
    # row0: first on 3 and 5; row1: 5<-row0, first on 9;
    # row2: 3<-row0, 9<-row1; row3: 9<-row2, 3<-row2
    assert chain.tolist() == [
        [TERMINAL, TERMINAL],
        [0, TERMINAL],
        [0, 1],
        [2, 2],
    ]


def test_protocol_step_executes_batch(mesh):
    num_replicas = 2 * mesh.shape["replica"]
    batch = 8 * mesh.shape["batch"]
    state = mesh_step.init_state(mesh, num_replicas, key_buckets=16)
    step = mesh_step.jit_protocol_step(mesh)

    rng = np.random.default_rng(1)
    key = jnp.asarray(rng.integers(0, 4, size=batch), dtype=jnp.int32)
    src = jnp.asarray(rng.integers(1, num_replicas + 1, size=batch), jnp.int32)
    seq = jnp.arange(batch, dtype=jnp.int32)

    state, out = step(state, key, src, seq)
    gids = np.asarray(out.gids)
    valid = gids >= 0
    resolved = np.asarray(out.resolved)
    assert resolved[valid].all()
    work = len(gids)
    # order is a permutation of the working rows
    assert sorted(out.order.tolist()) == list(range(work))
    # deps respect execution order: a command's dependency executes first
    pos = np.empty(work, dtype=np.int64)
    pos[np.asarray(out.order)] = np.arange(work)
    pos_by_gid = {int(g): pos[i] for i, g in enumerate(gids) if g >= 0}
    deps = np.asarray(out.deps_gid)
    for i in range(work):
        if not valid[i]:
            continue
        for d in deps[i]:
            if d >= 0:
                assert pos_by_gid[int(d)] < pos[i], f"dep of {i} executed after it"
    # state advanced
    assert int(state.next_gid) == batch
    assert state.frontier.tolist() == [batch] * num_replicas
    assert int(out.pending) == 0 and int(out.pend_dropped) == 0


def test_protocol_step_fast_path_divergence(mesh):
    """Replicas that disagree on prior deps (different key_clock entries)
    must not take the fast path; the committed dep is the union max."""
    num_replicas = mesh.shape["replica"] * 2
    batch = mesh.shape["batch"] * 8
    state = mesh_step.init_state(mesh, num_replicas, key_buckets=16)
    # replica 0 saw gid 7 on key 3; others saw nothing
    kc = np.array(state.key_clock)
    kc[0, 3] = 7
    state = state._replace(
        key_clock=jax.device_put(
            jnp.asarray(kc), state.key_clock.sharding
        ),
        next_gid=jnp.int32(100),
    )
    step = mesh_step.jit_protocol_step(mesh)

    key = jnp.full((batch,), 5, dtype=jnp.int32).at[0].set(3)
    src = jnp.ones((batch,), jnp.int32)
    seq = jnp.arange(batch, dtype=jnp.int32)
    state, out = step(state, key, src, seq)

    fast = np.asarray(out.fast_path)
    deps = np.asarray(out.deps_gid)[:, 0]
    valid = np.asarray(out.gids) >= 0
    new0 = state.pend_gid.shape[0]  # first new-batch working row
    assert not fast[new0], "diverging replica views must take the slow path"
    assert deps[new0] == 7, "union of reported deps = max gid"
    # the rest of the batch chains on key 5: deterministic, fast path
    assert fast[new0 + 1 :].all()
    # the Synod accept round committed the fast-path miss
    assert int(out.slow_paths) == 1
    assert np.asarray(out.resolved)[valid].all(), "slow-path command still commits"
    # GC watermark: all replicas executed the whole round
    assert int(out.stable) == batch


def test_slow_path_fails_without_write_quorum(mesh):
    """With fewer live replicas than the write quorum, slow-path commands
    do not commit — and neither does anything chained on them."""
    num_replicas = mesh.shape["replica"] * 2  # n=4: f=2, write quorum 3
    batch = mesh.shape["batch"] * 8
    state = mesh_step.init_state(mesh, num_replicas, key_buckets=16)
    kc = np.array(state.key_clock)
    kc[0, 3] = 7  # replica 0 alone saw a prior commit on key 3
    state = state._replace(
        key_clock=jax.device_put(jnp.asarray(kc), state.key_clock.sharding),
        next_gid=jnp.int32(100),
    )
    # only 2 live replicas < write quorum 3
    step = mesh_step.jit_protocol_step(mesh, live_replicas=2)

    key = jnp.full((batch,), 3, dtype=jnp.int32)  # all chained on key 3
    src = jnp.ones((batch,), jnp.int32)
    seq = jnp.arange(batch, dtype=jnp.int32)
    state, out = step(state, key, src, seq)

    resolved = np.asarray(out.resolved)
    new0 = state.pend_gid.shape[0]
    assert not np.asarray(out.fast_path)[new0], "cmd 0 sees diverging views"
    assert not resolved[new0], "no write quorum -> slow-path cmd uncommitted"
    # every later command chains (directly or transitively) on cmd 0
    assert not resolved.any(), "dependents of an uncommitted cmd cannot run"
    assert int(out.stable) == 0
    # the liveness fix: the whole round is carried, not dropped
    assert int(out.pending) == batch and int(out.pend_dropped) == 0


def test_state_carries_across_steps(mesh):
    """Round 2 commands conflict with round 1 via the key clock."""
    num_replicas = mesh.shape["replica"]
    batch = mesh.shape["batch"] * 4
    state = mesh_step.init_state(mesh, num_replicas, key_buckets=8)
    step = mesh_step.jit_protocol_step(mesh)

    key = jnp.zeros((batch,), jnp.int32)  # everyone on key 0
    src = jnp.ones((batch,), jnp.int32)
    seq = jnp.arange(batch, dtype=jnp.int32)
    state, _ = step(state, key, src, seq)

    state, out = step(state, key, src, seq)
    deps = np.asarray(out.deps_gid)[:, 0]
    valid = np.asarray(out.gids) >= 0
    new0 = state.pend_gid.shape[0]
    # first command of round 2 depends on the last command of round 1
    assert deps[new0] == batch - 1
    assert np.asarray(out.resolved)[valid].all()


def test_protocol_step_multikey(mesh):
    """Multi-key commands (two key buckets each) route through the general
    resolver on-mesh: per-slot deps all execute before their dependents,
    and round-2 chains continue from both key-clock slots."""
    num_replicas = mesh.shape["replica"]
    batch = mesh.shape["batch"] * 4
    state = mesh_step.init_state(
        mesh, num_replicas, key_buckets=16, key_width=2
    )
    step = mesh_step.jit_protocol_step(mesh)

    rng = np.random.default_rng(3)
    keys = np.stack(
        [rng.choice(6, size=2, replace=False) for _ in range(batch)]
    ).astype(np.int32)
    src = jnp.ones((batch,), jnp.int32)
    seq = jnp.arange(batch, dtype=jnp.int32)
    state, out = step(state, jnp.asarray(keys), src, seq)

    gids = np.asarray(out.gids)
    valid = gids >= 0
    assert np.asarray(out.resolved)[valid].all()
    work = len(gids)
    pos = np.empty(work, np.int64)
    pos[np.asarray(out.order)] = np.arange(work)
    pos_by_gid = {int(g): pos[i] for i, g in enumerate(gids) if g >= 0}
    deps = np.asarray(out.deps_gid)
    for i in range(work):
        if not valid[i]:
            continue
        for d in deps[i]:
            if d >= 0:
                assert pos_by_gid[int(d)] < pos[i], f"dep of {i} after it"

    # round 2 on the same key sets: both dep slots of the first round-2
    # command come from round 1 via the replicated key clock
    seq2 = jnp.arange(batch, 2 * batch, dtype=jnp.int32)
    state, out2 = step(state, jnp.asarray(keys), src, seq2)
    new0 = state.pend_gid.shape[0]
    deps2 = np.asarray(out2.deps_gid)
    # the latest write of each key; no read since it (the second half)
    assert (deps2[new0, :2] >= 0).all() and (deps2[new0, :2] < batch).all()
    assert (deps2[:, 2:] == -1).all()
    assert np.asarray(out2.resolved)[np.asarray(out2.gids) >= 0].all()
    assert state.frontier.tolist() == [2 * batch] * num_replicas


@pytest.mark.slow
def test_multikey_pending_commits_after_quorum_recovers(mesh):
    """Degraded-quorum liveness on the MULTI-key path: MISSING deps route
    through resolve_general's iterative branch inside shard_map; carried
    commands commit once the quorum recovers (the comment in
    mesh_step.py's resolver dispatch, proven rather than asserted)."""
    num_replicas = mesh.shape["replica"] * 2  # n=4: write quorum 3
    batch = mesh.shape["batch"] * 4
    state = mesh_step.init_state(
        mesh, num_replicas, key_buckets=16, pending_capacity=2 * batch,
        key_width=2,
    )
    kc = np.array(state.key_clock)
    kc[0, 3] = 7  # replica 0 alone saw a prior commit on key 3: slow path
    state = state._replace(
        key_clock=jax.device_put(jnp.asarray(kc), state.key_clock.sharding),
        next_gid=jnp.int32(100),
    )

    degraded = mesh_step.jit_protocol_step(mesh, live_replicas=2)
    # every command touches key 3 (the diverging one) plus a second key
    keys = np.stack(
        [[3, 4 + (i % 4)] for i in range(batch)]
    ).astype(np.int32)
    src = jnp.ones((batch,), jnp.int32)
    seq = jnp.arange(batch, dtype=jnp.int32)
    state, out1 = degraded(state, jnp.asarray(keys), src, seq)
    assert not np.asarray(out1.resolved).any(), "no write quorum -> no commit"
    assert int(out1.pending) == batch

    healthy = mesh_step.jit_protocol_step(mesh)
    keys2 = np.stack(
        [[8 + (i % 4), 12 + (i % 3)] for i in range(batch)]
    ).astype(np.int32)
    seq2 = jnp.arange(batch, 2 * batch, dtype=jnp.int32)
    state, out2 = healthy(state, jnp.asarray(keys2), src, seq2)

    gids = np.asarray(out2.gids)
    resolved = np.asarray(out2.resolved)
    carried = (gids >= 100) & (gids < 100 + batch)
    assert carried.sum() == batch
    assert resolved[carried].all(), "carried multi-key commands must commit"
    assert resolved[gids >= 0].all()
    assert int(out2.pending) == 0
    assert state.frontier.tolist() == [2 * batch] * num_replicas


def test_pending_commands_commit_after_quorum_recovers(mesh):
    """The VERDICT r2 weak-#4 liveness scenario: a quorum-failed round's
    commands carry in the device-resident pending buffer and commit in a
    later round once enough replicas are live again."""
    num_replicas = mesh.shape["replica"] * 2  # n=4: write quorum 3
    batch = mesh.shape["batch"] * 4
    state = mesh_step.init_state(
        mesh, num_replicas, key_buckets=16, pending_capacity=2 * batch
    )
    kc = np.array(state.key_clock)
    kc[0, 3] = 7  # replica 0 alone saw a prior commit on key 3: slow path
    state = state._replace(
        key_clock=jax.device_put(jnp.asarray(kc), state.key_clock.sharding),
        next_gid=jnp.int32(100),
    )

    degraded = mesh_step.jit_protocol_step(mesh, live_replicas=2)
    key = jnp.full((batch,), 3, dtype=jnp.int32)
    src = jnp.ones((batch,), jnp.int32)
    seq = jnp.arange(batch, dtype=jnp.int32)
    state, out1 = degraded(state, key, src, seq)
    assert not np.asarray(out1.resolved).any()
    assert int(out1.pending) == batch

    # quorum recovers; a fresh (disjoint-key) batch arrives
    healthy = mesh_step.jit_protocol_step(mesh)
    key2 = jnp.full((batch,), 9, dtype=jnp.int32)
    seq2 = jnp.arange(batch, 2 * batch, dtype=jnp.int32)
    state, out2 = healthy(state, key2, src, seq2)

    gids = np.asarray(out2.gids)
    resolved = np.asarray(out2.resolved)
    carried = (gids >= 100) & (gids < 100 + batch)
    assert carried.sum() == batch, "round-1 commands must be in the working set"
    assert resolved[carried].all(), "carried commands commit after recovery"
    assert resolved[gids >= 0].all()
    assert int(out2.pending) == 0
    # every replica executed both rounds
    assert state.frontier.tolist() == [2 * batch] * num_replicas


# --- Newt timestamp round on the mesh ---


def _newt_setup(mesh, f=1, key_buckets=64, live_replicas=None, pending=64):
    num_replicas = 2 * mesh.shape[mesh_step.REPLICA_AXIS]
    batch = 8 * mesh.shape[mesh_step.BATCH_AXIS]
    state = mesh_step.init_newt_state(
        mesh, num_replicas, key_buckets=key_buckets, pending_capacity=pending
    )
    step = mesh_step.jit_newt_step(mesh, f=f, live_replicas=live_replicas)
    return num_replicas, batch, state, step


def test_newt_step_commits_and_stabilizes(mesh):
    """A healthy round commits everything on the fast path (identical
    replica clocks -> every quorum member reports the same max) and the
    whole batch is stable-ordered by (clock, dot) per key."""
    num_replicas, batch, state, step = _newt_setup(mesh)
    rng = np.random.default_rng(0)
    key = jnp.asarray(rng.integers(0, 4, size=batch), jnp.int32)
    src = jnp.asarray(rng.integers(1, num_replicas + 1, size=batch), jnp.int32)
    seq = jnp.arange(batch, dtype=jnp.int32)
    state, out = step(state, key, src, seq)
    executed = np.asarray(out.executed)
    assert executed.sum() == batch
    assert np.asarray(out.fast_path).sum() == batch
    assert int(out.slow_paths) == 0
    assert int(out.pending) == 0
    # (clock, dot)-sorted execution, per-key clocks strictly increasing
    order = np.asarray(out.order)
    clock = np.asarray(out.clock)
    pend_cap = state.pend_key.shape[0]
    keys_w = np.concatenate([np.full(pend_cap, -1, np.int32), np.asarray(key)])
    last = {}
    for w in order:
        if not executed[w]:
            continue
        k = int(keys_w[w])
        assert last.get(k, 0) < clock[w]
        last[k] = int(clock[w])


def test_newt_clocks_continue_across_rounds(mesh):
    """Round 2 proposals continue above round 1's committed clocks per
    key (the device key-clock table carries)."""
    num_replicas, batch, state, step = _newt_setup(mesh)
    key = jnp.asarray(np.zeros(batch), jnp.int32)  # one hot key
    src = jnp.asarray(np.ones(batch), jnp.int32)
    state, out1 = step(state, key, src, jnp.arange(batch, dtype=jnp.int32))
    state, out2 = step(
        state, key, src, jnp.arange(batch, 2 * batch, dtype=jnp.int32)
    )
    c1 = np.asarray(out1.clock)[np.asarray(out1.executed)]
    c2 = np.asarray(out2.clock)[np.asarray(out2.executed)]
    assert len(c1) == len(c2) == batch
    assert c2.min() > c1.max()


@pytest.mark.slow
def test_newt_degraded_quorum_carries_pending(mesh):
    """With fewer live replicas than the write quorum, slow-path commands
    cannot commit; they carry in the pending buffer and commit + execute
    once the quorum recovers."""
    num_replicas, batch, state, step = _newt_setup(mesh)
    key = jnp.asarray(np.zeros(batch), jnp.int32)
    src = jnp.asarray(np.ones(batch), jnp.int32)
    # stagger replica 0's key clock so the first proposal's max is unique
    # to one replica (max_count < f is impossible at f=1; force the slow
    # path by staggering so that the max is reported once... at f=1 a
    # single report satisfies the fast path, so instead degrade below the
    # write quorum AND the fast path by staggering every quorum member
    # differently via distinct priors)
    kc = np.array(state.key_clock)
    for r in range(num_replicas):
        kc[r, 0] = r * 10  # all replicas disagree on the hot key's clock
    state = state._replace(
        key_clock=jax.device_put(jnp.asarray(kc), state.key_clock.sharding)
    )
    degraded = mesh_step.jit_newt_step(mesh, f=1, live_replicas=0)
    state, out = degraded(state, key, src, jnp.arange(batch, dtype=jnp.int32))
    # fast path needs the max reported >= f times: the max proposal comes
    # only from the staggered top replica if it is in the fast quorum...
    # at f=1 one report suffices, so fast commits still happen; what must
    # NOT happen is slow-path commits with zero live replicas
    committed = np.asarray(out.committed)
    fast = np.asarray(out.fast_path)
    assert (committed == fast).all(), "slow path must not commit with no live acks"
    carried = int(out.pending)
    # fast-path commits with no live replicas cannot stabilize either
    # (frontiers never advance), so they carry too
    assert carried == batch - np.asarray(out.executed).sum()

    # recovery: everything (carried + nothing new) commits and executes
    recovered = mesh_step.jit_newt_step(mesh, f=1)
    empty = jnp.full((batch,), mesh_step.KEY_PAD, jnp.int32)
    zeros = jnp.zeros((batch,), jnp.int32)
    state, out2 = recovered(state, empty, zeros, zeros)
    assert int(out2.pending) == 0
    assert np.asarray(out2.executed).sum() == carried


def test_newt_stability_with_lagging_minority(mesh):
    """With a minority of replicas dead, commits still stabilize: the
    (n - threshold)-th smallest frontier ignores the laggards (the Newt
    stability condition, mod.rs:247-270)."""
    num_replicas, batch, state, step = _newt_setup(mesh)
    f = 1
    live = num_replicas - f  # one dead replica
    partial = mesh_step.jit_newt_step(mesh, f=f, live_replicas=live)
    key = jnp.asarray(np.arange(batch) % 3, jnp.int32)
    src = jnp.asarray(np.ones(batch), jnp.int32)
    state, out = partial(state, key, src, jnp.arange(batch, dtype=jnp.int32))
    assert np.asarray(out.executed).sum() == batch, (
        "a lagging minority must not block timestamp stability"
    )


# --- leader-based (FPaxos/MultiPaxos) slot round ---


def test_paxos_step_slot_order_and_recovery(mesh):
    """The third consensus class on the mesh: a healthy round commits and
    executes the whole batch in contiguous slot order; with fewer live
    acceptors than f+1 nothing commits and rows carry with their slots
    (MultiPaxos slot stickiness); recovery commits the SAME slots and the
    frontier resumes contiguously."""
    batch = 8 * mesh.shape[mesh_step.BATCH_AXIS]
    state = mesh_step.init_paxos_state(mesh, pending_capacity=2 * batch)
    step = mesh_step.jit_paxos_step(mesh, f=1)
    valid = jnp.ones(batch, bool)
    src = jnp.ones(batch, jnp.int32)
    seq = jnp.arange(batch, dtype=jnp.int32)

    state, out = step(state, valid, src, seq)
    executed = np.asarray(out.executed)
    slots = np.asarray(out.slot)
    assert executed.sum() == batch
    assert sorted(slots[executed].tolist()) == list(range(batch))
    assert int(state.exec_frontier) == batch

    degraded = mesh_step.jit_paxos_step(mesh, f=1, live_replicas=1)
    state, out2 = degraded(state, valid, src, seq + batch)
    assert np.asarray(out2.executed).sum() == 0
    assert int(out2.pending) == batch

    state, out3 = step(state, jnp.zeros(batch, bool), src, seq)
    ex3 = np.asarray(out3.executed)
    slots3 = np.asarray(out3.slot)
    assert ex3.sum() == batch
    assert sorted(slots3[ex3].tolist()) == list(range(batch, 2 * batch))
    assert int(state.exec_frontier) == 2 * batch


def test_paxos_overflow_reclaims_slots(mesh):
    """Pending overflow must not wedge the slot log: dropped rows are the
    HIGHEST slots and the slot counter rolls back over them, so later
    rounds re-fill a dense log and the contiguous frontier never freezes
    (the livelock a naive drop creates)."""
    batch = 8 * mesh.shape[mesh_step.BATCH_AXIS]
    cap = batch // 2
    state = mesh_step.init_paxos_state(mesh, pending_capacity=cap)
    degraded = mesh_step.jit_paxos_step(mesh, f=1, live_replicas=1)
    valid = jnp.ones(batch, bool)
    src = jnp.ones(batch, jnp.int32)
    seq = jnp.arange(batch, dtype=jnp.int32)
    state, out1 = degraded(state, valid, src, seq)
    assert int(out1.pend_dropped) == batch - cap
    # the dropped (highest) slots were reclaimed
    assert int(state.next_slot) == cap

    # recovery: the carried low slots commit; new commands take the
    # reclaimed slot numbers — the log stays dense and fully executes
    step = mesh_step.jit_paxos_step(mesh, f=1)
    state, out2 = step(state, valid, src, seq + batch)
    assert np.asarray(out2.executed).sum() == cap + batch
    assert int(state.exec_frontier) == cap + batch
    assert int(state.next_slot) == cap + batch


def test_newt_multikey_round(mesh):
    """Multi-key commands through the Newt mesh round: every command
    commits and executes once its clock is stable on ALL its keys, per-key
    (clock, dot) order is monotone within the round, and round-2 clocks on
    the same keys strictly dominate round 1's commits."""
    num_replicas = 2 * mesh.shape[mesh_step.REPLICA_AXIS]
    batch = 8 * mesh.shape[mesh_step.BATCH_AXIS]
    state = mesh_step.init_newt_state(
        mesh, num_replicas, key_buckets=64, pending_capacity=64, key_width=2
    )
    step = mesh_step.jit_newt_step(mesh, f=1)
    rng = np.random.default_rng(3)
    keys = jnp.asarray(
        np.stack([rng.choice(6, size=2, replace=False) for _ in range(batch)]),
        dtype=jnp.int32,
    )
    src = jnp.asarray(rng.integers(1, num_replicas + 1, size=batch), jnp.int32)
    seq = jnp.arange(batch, dtype=jnp.int32)
    state, out = step(state, keys, src, seq)
    executed = np.asarray(out.executed)
    clock = np.asarray(out.clock)
    order = np.asarray(out.order)
    assert executed.sum() == batch
    # per-key clocks non-decreasing along the execution order
    pend_cap = state.pend_key.shape[0]
    keys_np = np.asarray(keys)
    last = {}
    for w in order:
        if not executed[w]:
            continue
        for k in keys_np[w - pend_cap]:
            assert last.get(int(k), -1) <= clock[w]
            last[int(k)] = int(clock[w])
    r1_max = clock[executed].max()

    # round 2 on the same key space strictly dominates per key
    state, out2 = step(state, keys, src, seq + batch)
    c2 = np.asarray(out2.clock)
    e2 = np.asarray(out2.executed)
    assert e2.sum() == batch
    for w in np.nonzero(e2)[0]:
        for k in keys_np[w - pend_cap]:
            assert c2[w] > last[int(k)]  # strict per-key domination
    assert c2[e2].max() > r1_max


@pytest.mark.slow
def test_newt_multikey_holdback_preserves_per_key_order(mesh):
    """Regression (r4 review): a multi-key command stable on key A but
    blocked by key B must hold back higher-clocked commands on A, or A's
    (clock, dot) execution order breaks across rounds.  Staged state: key
    0's stability watermark is far ahead (1000) while key 1 lags at 0; a
    carried committed command D{0,1} at clock 5 stays blocked (minority
    of live replicas, so its votes cannot stabilize key 1); a fresh
    command F{0} commits at clock 101 <= stable(key 0) — without the
    holdback it would execute past D on key 0."""
    num_replicas = 2 * mesh.shape[mesh_step.REPLICA_AXIS]
    batch = 8 * mesh.shape[mesh_step.BATCH_AXIS]
    state = mesh_step.init_newt_state(
        mesh, num_replicas, key_buckets=8, pending_capacity=8, key_width=2
    )
    vf = np.array(state.vote_frontier)
    vf[:, 0] = 1000  # key 0 pre-stable far ahead
    kc = np.array(state.key_clock)
    kc[:, 0] = 100
    pend_key = np.full((8, 2), mesh_step.KEY_PAD, np.int32)
    pend_key[0] = [0, 1]  # D{0,1}, committed at clock 5
    pend = lambda a: jax.device_put(jnp.asarray(a, dtype=jnp.int32))
    state = state._replace(
        vote_frontier=jax.device_put(jnp.asarray(vf), state.vote_frontier.sharding),
        key_clock=jax.device_put(jnp.asarray(kc), state.key_clock.sharding),
        pend_key=pend(pend_key),
        pend_src=pend([1] + [-1] * 7),
        pend_seq=pend([1] + [-1] * 7),
        pend_clock=pend([5] + [-1] * 7),
    )
    # minority-live round: D's carried votes cannot stabilize key 1
    step1 = mesh_step.jit_newt_step(mesh, f=1, live_replicas=1)
    keys = np.full((batch, 2), mesh_step.KEY_PAD, np.int32)
    keys[0, 0] = 0  # F{0}
    state, out = step1(
        state,
        jnp.asarray(keys),
        jnp.asarray(np.r_[2, np.zeros(batch - 1)].astype(np.int32)),
        jnp.asarray(np.r_[1, np.zeros(batch - 1)].astype(np.int32)),
    )
    executed = np.asarray(out.executed)
    committed = np.asarray(out.committed)
    clock = np.asarray(out.clock)
    assert committed[8] and clock[8] > 100, "F must commit above key 0's clock"
    assert committed[0] and not executed[0], "D stays blocked by key 1"
    assert not executed[8], (
        "F executed past the lower-clocked blocked command D on key 0"
    )
    assert int(out.pending) == 2

    # full-live round: D's votes stabilize key 1; D then F execute in
    # (clock, dot) order
    step2 = mesh_step.jit_newt_step(mesh, f=1)
    empty = jnp.full((batch, 2), mesh_step.KEY_PAD, jnp.int32)
    zeros = jnp.zeros((batch,), jnp.int32)
    state, out2 = step2(state, empty, zeros, zeros)
    ex2 = np.asarray(out2.executed)
    clock2 = np.asarray(out2.clock)
    order2 = np.asarray(out2.order)
    assert ex2.sum() == 2
    ex_rows = [w for w in order2 if ex2[w]]
    assert clock2[ex_rows[0]] < clock2[ex_rows[1]], "D must execute before F"


# ---------------------------------------------------------------------------
# partial replication on ONE mesh: sharded key axis + per-shard quorums
# ---------------------------------------------------------------------------


def test_sharded_step_cross_shard_dependencies(mesh):
    """shard_count=2 on one mesh (6 replica rows = 2 shards x 3): a
    multi-shard command orders after its dependency chains on BOTH
    shards' buckets in one round — the mesh-native form of the
    cross-shard dep requests of fantoch_ps/src/executor/graph/
    mod.rs:279-408 — and each shard's replicas learn only their own
    buckets' key state."""
    m = mesh_step.make_mesh(num_replicas=6)
    state = mesh_step.init_state(m, 6, key_buckets=64, key_width=2)
    step = mesh_step.jit_protocol_step(m, shard_count=2)
    KP = mesh_step.KEY_PAD

    # bucket 4 -> shard 0, bucket 5 -> shard 1 (b % 2)
    # rows: two on each shard's bucket, then a multi-shard row, then one
    # more on each bucket — the multi row must land between them on BOTH
    key = jnp.asarray(
        [[4, KP], [5, KP], [4, KP], [5, KP], [4, 5], [4, KP], [5, KP]]
        + [[KP, KP]] * 1,
        dtype=jnp.int32,
    )
    batch = key.shape[0]
    src = jnp.ones((batch,), jnp.int32)
    seq = jnp.arange(batch, dtype=jnp.int32)
    state, out = step(state, key, src, seq)
    gids = np.asarray(out.gids)
    resolved = np.asarray(out.resolved)
    order = np.asarray(out.order)
    valid = gids >= 0
    assert resolved[valid].all(), "healthy sharded round must resolve all"

    # positions in the execution order (working rows: pend_cap offset)
    pend_cap = state.pend_gid.shape[0]
    pos = {int(gids[w]): i for i, w in enumerate(order) if gids[w] >= 0}
    g = lambda i: i  # gid == batch index here (fresh state, next_gid=0)
    multi = pos[g(4)]
    assert pos[g(0)] < pos[g(2)] < multi < pos[g(5)]  # shard-0 chain
    assert pos[g(1)] < pos[g(3)] < multi < pos[g(6)]  # shard-1 chain

    # ownership: shard-0 rows (0..2) never learned bucket 5, shard-1
    # rows (3..5) never learned bucket 4
    kc = np.asarray(state.key_clock)
    assert (kc[0:3, 5] == -1).all() and (kc[3:6, 4] == -1).all()
    assert (kc[0:3, 4] >= 0).all() and (kc[3:6, 5] >= 0).all()


@pytest.mark.slow
def test_sharded_step_degraded_shard_blocks_multi_shard(mesh):
    """A dead majority in ONE shard blocks that shard's slow-path
    commands AND any multi-shard command touching it, while the healthy
    shard keeps committing; recovery commits the carried rows."""
    m = mesh_step.make_mesh(num_replicas=6)
    state = mesh_step.init_state(m, 6, key_buckets=64, key_width=2)
    healthy = mesh_step.jit_protocol_step(m, shard_count=2)
    KP = mesh_step.KEY_PAD

    # round 1 (healthy): seed both buckets so the clocks hold real gids
    key1 = jnp.asarray([[4, KP], [5, KP]], dtype=jnp.int32)
    state, out1 = step_pad(healthy, state, key1)
    assert np.asarray(out1.resolved)[np.asarray(out1.gids) >= 0].all()

    # stagger shard 1's member-0 view of bucket 5 (rows 3..5 are shard 1;
    # fq = members 0,1 = rows 3,4): fast path must miss there
    kc = np.array(state.key_clock)
    kc[3, 5] = 0  # an older *executed* gid (gid 0 was row 0 of round 1)
    state = state._replace(
        key_clock=jax.device_put(jnp.asarray(kc), state.key_clock.sharding)
    )

    # round 2 under a dead shard-1 majority (rows 0..3 live = shard 0
    # full + shard 1 member 0 only): shard-0 command commits; the
    # bucket-5 command and the multi-shard command carry
    degraded = mesh_step.jit_protocol_step(m, shard_count=2, live_replicas=4)
    key2 = jnp.asarray([[4, KP], [5, KP], [4, 5]], dtype=jnp.int32)
    state, out2 = step_pad(degraded, state, key2, seq0=10)
    gids2 = np.asarray(out2.gids)
    res2 = np.asarray(out2.resolved)
    rows2 = res2[gids2 >= 0]  # batch rows in order (pads commit as no-ops)
    assert rows2[0], "the shard-0 command must commit"
    assert not rows2[1] and not rows2[2], (
        "the bucket-5 and multi-shard commands must carry"
    )
    assert int(out2.pending) == 2

    # round 3 recovered: carried rows commit and resolve
    state, out3 = step_pad(healthy, state, None, batch=3)
    gids3 = np.asarray(out3.gids)
    assert np.asarray(out3.resolved)[gids3 >= 0].all()
    assert int(out3.pending) == 0


def step_pad(step, state, key, seq0=0, batch=None):
    """Run one step, padding the key matrix to a mesh-divisible batch."""
    KP = mesh_step.KEY_PAD
    b = 8  # divisible by any batch-axis factor of 8 devices
    full = jnp.full((b, state.pend_key.shape[1]), KP, dtype=jnp.int32)
    if key is not None:
        full = full.at[: key.shape[0]].set(key)
    src = jnp.ones((b,), jnp.int32)
    seq = jnp.arange(seq0, seq0 + b, dtype=jnp.int32)
    return step(state, full, src, seq)


def test_sharded_newt_cross_shard_clocks(mesh):
    """shard_count=2 on the Newt round (6 replica rows = 2 shards x 3):
    per-key clocks advance per shard, a multi-shard command's commit
    clock is the max over its shards' clocks (the MShardCommit
    aggregation), per-key execution order is (clock, dot) on each
    shard's bucket, and replicas never learn foreign buckets."""
    m = mesh_step.make_mesh(num_replicas=6)
    state = mesh_step.init_newt_state(
        m, 6, key_buckets=64, pending_capacity=16, key_width=2
    )
    step = mesh_step.jit_newt_step(m, f=1, shard_count=2)
    KP = mesh_step.KEY_PAD

    # bucket 4 -> shard 0 (rows 0..2), bucket 5 -> shard 1 (rows 3..5)
    key = jnp.asarray(
        [[4, KP], [5, KP], [4, KP], [5, KP], [4, 5], [4, KP], [5, KP]]
        + [[KP, KP]],
        dtype=jnp.int32,
    )
    batch = key.shape[0]
    src = jnp.ones((batch,), jnp.int32)
    seq = jnp.arange(batch, dtype=jnp.int32)
    state, out = step(state, key, src, seq)
    executed = np.asarray(out.executed)
    clock = np.asarray(out.clock)
    pend_cap = state.pend_key.shape[0]
    w = lambda i: pend_cap + i  # fresh state: working row of batch row i
    real = [w(i) for i in range(7)]
    assert executed[real].all(), "healthy sharded Newt round executes all"
    assert np.asarray(out.fast_path)[real].all()
    assert int(out.slow_paths) == 0

    # per-key consecutive clocks in batch order; the multi-shard row's
    # clock is the max of its two shard-local assignments
    assert clock[w(0)] < clock[w(2)] < clock[w(4)] < clock[w(5)]  # bucket 4
    assert clock[w(1)] < clock[w(3)] < clock[w(4)] < clock[w(6)]  # bucket 5
    assert clock[w(4)] == max(clock[w(2)], clock[w(3)]) + 1

    # ownership: shard-0 rows never learned bucket 5 and vice versa
    kc = np.asarray(state.key_clock)
    vf = np.asarray(state.vote_frontier)
    assert (kc[0:3, 5] == 0).all() and (kc[3:6, 4] == 0).all()
    assert (vf[0:3, 5] == 0).all() and (vf[3:6, 4] == 0).all()
    assert (kc[0:3, 4] > 0).all() and (kc[3:6, 5] > 0).all()


@pytest.mark.slow
def test_sharded_newt_degraded_shard_blocks_stability(mesh):
    """A dead majority in shard 1 leaves its commits unstable (the
    per-shard frontier order statistic cannot advance), blocking its
    rows AND the multi-shard row, while shard 0 executes; recovery
    drains the carried rows in per-key clock order."""
    m = mesh_step.make_mesh(num_replicas=6)
    state = mesh_step.init_newt_state(
        m, 6, key_buckets=64, pending_capacity=16, key_width=2
    )
    KP = mesh_step.KEY_PAD
    # rows 0..3 live = all of shard 0 + shard 1 member 0 only: shard 1's
    # stability threshold (n - f = 2) cannot be met
    degraded = mesh_step.jit_newt_step(m, f=1, shard_count=2, live_replicas=4)
    key = jnp.asarray(
        [[4, KP], [5, KP], [4, KP], [5, KP], [4, 5], [KP, KP], [KP, KP],
         [KP, KP]],
        dtype=jnp.int32,
    )
    batch = key.shape[0]
    src = jnp.ones((batch,), jnp.int32)
    state, out = degraded(state, key, src, jnp.arange(batch, dtype=jnp.int32))
    executed = np.asarray(out.executed)
    pend_cap = state.pend_key.shape[0]
    w = lambda i: pend_cap + i
    assert executed[[w(0), w(2)]].all(), "shard-0 rows execute"
    assert not executed[[w(1), w(3), w(4)]].any(), (
        "shard-1 and multi-shard rows must wait for shard-1 stability"
    )
    assert int(out.pending) == 3

    # recovery: carried rows stabilize and drain
    healthy = mesh_step.jit_newt_step(m, f=1, shard_count=2)
    empty = jnp.full((batch, 2), KP, jnp.int32)
    zeros = jnp.zeros((batch,), jnp.int32)
    state, out2 = healthy(state, empty, zeros, zeros)
    assert int(out2.pending) == 0
    assert np.asarray(out2.executed).sum() == 3
    # carried per-key order: bucket-5 rows drain in their committed
    # (clock, dot) order
    order2 = np.asarray(out2.order)
    ex2 = np.asarray(out2.executed)
    clocks2 = np.asarray(out2.clock)
    drained = [int(clocks2[i]) for i in order2 if ex2[i]]
    assert drained == sorted(drained)


def test_newt_tiny_quorums_on_mesh(mesh):
    """newt_tiny_quorums shrinks the fast quorum to f+1 (newt.rs:90-100):
    a replica OUTSIDE the tiny quorum with a divergent key clock must not
    influence the commit clock, while the regular quorum consults it."""
    m = mesh_step.make_mesh(num_replicas=4)

    def run(tiny):
        state = mesh_step.init_newt_state(
            m, 4, key_buckets=8, pending_capacity=8
        )
        kc = np.array(state.key_clock)
        kc[2, 0] = 50  # replica 2: inside fq=3 (regular), outside fq=2 (tiny)
        state = state._replace(
            key_clock=jax.device_put(jnp.asarray(kc), state.key_clock.sharding)
        )
        step = mesh_step.jit_newt_step(m, f=1, tiny_quorums=tiny)
        key = jnp.zeros((8,), jnp.int32).at[1:].set(mesh_step.KEY_PAD)
        src = jnp.ones((8,), jnp.int32)
        state, out = step(state, key, src, jnp.arange(8, dtype=jnp.int32))
        w = state.pend_key.shape[0]
        assert bool(np.asarray(out.executed)[w])
        return int(np.asarray(out.clock)[w])

    assert run(tiny=True) == 1  # rows 0,1 agree at clock 1
    assert run(tiny=False) == 51  # row 2's stale view raises the max


@pytest.mark.slow
def test_newt_multikey_fast_path_is_row_level(mesh):
    """Unsharded multi-key fast-path regression (review finding): the
    count-of-max must aggregate at ROW level per shard, not per key slot.
    n=5, f=2, KW=2: quorum members propose per-slot clocks (3,5), (5,3),
    (1,1), (1,1) — each slot's max 5 is reported once, but the ROW max 5
    is reported twice >= f, so the command must take the fast path at
    clock 5 (newt.rs:527-546 counts reports of the single aggregated
    commit clock)."""
    m = mesh_step.make_mesh(num_replicas=5)
    state = mesh_step.init_newt_state(
        m, 5, key_buckets=8, pending_capacity=8, key_width=2
    )
    kc = np.array(state.key_clock)
    kc[0, 0], kc[0, 1] = 2, 4  # replica 0: a=2, b=4 -> proposes (3, 5)
    kc[1, 0], kc[1, 1] = 4, 2  # replica 1: a=4, b=2 -> proposes (5, 3)
    state = state._replace(
        key_clock=jax.device_put(jnp.asarray(kc), state.key_clock.sharding)
    )
    step = mesh_step.jit_newt_step(m, f=2)
    KP = mesh_step.KEY_PAD
    key = jnp.asarray([[0, 1]] + [[KP, KP]] * 7, dtype=jnp.int32)
    src = jnp.ones((8,), jnp.int32)
    seq = jnp.arange(8, dtype=jnp.int32)
    state, out = step(state, key, src, seq)
    w = state.pend_key.shape[0]  # working row of batch row 0
    assert bool(np.asarray(out.fast_path)[w]), (
        "row-level max reported >= f times must take the fast path"
    )
    assert int(np.asarray(out.clock)[w]) == 5
    assert int(out.slow_paths) == 0
    assert bool(np.asarray(out.executed)[w])


def _flat_equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations carry
    (shard_map, pjit, a loop's body, a ``cond``'s branches), in program
    order, as ``(primitive, input shapes, output shapes)``; a plain call is
    not listed itself."""
    for eqn in jaxpr.eqns:
        for value in eqn.params.values():
            for carried in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(carried, "jaxpr", carried)
                if hasattr(inner, "eqns"):
                    yield from _flat_equations(inner)
        if eqn.primitive.name not in ("pjit", "shard_map", "closed_call"):
            yield (
                eqn.primitive.name,
                [getattr(var.aval, "shape", ()) for var in eqn.invars],
                [var.aval.shape for var in eqn.outvars],
            )


@pytest.mark.parametrize("key_width", (1, 2))
@pytest.mark.parametrize("shard_count", (1, 4))
def test_nothing_in_the_newt_round_scales_with_the_key_space(shard_count, key_width):
    """Traced at 4096 and at 65536 key buckets with the same working set,
    the round is the same program but for the shapes of: the two donated
    tables' scatters (in place), the gathers that read the tables and the
    hold-back at the round's key slots (operand only: neither indices nor
    result grow), and the hold-back's fill and scatter-min.  No sort,
    all_gather, iota, reduction or elementwise pass is as long as the key
    space: stability and the watermark are computed on the slots."""
    devices = np.array(jax.devices()[:shard_count]).reshape(shard_count, 1)
    mesh = jax.sharding.Mesh(devices, (mesh_step.REPLICA_AXIS, mesh_step.BATCH_AXIS))
    batch, pending, rows = 16, 8, 5 * shard_count

    def trace(key_buckets):
        state = jax.eval_shape(
            lambda: mesh_step.init_newt_state(
                mesh, rows, key_buckets=key_buckets, pending_capacity=pending,
                key_width=key_width,
            )
        )
        column = jax.ShapeDtypeStruct((batch,), jnp.int32)
        keys = jax.ShapeDtypeStruct((batch, key_width), jnp.int32)
        round_ = functools.partial(
            mesh_step.newt_protocol_step, mesh=mesh, shard_count=shard_count
        )
        return list(_flat_equations(jax.make_jaxpr(round_)(state, keys, column, column).jaxpr))

    small, large = trace(4096), trace(65536)
    assert [eqn[0] for eqn in small] == [eqn[0] for eqn in large]
    block = rows // shard_count  # a device's replica rows
    scaled = []
    for (name, ins, outs), (_, ins_l, outs_l) in zip(small, large):
        if (ins, outs) == (ins_l, outs_l):
            continue
        scaled.append(name)
        if name == "gather":
            assert ins[1:] == ins_l[1:] and outs == outs_l, "a gather's indices or result grew"
        elif name in ("scatter-max", "scatter-min"):
            assert ins[1:] == ins_l[1:], f"{name}'s indices or updates grew"
        else:
            assert (name, outs_l) == ("broadcast_in_dim", [(65536,)]), (name, ins_l, outs_l)
    assert sorted(name for name in scaled if name != "gather") == [
        "broadcast_in_dim", "scatter-max", "scatter-max", "scatter-min"]
    tables = [ins_l[0] for (name, ins_l, _) in large if name == "scatter-max"
              and ins_l[0][-1:] == (65536,)]
    assert tables == [(block, 65536)] * 2  # vote_frontier, key_clock


# ---------------------------------------------------------------------------
# Caesar on the mesh: the fourth consensus shape
# ---------------------------------------------------------------------------


def test_caesar_step_timestamp_order(mesh):
    """A healthy Caesar round commits the whole batch on the fast path
    (consistent clock views) and executes conflicts in (clock, dot)
    order; the clock index carries across rounds."""
    state = mesh_step.init_caesar_state(
        mesh, 4, key_buckets=64, pending_capacity=16
    )
    step = mesh_step.jit_caesar_step(mesh, num_replicas=4)
    batch = 8 * mesh.shape[mesh_step.BATCH_AXIS]
    key = jnp.asarray([5] * batch, dtype=jnp.int32)  # one hot bucket
    src = jnp.ones((batch,), jnp.int32)
    seq = jnp.arange(batch, dtype=jnp.int32)
    state, out = step(state, key, src, seq)
    executed = np.asarray(out.executed)
    clock = np.asarray(out.clock)
    order = np.asarray(out.order)
    valid = clock >= 0
    assert executed[valid].all(), "healthy round executes everything"
    assert bool(np.asarray(out.fast_path)[valid].all())
    # within-round same-bucket commands take consecutive, unique clocks,
    # executed in clock order
    ex_rows = [w for w in order.tolist() if executed[w]]
    ex_clocks = clock[ex_rows]
    assert sorted(set(ex_clocks.tolist())) == ex_clocks.tolist()
    # next round proposes above the carried ceiling
    state, out2 = step(state, key[:batch], src, seq + batch)
    clock2 = np.asarray(out2.clock)
    assert clock2[clock2 >= 0].min() > ex_clocks.max()


def test_caesar_step_degraded_wait_and_recovery(mesh):
    """Divergent clock views force the retry (slow) path; with fewer
    live replicas than the write quorum the retry cannot commit and the
    command carries — blocking later commits on its bucket (the wait
    condition) — and a recovered round commits and executes everything
    in timestamp order."""
    state = mesh_step.init_caesar_state(
        mesh, 4, key_buckets=64, pending_capacity=16
    )
    healthy = mesh_step.jit_caesar_step(mesh, num_replicas=4)
    batch = 8 * mesh.shape[mesh_step.BATCH_AXIS]
    KP = mesh_step.KEY_PAD

    # round 1 healthy on bucket 7: seeds the clock index
    key1 = jnp.full((batch,), 7, dtype=jnp.int32)
    src = jnp.ones((batch,), jnp.int32)
    state, out1 = healthy(state, key1, src, jnp.arange(batch, dtype=jnp.int32))
    assert np.asarray(out1.executed)[np.asarray(out1.clock) >= 0].all()

    # stagger replica 0's bucket-7 ceiling: the next proposal diverges
    # across the fast quorum -> retry path; live=1 < write quorum (3) ->
    # uncommitted carry
    kc = np.array(state.key_clock)
    kc[0, 7] += 7
    state = state._replace(
        key_clock=jax.device_put(jnp.asarray(kc), state.key_clock.sharding)
    )
    degraded = mesh_step.jit_caesar_step(mesh, num_replicas=4, live_replicas=1)
    key2 = jnp.full((batch,), KP, dtype=jnp.int32)
    key2 = key2.at[0].set(7).at[1].set(7)
    state, out2 = degraded(
        state, key2, src, jnp.arange(batch, 2 * batch, dtype=jnp.int32)
    )
    committed2 = np.asarray(out2.committed)
    # working rows: pend_cap offset is 16
    w0, w1 = 16, 17
    assert not committed2[w0] and not committed2[w1]
    assert int(out2.pending) == 2
    assert int(out2.slow_paths) >= 2

    # recovered round: the carried commands commit via retry and execute
    state, out3 = healthy(
        state, jnp.full((batch,), KP, dtype=jnp.int32), src,
        jnp.arange(2 * batch, 3 * batch, dtype=jnp.int32),
    )
    executed3 = np.asarray(out3.executed)
    clock3 = np.asarray(out3.clock)
    assert executed3[:2].all(), "carried rows must execute after recovery"
    assert int(out3.pending) == 0
    # per-bucket timestamp order: the two carried rows' clocks are unique
    assert clock3[0] != clock3[1]


@pytest.mark.slow
def test_caesar_wait_gate_transitive_holdback(mesh):
    """A committed multi-key row held behind an uncommitted lower-clock
    conflict on one bucket must transitively hold back higher-clock rows
    on its OTHER buckets — commitment is not clock-monotone per bucket
    in Caesar, so the gate is a fixpoint (review-caught: the one-pass
    gate let X(22) execute before M(21) on their shared bucket)."""
    state = mesh_step.init_caesar_state(
        mesh, 4, key_buckets=64, pending_capacity=16, key_width=2
    )
    KP = mesh_step.KEY_PAD
    kc = np.array(state.key_clock)
    kc[:, 4] = 5
    kc[0, 4] = 10  # divergent views on bucket 4
    kc[:, 5] = 20
    state = state._replace(
        key_clock=jax.device_put(jnp.asarray(kc), state.key_clock.sharding)
    )
    degraded = mesh_step.jit_caesar_step(mesh, num_replicas=4, live_replicas=1)
    batch = 8 * mesh.shape[mesh_step.BATCH_AXIS]
    key = jnp.full((batch, 2), KP, dtype=jnp.int32)
    key = key.at[0, 0].set(4)                 # A: bucket 4 only
    key = key.at[1, 0].set(4).at[1, 1].set(5)  # M: buckets 4 and 5
    key = key.at[2, 0].set(5)                 # X: bucket 5 only
    src = jnp.ones((batch,), jnp.int32)
    seq = jnp.arange(batch, dtype=jnp.int32)
    state, out = degraded(state, key, src, seq)
    committed = np.asarray(out.committed)
    executed = np.asarray(out.executed)
    w0 = 16  # pend_cap offset
    A, M, X = w0, w0 + 1, w0 + 2
    assert not committed[A], "divergent views + no write quorum: A waits"
    assert committed[M] and committed[X], "M and X fast-commit"
    # the fixpoint gate: M is held by A on bucket 4, and X must be held
    # by M on bucket 5 — nothing executes
    assert not executed[M] and not executed[X]
    assert int(out.pending) == 3

    # recovery: A commits via retry above everything; per-bucket
    # timestamp order holds — M(21) before A and X on their buckets
    healthy = mesh_step.jit_caesar_step(mesh, num_replicas=4)
    state, out2 = healthy(
        state, jnp.full((batch, 2), KP, dtype=jnp.int32), src,
        jnp.arange(batch, 2 * batch, dtype=jnp.int32),
    )
    executed2 = np.asarray(out2.executed)
    clock2 = np.asarray(out2.clock)
    order2 = np.asarray(out2.order)
    assert executed2[:3].all(), "recovered round executes all three"
    pos = {w: i for i, w in enumerate(order2.tolist())}
    # M committed at 21 executes before X (22) and before A (retry > 21)
    m_slot = min(range(3), key=lambda w: clock2[w])
    assert clock2[m_slot] == 21
    assert all(pos[m_slot] < pos[w] for w in range(3) if w != m_slot)


# ---------------------------------------------------------------------------
# The one-key dep-commit round against the formulation it replaced
# ---------------------------------------------------------------------------


def _one_device_mesh():
    return jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), (mesh_step.REPLICA_AXIS, mesh_step.BATCH_AXIS)
    )


def _oracle_round(state, key, src, seq, *, shard_count, live_replicas):
    """One dep-commit round at key width 1 over numpy, row by row, resolved
    as the round was before it read its key runs: a final dep is joined
    back to a working row by its gid, uncommitted rows are ``MISSING``, and
    ``ops/graph_resolve.resolve_functional`` (pointer doubling) ranks the
    graph.  Returns ``(next state, StepOutput)`` as numpy."""
    from fantoch_tpu.ops.graph_resolve import MISSING, resolve_functional

    clock, frontier, next_gid, pend_key, pend_src, pend_seq, pend_gid = (
        np.asarray(x) for x in state[:7]
    )
    rows, buckets = clock.shape
    per_shard = rows // shard_count
    fast_quorum, write_quorum = mesh_step.quorum_sizes(per_shard)
    cap, batch = len(pend_gid), len(key)
    work = cap + batch
    gid = np.concatenate([pend_gid, next_gid + np.arange(batch, dtype=np.int32)])
    valid = gid >= 0
    key_cat = np.concatenate([pend_key[:, 0], key])
    real = valid & (key_cat != mesh_step.KEY_PAD)
    src_f = np.where(valid, np.concatenate([pend_src, src]), 0).astype(np.int32)
    seq_f = np.where(valid, np.concatenate([pend_seq, seq]), 0).astype(np.int32)

    # step 2: the latest earlier working row on the same key, else the clock
    chain, latest = np.full(work, TERMINAL, np.int32), {}
    for i in np.flatnonzero(real):
        chain[i] = latest.get(key_cat[i], TERMINAL)
        latest[key_cat[i]] = i
    safe = np.where(real, key_cat, 0)
    dep = np.where(chain >= 0, gid[np.maximum(chain, 0)], np.where(real, clock[:, safe], -1))
    # step 3: the fast quorum of the slot's shard, then the accept round
    shard_of = np.where(real, key_cat % shard_count, 0)
    member = np.arange(rows)
    in_fq = (member[:, None] // per_shard == shard_of[None]) & (
        member[:, None] % per_shard < fast_quorum
    )
    fq_max = np.where(in_fq, dep, np.iinfo(np.int32).min).max(axis=0)
    fq_min = np.where(in_fq, dep, np.iinfo(np.int32).max).min(axis=0)
    fast = (fq_max == fq_min) & valid
    live = member < live_replicas
    shard_live = np.bincount(member[live] // per_shard, minlength=shard_count)
    committed = (fast | np.where(real, shard_live[shard_of] >= write_quorum, True)) & valid

    # step 4 as it was: the gid join, then pointer doubling
    row_of = {int(g): i for i, g in enumerate(gid) if g >= 0}
    join = np.array([row_of.get(int(g), TERMINAL) if g >= 0 else TERMINAL for g in fq_max], np.int32)
    assert np.array_equal(join, chain), "the join found a clock entry in the working set"
    dep_idx = np.where(valid, np.where(committed, join, MISSING), TERMINAL).astype(np.int32)
    res = resolve_functional(jnp.asarray(dep_idx), jnp.asarray(src_f), jnp.asarray(seq_f))
    executed = np.asarray(res.resolved) & committed

    # steps 5 and 6
    new_clock = clock.copy()
    for i in np.flatnonzero(executed & real):
        learns = live & (member // per_shard == shard_of[i])
        new_clock[learns, key_cat[i]] = np.maximum(new_clock[learns, key_cat[i]], gid[i])
    new_frontier = frontier + np.where(live, executed.sum(), 0).astype(np.int32)
    carry = np.flatnonzero(valid & ~executed)
    take = carry[:cap]

    def carried(column, empty):
        out = np.full(cap, empty, np.int32)
        out[: len(take)] = column[take]
        return out

    # the round as it was had one clock and no read flag: a round without
    # a read leaves the read clock and the carried flags as they were
    state = mesh_step.ReplicaState(
        new_clock, new_frontier, np.int32(next_gid + batch),
        carried(np.where(real, key_cat, mesh_step.KEY_PAD), mesh_step.KEY_PAD)[:, None],
        carried(src_f, -1), carried(seq_f, -1), carried(gid, -1),
        np.asarray(state.read_clock), np.zeros(cap, bool),
    )
    deps = np.where(real, fq_max, -1)
    # its one dependency slot a key is the first of the two; no read, so
    # the second is empty and nothing commuted
    out = mesh_step.StepOutput(
        np.asarray(res.order), executed, fast, np.stack([deps, np.full_like(deps, -1)], axis=1),
        np.where(valid, gid, -1), np.int32((~fast & valid).sum()), new_frontier.min(),
        np.int32(min(len(carry), cap)), np.int32(max(len(carry) - cap, 0)),
        # mesh_step.ROUND_TALLIES: every dependency is a link, none of them between reads
        np.array([(executed & (deps >= 0)).sum()] * 2 + [0, 0, 0], np.int32),
    )
    return state, out


@pytest.mark.parametrize("distinct_keys", (1, 30, 1000))
@pytest.mark.parametrize(
    "shard_count, lives",
    [
        (1, (5,)),  # all live
        (1, (3, 3, 5)),  # at the write quorum: every slow path commits
        (1, (2, 2, 2, 5, 5)),  # below it: MISSING rows, blocked suffixes, carry
        (2, (10, 8, 8, 10)),  # the second shard at its write quorum
        (2, (7, 7, 7, 10, 10)),  # the second shard below it
    ],
)
def test_one_key_round_equals_the_gid_join_and_pointer_doubling(shard_count, lives, distinct_keys):
    """Several rounds in sequence on one state, full and part-full batches,
    pending carried and overflowing, no read among them: every field of
    ``StepOutput`` and of the next ``ReplicaState`` equals, element for
    element, what the round gave before it knew reads from writes, when it
    exported its chain as gids, joined them back to rows and resolved by
    pointer doubling (``_oracle_round``); the second dependency slot, the
    read clock and the tallies of reads stay empty."""
    rows, batch, pending, buckets = 5 * shard_count, 48, 32, 2048
    mesh = _one_device_mesh()
    state = mesh_step.init_state(mesh, rows, key_buckets=buckets, pending_capacity=pending)
    steps = {
        live: mesh_step.jit_protocol_step(mesh, live_replicas=live, shard_count=shard_count)
        for live in set(lives)
    }
    rng = np.random.default_rng(35 + distinct_keys + shard_count)
    carried = 0
    for r in range(max(7, 2 * len(lives) + 1)):
        live = lives[r % len(lives)]
        fill = batch if r % 3 == 0 else int(rng.integers(1, batch + 1))
        key = np.full(batch, mesh_step.KEY_PAD, np.int32)
        # from shard_count - 1 up: a single key is the last shard's, the degraded one
        key[:fill] = shard_count - 1 + rng.integers(0, distinct_keys, fill)
        src = np.zeros(batch, np.int32)
        src[:fill] = rng.integers(1, 6, fill)
        seq = np.zeros(batch, np.int32)
        seq[:fill] = r * batch + np.arange(fill)
        want_state, want = _oracle_round(
            state, key, src, seq, shard_count=shard_count, live_replicas=live
        )
        state, out = steps[live](
            state, jnp.asarray(key), jnp.asarray(src), jnp.asarray(seq), jnp.zeros(batch, bool)
        )
        for name, got, expected in zip(out._fields + state._fields, out + state, want + want_state):
            got = np.asarray(got)
            assert got.dtype == np.asarray(expected).dtype or got.dtype == bool, (r, name)
            assert np.array_equal(got, expected), (r, name)
        carried += int(out.pending)
    if min(lives) < 3 + 5 * (shard_count - 1) and distinct_keys < 1000:
        assert carried > 0, "a quorum below the write quorum carried nothing: the case tests less than it says"


def test_the_one_key_round_has_no_loop_and_no_more_gathers_at_a_larger_working_set():
    """The one-key round's jaxpr at W = 512 and at W = 8192: no ``scan`` and
    no ``while`` (pointer doubling unrolled 2 log2(2W) steps of gathers and
    the gid join searched by a loop), and the same gathers and scatters,
    one for one: their number does not grow with the working set."""
    mesh = _one_device_mesh()

    def trace(half):
        state = jax.eval_shape(
            lambda: mesh_step.init_state(mesh, 5, key_buckets=4096, pending_capacity=half)
        )
        column = jax.ShapeDtypeStruct((half,), jnp.int32)
        round_ = functools.partial(mesh_step.protocol_step, mesh=mesh)
        return [name for name, _, _ in _flat_equations(
            jax.make_jaxpr(round_)(state, column, column, column).jaxpr)]

    small, large = trace(256), trace(4096)
    assert small == large
    assert not {"scan", "while"} & set(small)
    moves = [name for name in small if name == "gather" or name.startswith("scatter")]
    # gathers: the two clocks' reads, gid[chain] of each, a slot's shard's
    # live count, the blocking flags at the sorted positions, the carry's
    # six reads (the sorted keys and the read flags at the sorted positions
    # ride the key sort, and the latest write's and the latest read's slot
    # of a run the running max that finds their position); scatters: the
    # two chains and the level back to row order, the live count per shard,
    # the two clocks' scatter-max (the read clock's under a ``cond``: a
    # round that executed no read skips it)
    assert sorted(moves) == ["gather"] * 12 + ["scatter"] * 3 + ["scatter-add"] + ["scatter-max"] * 2, moves
    assert small.count("sort") == 3 and small.count("cumsum") == 1
