"""Multi-chip SPMD protocol step: replica x batch sharding over a device mesh.

The reference scales by (1) geo-replication — n processes each running the
protocol state machine (fantoch/src/protocol/base.rs) — and (2) per-key /
per-dot sharding inside each process (fantoch/src/run/pool.rs:115-124).
The TPU-native equivalents are two mesh axes:

  * ``replica`` — each mesh slice along this axis holds one (or a block of)
    replica's protocol state: its key-clock table (the analog of
    ``KeyDeps``, fantoch_ps/src/protocol/common/graph/deps/keys/sequential.rs)
    and its executed frontier.  Quorum aggregation (the MCollectAck fan-in,
    fantoch_ps/src/protocol/epaxos.rs:305-370) becomes ``pmax``/``pmin``
    collectives along this axis — riding ICI instead of TCP.
  * ``batch`` — commands of one round are sharded along this axis; per-key
    conflict detection is local work + one ``all_gather`` (commands are
    tiny: a key bucket and a dot), and the dependency-graph resolution
    (fantoch_ps/src/executor/graph/tarjan.rs) runs batched via
    :mod:`fantoch_tpu.ops.graph_resolve`.

One :func:`protocol_step` is the analog of delivering a full
MCollect -> MCollectAck -> [MConsensus -> MConsensusAck] -> MCommit ->
execute round for B commands on all replicas at once:

  1. per-replica dependency computation (scatter/gather over the replica's
     two key-clock shards) — each replica reports the latest conflicting
     commands it knows, with the read/write split of ``KeyDeps::add_cmd``
     (deps/keys/locked.rs): a read depends on the latest write of each of
     its keys, a write on the latest write and on the latest read since
     it, two dependency slots a key;
  2. fast-path check over the **fast quorum only** (the first
     ``fast_quorum_size`` replicas, mirroring the distance-sorted quorum of
     fantoch/src/protocol/base.rs:59-131): EPaxos commits on the fast path
     iff all fast-quorum replicas report identical deps (epaxos.rs:339-345)
     — here a masked ``pmax == pmin`` along ``replica``; Atlas iff every
     dependency of the union was reported by at least ``f`` of them
     (atlas.rs, ``QuorumDeps::check_threshold``), always so at ``f`` = 1;
  3. slow path (Synod accept round, fantoch_ps/src/protocol/common/synod/
     single.rs): for fast-path misses the coordinator proposes the *union*
     of fast-quorum deps (= masked max over singletons) at ballot 0 via the
     skip-prepare trick (single.rs:86); replica accept indicators are
     counted with a ``psum`` along ``replica`` and the command commits once
     ``acks >= write_quorum_size`` (f + 1);
  4. topological resolution of the committed working set, shared across
     the ``batch`` axis via one small all_gather: with one key a command a
     row's level is read off its place in its key's run of step 1's sort
     (a segmented scan: reads of one stretch share a level) and it is
     blocked iff an uncommitted row stands before it there
     (:func:`_resolve_run_position`; the round builds no cycle, so none is
     searched for); with several keys the general resolver
     (ops/graph_resolve.resolve_general);
  5. state update: scatter-max the executed writes into every replica's
     key-clock and the executed reads into its read-clock, advance the
     executed frontier, and compute the GC stability
     watermark = ``pmin`` of all replicas' frontiers (the AEClock meet of
     fantoch/src/protocol/gc.rs:72-116, collapsed to a counter in this
     dense round-based regime).

All state stays device-resident across steps (donated), so the host only
feeds command batches and drains execution orders.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fantoch_tpu.ops.graph_resolve import (
    MISSING,
    TERMINAL,
    components_residual,
    resolve_general,
    resolve_key_runs,
)

REPLICA_AXIS = "replica"
BATCH_AXIS = "batch"
KEY_PAD = -1  # empty key slot in a [.., KW] key matrix


class ReplicaState(NamedTuple):
    """Per-replica device-resident protocol state.

    ``key_clock[R, K]``: global id (see below) of the latest executed
    *write* per key bucket, per replica; -1 when none.  ``read_clock[R, K]``:
    the same for the latest executed *read*.  Together the analog of the
    per-process ``KeyDeps`` map with the read/write split
    (deps/keys/locked.rs: ``LatestRWDep``).

    ``frontier[R]``: number of commands this replica has committed+executed
    (the AEClock frontier of fantoch/src/protocol/gc.rs, collapsed to a
    counter in this dense batched regime where execution is in rounds).

    ``pend_*[Pcap]``: the device-resident pending buffer — commands a
    previous round could not execute (failed Synod quorum, or blocked
    behind one) carry into the next round instead of being dropped
    (VERDICT r2 weak #4 liveness fix).  Slot empty iff ``pend_gid == -1``;
    replicated across the mesh (pending commands are global protocol
    state, like the reference's per-dot info store awaiting commit).

    ``pend_key`` is ``int32[Pcap, KW]``: commands carry up to KW key
    buckets (multi-key commands, command.rs:12-19), padded with KEY_PAD.
    """

    key_clock: jax.Array  # int32[R, K] — latest executed write
    frontier: jax.Array  # int32[R]
    next_gid: jax.Array  # int32[] — global id of the next batch's first cmd
    pend_key: jax.Array  # int32[Pcap, KW]
    pend_src: jax.Array  # int32[Pcap]
    pend_seq: jax.Array  # int32[Pcap]
    pend_gid: jax.Array  # int32[Pcap] (-1 = empty slot)
    read_clock: jax.Array  # int32[R, K] — latest executed read
    pend_read: jax.Array  # bool[Pcap] — the carried command is a read


class StepOutput(NamedTuple):
    """Per-round outputs over the W = Pcap + B working rows (pending
    buffer first, then the new batch; a working row's command is
    identified by ``gids``)."""

    order: jax.Array  # int32[W] execution order (working-row indices)
    resolved: jax.Array  # bool[W] — executed this round
    fast_path: jax.Array  # bool[W] — committed on the fast path
    # final deps (global ids, -1 none): columns [0, KW) the latest write
    # of each key slot, columns [KW, 2KW) the latest read since it (a
    # write's only)
    deps_gid: jax.Array  # int32[W, 2*KW]
    gids: jax.Array  # int32[W] — global id per working row (-1 = empty)
    slow_paths: jax.Array  # int32[] — commands that took the Synod round
    stable: jax.Array  # int32[] — GC watermark: min executed frontier
    pending: jax.Array  # int32[] — commands carried to the next round
    pend_dropped: jax.Array  # int32[] — overflow beyond the pending capacity
    # tallies over the rows executed this round, one vector (a drain
    # fetches it in one transfer), by the names of ROUND_TALLIES
    tallies: jax.Array  # int32[5]


class SiteStepOutput(NamedTuple):
    """What the round with a coordinator at every site gives
    (``protocol_step(sites=n)``): :class:`StepOutput`'s fields, with the
    committed dependencies as a set and the rows the host orders."""

    order: jax.Array  # int32[W] — the rows the device ordered first, in execution order
    resolved: jax.Array  # bool[W] — executed this round (``finish`` rows among them)
    fast_path: jax.Array  # bool[W]
    # the union of what the fast quorums reported (global ids, -1 none,
    # repeats left in): per key slot and member its latest write, then its
    # latest read since it (a write's only)
    deps_gid: jax.Array  # int32[W, 2*KW*fast_quorum]
    gids: jax.Array  # int32[W]
    slow_paths: jax.Array  # int32[]
    stable: jax.Array  # int32[]
    pending: jax.Array  # int32[]
    pend_dropped: jax.Array  # int32[]
    # SITE_ROUND_TALLIES, then SITE_ROUND_GAUGES
    tallies: jax.Array  # int32[15]
    # executed this round at the place the host's Tarjan finds: one key a
    # command, the executable rows of a key's run the device's resolver
    # did not cut (ops/graph_resolve.resolve_key_runs); several, the rows
    # the components pass of ``resolve_general`` could not hold (its
    # ``stuck``); not in ``order``'s front part
    finish: jax.Array  # bool[W]


# StepOutput.tallies, in order: the executed rows' non-empty dependency
# slots; their key slots with an earlier command on the bucket, and of
# those the ones where both are reads (no dependency: reads commute); the
# reads among the executed rows; those on more than one shard
ROUND_TALLIES = (
    "deps_committed", "key_links", "read_links_commuted", "read_rows",
    "cross_shard_executed",
)
# SiteStepOutput.tallies: those five, then what the round with a
# coordinator at every site says of its graph, summed over rounds: the
# executed rows in a strongly connected component of several rows, such
# components, passes of the resolver's loops, rows whose order the host's
# Tarjan finds (the device's share; the driver adds what the finisher
# found), the executed rows in a component of several whose rows hold more
# than one key bucket and in one whose rows' keys lie on more than one shard
# (the device's components only; 0 with one key a command, where a
# component lies in one key's run); then what Atlas's threshold at ``f`` >= 2
# made of the executed rows (0 under EPaxos's rule, where the second is
# ``slow_paths``, and at ``f`` = 1, where nothing is compared): the distinct
# dependencies of the union that fewer than ``f`` members of a shard's ring
# reported (what the accept round was run for), the rows whose members'
# reports were not one set (``check_union`` would have sent them slow), and
# of those the rows the threshold still took fast ...
SITE_ROUND_TALLIES = ROUND_TALLIES + (
    "scc_rows", "scc_count", "resolve_iters", "finisher_rows",
    "scc_span_rows", "scc_shard_rows",
    "threshold_short_deps", "split_quorum_rows", "threshold_fast_split_rows",
)
# ... and, last in the vector, a gauge: the largest component of the round
# (the driver keeps the last round's that had one)
SITE_ROUND_GAUGES = ("scc_rows_max",)


DEP_COMMIT_RULES = ("epaxos", "atlas")


def quorum_sizes(
    num_replicas: int, f: int = 1, rule: str = "epaxos"
) -> Tuple[int, int]:
    """(fast_quorum_size, write_quorum_size) of the dep-commit round under
    ``rule``: the shared protocol-fact formulas of ``Config``.  EPaxos
    tolerates a minority whatever ``f`` says (``epaxos_quorum_sizes``
    takes its own ``f = n // 2``); Atlas's are ``(n // 2 + f, f + 1)``."""
    from fantoch_tpu.core.config import Config

    assert rule in DEP_COMMIT_RULES, rule
    config = Config(num_replicas, f)
    if rule == "atlas":
        return config.atlas_quorum_sizes()
    return config.epaxos_quorum_sizes()


def shard_of_row(row: int, num_replicas_total: int, shard_count: int) -> int:
    """Owning shard of a replica row — the row-order contract tests pin.

    Replica rows are **shard-major**: shard ``s`` owns the contiguous
    block ``[s*n, (s+1)*n)`` of the ``num_replicas_total = n * shard_count``
    rows (protocol_step computes ``row // per_shard`` on-device; this is
    the host-side mirror).  Host placement that wants a shard's quorum
    fan-in on ICI must therefore map whole *blocks* — not strided rows —
    onto one host (parallel/multihost.py validates exactly that).
    """
    assert num_replicas_total % shard_count == 0
    return row // (num_replicas_total // shard_count)


def make_mesh(
    n_devices: int | None = None,
    num_replicas: int | None = None,
    shard_count: int = 1,
) -> Mesh:
    """Factor the device list into a (replica, batch) mesh.

    Replica axis gets the smaller factor (real deployments have 3..11
    replicas; batches are wide).  When ``num_replicas`` is given, the
    replica axis must divide it (each device slice holds a whole number of
    replica blocks — init_state's sharding contract).

    ``shard_count`` > 1 (partial replication; ``num_replicas`` then counts
    every shard's rows): where the devices can hold whole shards, the
    replica axis is the largest factor of the device count that divides
    the shard count, and may then exceed the batch axis.  Rows are
    shard-major (:func:`shard_of_row`), so each slice along ``replica``
    holds ``shard_count // replica`` whole shards: a shard's quorum
    ``pmax`` / ``psum`` reduce over rows of one device, and only the
    cross-shard max and the stability gather cross devices.  4 devices x
    4 shards gives ``replica:4 x batch:1``, one shard a device.  One
    device, one shard, and a shard count no factor of the device count
    divides get the mesh of the rule above.
    """
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    replica = max(
        (
            cand
            for cand in range(2, min(n, shard_count) + 1)
            if n % cand == 0 and shard_count % cand == 0
        ),
        default=0,
    )
    if not replica:
        replica = 1
        for cand in range(min(n, 8), 0, -1):
            if (
                n % cand == 0
                and cand <= n // cand
                and (num_replicas is None or num_replicas % cand == 0)
            ):
                replica = cand
                break
    import numpy as np

    dev_array = np.array(devices).reshape(replica, n // replica)
    return Mesh(dev_array, (REPLICA_AXIS, BATCH_AXIS))


def shards_on_devices(
    mesh: Mesh, num_replicas_total: int, shard_count: int
) -> list[list[int]]:
    """The shards whose replica rows each device of ``mesh`` holds, in the
    mesh's device order (row-major over replica x batch): what the
    "serving clients" banner and the snapshot's ``backend`` say of the
    layout.  One shard a device reads ``[[0], [1], [2], [3]]``; the
    ``replica:2 x batch:2`` mesh of four shards ``[[0, 1], [0, 1],
    [2, 3], [2, 3]]`` (the batch axis repeats a replica slice)."""
    slices, batch = mesh.shape[REPLICA_AXIS], mesh.shape[BATCH_AXIS]
    rows = num_replicas_total // slices
    out = []
    for at in range(slices):
        held = sorted({
            shard_of_row(row, num_replicas_total, shard_count)
            for row in range(at * rows, (at + 1) * rows)
        })
        out.extend([held] * batch)
    return out


def init_state(
    mesh: Mesh,
    num_replicas: int,
    key_buckets: int = 4096,
    pending_capacity: int = 256,
    key_width: int = 1,
) -> ReplicaState:
    """Device-resident initial state, sharded over the replica axis.

    ``key_width``: max key buckets per command (multi-key commands route
    through the general resolver on-mesh)."""
    sharding = NamedSharding(mesh, P(REPLICA_AXIS, None))

    def clock():  # distinct buffers: donated state must not alias
        return jax.device_put(
            jnp.full((num_replicas, key_buckets), -1, dtype=jnp.int32), sharding
        )

    frontier = jax.device_put(
        jnp.zeros((num_replicas,), dtype=jnp.int32),
        NamedSharding(mesh, P(REPLICA_AXIS)),
    )
    rep = NamedSharding(mesh, P())
    next_gid = jax.device_put(jnp.int32(0), rep)

    def empty(shape):
        return jax.device_put(jnp.full(shape, -1, dtype=jnp.int32), rep)

    cap = pending_capacity
    return ReplicaState(
        clock(), frontier, next_gid,
        empty((cap, key_width)), empty((cap,)), empty((cap,)), empty((cap,)),
        clock(), jax.device_put(jnp.zeros((cap,), bool), rep),
    )


def _key_runs(
    keys: jax.Array, flag: jax.Array | None = None
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Stable sort of the flattened (row-major) key slots:
    ``(perm, head, flag_at)``.

    ``perm[p]`` is the slot at sorted position ``p``; the slots of one key
    are one contiguous run in arrival order, and ``head[p]`` marks a run's
    first position.  ``flag`` (bool, by row; default: no row) rides the
    sort: ``flag_at[p]`` is the flag of the row of ``perm[p]``, as the
    sorted keys are the sort's own (a gather each, saved).
    """
    flat = keys.reshape(-1)
    slots = jnp.arange(flat.shape[0], dtype=jnp.int32)
    if flag is None:
        flag = jnp.zeros((keys.shape[0],), bool)
    sorted_key, perm, flag_at = jax.lax.sort(
        (flat, slots, jnp.repeat(flag, keys.shape[1])), num_keys=1, is_stable=True
    )
    head = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_key[1:] != sorted_key[:-1]]
    )
    return perm, head, flag_at


def _run_start(head: jax.Array) -> jax.Array:
    """The first sorted position of each position's key run."""
    pos = jnp.arange(head.shape[0], dtype=jnp.int32)
    return jax.lax.cummax(jnp.where(head, pos, 0))


def _run_end(head: jax.Array) -> jax.Array:
    """The last sorted position of each position's key run."""
    size = head.shape[0]
    pos = jnp.arange(size, dtype=jnp.int32)
    tail = jnp.concatenate([head[1:], jnp.ones((1,), bool)])
    return jax.lax.cummin(jnp.where(tail, pos, size), reverse=True)


def _count_in_run(head: jax.Array, x: jax.Array) -> jax.Array:
    """How many of ``x`` (bool[n, W], by sorted position) each key run holds
    up to and with each position."""
    total = jnp.cumsum(x.astype(jnp.int32), axis=1)
    # less what the run's first position found before it: totals only
    # grow, so the latest head's wins a running max
    return total - jax.lax.cummax(
        jnp.where(head[None], total - x.astype(jnp.int32), 0), axis=1
    )


def _chain_of_runs(
    perm: jax.Array, head: jax.Array, shape, member: jax.Array | None = None
) -> jax.Array:
    """chain[i, w] = latest row j < i sharing key keys[i, w], else -1.

    Each slot's predecessor within its key run is the latest earlier slot
    of the same key — the tensorized ``KeyDeps::add_cmd`` latest-per-key
    chain for commands of the same round, one dependency slot per key.
    Rows must not repeat a key (commands hold distinct keys), so an in-run
    predecessor is always an earlier row.

    ``member`` (bool, by sorted position; default: every position) keeps
    only predecessors of one class, the latest read or the latest write
    of the key: the latest earlier position of the run that is a member.
    """
    n = perm.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    if member is None:
        member = jnp.ones((n,), bool)
    # the latest member position so far and the slot standing there, in
    # one running max: positions only grow, so packed with its slot below
    # it a position still wins by itself (a gather of ``perm`` saved)
    width = max(1, (n - 1).bit_length())
    if 2 * width <= 31:
        seen = jax.lax.cummax(jnp.where(member, (pos << width) | perm, -1))
        before, slot = seen >> width, seen & ((1 << width) - 1)
    else:  # a working set too long to pack into an int32
        before = jax.lax.cummax(jnp.where(member, pos, -1))
        slot = perm[jnp.maximum(before, 0)]

    def earlier(x):
        return jnp.concatenate([jnp.full((1,), -1, jnp.int32), x[:-1]])

    prev_same = jnp.where(
        earlier(before) >= _run_start(head),
        earlier(slot) // shape[1],  # predecessor's row
        jnp.int32(TERMINAL),
    )
    return jnp.zeros_like(perm).at[perm].set(prev_same).reshape(shape)


def _intra_batch_chain(keys: jax.Array) -> jax.Array:
    """The chain of :func:`_chain_of_runs` over the runs of ``keys``."""
    perm, head, _ = _key_runs(keys)
    return _chain_of_runs(perm, head, keys.shape)


def _resolve_run_position(
    perm: jax.Array, head: jax.Array, blocking: jax.Array, read_at: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """``(order, resolved)`` of a one-key working set from its key runs.

    The chains of :func:`_chain_of_runs` only ever point back along a
    key's run, so there is no cycle to look for.  A row's topological
    level is read off its place in the run: a read stands one above the
    last write before it (reads of one stretch share a level: they
    commute), a write one above the last write, or two where a read
    stands between; with no read in the run that is the position in the
    run.  It resolves iff no ``blocking`` row (valid and uncommitted: the
    resolvers' ``MISSING``) stands at or before it in the run: the reads
    of one stretch report the same dependencies and commit or fail
    together, so the one a later write depends on stands for all of them.
    A segmented ``cumsum`` and two ``cummax`` in sorted space and one
    scatter back give what pointer doubling over the same chain gives
    (``ops/graph_resolve.resolve_functional``, the oracle of
    ``tests/test_mesh_step.py``), element for element: ``order`` is rows
    by (level, row), unresolved rows at the tail.  ``blocking`` is by
    row, ``read_at`` (the row is a read) by sorted position.
    """
    pos = jnp.arange(perm.shape[0], dtype=jnp.int32)
    run_start = _run_start(head)
    last_blocked = jax.lax.cummax(jnp.where(blocking[perm], pos, -1))
    write_at = (~read_at).astype(jnp.int32)
    after_read = ~read_at & ~head & jnp.roll(read_at, 1)
    rise = write_at + after_read
    total = jnp.cumsum(rise)
    # what the run's first position found: no smaller at a later run
    depth = total - jax.lax.cummax(jnp.where(head, total - rise, 0))
    unresolved = jnp.iinfo(jnp.int32).max
    level = jnp.zeros_like(pos).at[perm].set(
        jnp.where(last_blocked < run_start, depth - write_at, unresolved)
    )
    order = jnp.argsort(level, stable=True).astype(jnp.int32)
    return order, level != unresolved


def resolver_name(key_width: int, sites: int = 1) -> str:
    """What resolves a :func:`protocol_step` round of this key width (the
    snapshot's ``backend`` and the "serving clients" banner say it): with
    one coordinator the key runs' positions or ``resolve_general``'s
    arrival pass, with a coordinator at every site ``resolve_key_runs`` or
    ``resolve_general``'s components pass."""
    if sites == 1:
        return "run_position" if key_width == 1 else "general"
    return "key_runs" if key_width == 1 else "general_components"


def protocol_step(
    state: ReplicaState,
    key: jax.Array,  # int32[B] or int32[B, KW] key buckets, replicated
    dot_src: jax.Array,  # int32[B]
    dot_seq: jax.Array,  # int32[B]
    read: jax.Array | None = None,  # bool[B] — the command is a read
    *,
    mesh: Mesh,
    live_replicas: int | None = None,
    shard_count: int = 1,
    f: int = 1,
    rule: str = "epaxos",
    sites: int = 1,
    site_base: int = 1,
) -> Tuple[ReplicaState, StepOutput]:
    """One batched commit+execute round over the (replica, batch) mesh.

    ``key`` may carry up to KW distinct key buckets per command (KEY_PAD
    pads unused slots).  A one-key round (KW == 1) is resolved from the
    key-sorted working set that builds its chains: the level is read off
    the row's place in its key's run, blocked iff an uncommitted row
    stands earlier in the run (:func:`_resolve_run_position`,
    :func:`resolver_name` ``run_position``).
    Multi-key rounds resolve through the general out-degree-2KW resolver
    (ops/graph_resolve.resolve_general, ``general``): with one coordinator
    every dependency points backward, so its arrival pass covers the
    clean-commit case and its iterative pass the ``MISSING`` rows a shard
    under its write quorum leaves; with a coordinator at every site
    (``sites``, below) its components pass, every round.

    ``read`` marks the commands that only read (default: none does).  The
    conflict relation is ``KeyDeps``'s with the read/write split
    (deps/keys/locked.rs): a read depends on the latest write of each of
    its keys and becomes the key's latest read; a write depends on the
    latest write and on the latest read since it, and becomes the latest
    write.  "Latest" is over the working set in arrival order (pending
    rows, then the batch), then the replica's two clocks; "since" is by
    gid, which is arrival order.  With no read in a round the second half
    of ``deps_gid`` is empty and everything else is what a round without
    the split gives.

    ``rule`` picks the quorums and the fast-path test
    (:func:`quorum_sizes`): ``epaxos``, every fast-quorum replica reported
    the same dependencies; ``atlas``, every dependency of the union was
    reported by at least ``f`` of the fast quorum (at ``f`` = 1, always).

    ``live_replicas``: replicas (global rows) < this count respond to the
    Synod accept round; the rest are crashed/partitioned for the round.
    With fewer than write_quorum live replicas, slow-path commands do NOT
    commit this round (and neither does anything depending on them).
    Default: all replicas live.

    ``shard_count`` (partial replication, the mesh-native answer to
    fantoch_ps/src/protocol/partial.rs + the cross-shard dep requests of
    fantoch_ps/src/executor/graph/mod.rs:279-408): the replica rows
    factor into ``shard_count`` shards of ``R / shard_count`` replicas
    each, and key bucket ``b`` belongs to shard ``b % shard_count``.
    Quorums are per shard *per key slot* — a multi-shard command commits
    only when every touched shard's quorum agrees — and a replica's key
    clock learns only its own shard's buckets.  Cross-shard dependencies
    need no request RPCs at all: the working set is globally visible on
    the mesh, so the resolver orders a multi-shard command after ALL its
    deps (both shards') in the same gather it uses for one shard.

    ``sites`` (static, like the key width): 1 is the round above, every
    command coordinated by replica 0 and seen by every replica in arrival
    order.  ``sites == n`` (the replicas a shard) is the round with a
    coordinator at every site (:func:`_protocol_step_sites`: any key width
    and shard count, EPaxos's rule or Atlas's at any ``f``): a command's
    coordinator is the replica at site ``dot_src - site_base`` of every
    shard it touches, the replicas see a round's commands in different
    orders and the committed graph has cycles, across keys and shards
    where commands have several keys.  Same state, same columns, another
    program.
    """
    if sites != 1:
        assert sites * shard_count == state.key_clock.shape[0], (
            "a coordinator at every site: a site a replica of every shard"
        )
        return _protocol_step_sites(
            state, key, dot_src, dot_seq, read,
            mesh=mesh, live_replicas=live_replicas, site_base=site_base,
            shard_count=shard_count, f=f, rule=rule,
        )
    num_replicas, key_buckets = state.key_clock.shape
    if key.ndim == 1:
        key = key[:, None]
    batch, key_width = key.shape
    assert key_width == state.pend_key.shape[1], (
        "key width must match init_state(key_width=...)"
    )
    if read is None:
        read = jnp.zeros((batch,), bool)
    pend_cap = state.pend_gid.shape[0]
    work = pend_cap + batch  # working rows: pending buffer first, then new
    assert num_replicas % shard_count == 0, (
        "replica rows must factor into shard_count equal shards"
    )
    per_shard = num_replicas // shard_count
    fast_quorum, write_quorum = quorum_sizes(per_shard, f, rule)
    if live_replicas is None:
        live_replicas = num_replicas
    replica_blocks = num_replicas // mesh.shape[REPLICA_AXIS]
    int_min = jnp.iinfo(jnp.int32).min
    int_max = jnp.iinfo(jnp.int32).max

    def step(
        key_clock, frontier, next_gid, pend_key, pend_src, pend_seq, pend_gid,
        read_clock, pend_read, key_l, dot_src_l, dot_seq_l, read_l,
    ):
        # local blocks: key_clock [r_blk, K], key_l [b_blk, KW] (sharded
        # batch).  1. full batch view of the keys (commands are tiny; one
        # gather), prefixed with the carried pending buffer (older commands
        # first so intra-batch chains point the right way)
        key_new = jax.lax.all_gather(key_l, BATCH_AXIS, tiled=True)  # [B, KW]
        src_new = jax.lax.all_gather(dot_src_l, BATCH_AXIS, tiled=True)
        seq_new = jax.lax.all_gather(dot_seq_l, BATCH_AXIS, tiled=True)
        read_new = jax.lax.all_gather(read_l, BATCH_AXIS, tiled=True)

        widx = jnp.arange(work, dtype=jnp.int32)
        gid = jnp.concatenate(
            [pend_gid, next_gid + jnp.arange(batch, dtype=jnp.int32)]
        )  # [W]
        valid = gid >= 0  # empty pending slots are invalid rows
        key_cat = jnp.concatenate([pend_key, key_new], axis=0)  # [W, KW]
        real_slot = valid[:, None] & (key_cat != KEY_PAD)  # [W, KW]
        # pad slots and invalid rows get unique out-of-range keys:
        # singleton runs, no chain links, no key-clock read
        slot_iota = jnp.arange(work * key_width, dtype=jnp.int32).reshape(
            work, key_width
        )
        key_full = jnp.where(real_slot, key_cat, key_buckets + slot_iota)
        dot_src_f = jnp.where(valid, jnp.concatenate([pend_src, src_new]), 0)
        dot_seq_f = jnp.where(valid, jnp.concatenate([pend_seq, seq_new]), 0)
        read_f = valid & jnp.concatenate([pend_read, read_new])  # [W]

        # 2. per-replica deps, two slots per key (KeyDeps::add_cmd per
        # replica, with the read/write split): the latest write and the
        # latest read of the key among the earlier working rows, else the
        # replica's clock entries; a read keeps the write alone, a write
        # also the read where it came after that write
        perm, head, read_at = _key_runs(key_full, read_f)  # by sorted position
        chain_w = _chain_of_runs(perm, head, key_full.shape, ~read_at)
        chain_r = _chain_of_runs(perm, head, key_full.shape, read_at)
        safe_key = jnp.minimum(key_full, key_buckets - 1)

        def latest(chain, clock):  # [r_blk, W, KW] gids
            prior = jnp.where(real_slot[None], clock[:, safe_key], -1)
            return jnp.where(chain >= 0, gid[jnp.maximum(chain, 0)], prior)

        write_gid = latest(chain_w, key_clock)
        read_gid = latest(chain_r, read_clock)
        since = ~read_f[None, :, None] & (read_gid > write_gid)
        # the third block is no dependency: the key's latest read as each
        # replica knows it, for the tally of the links that commuted
        dep_gid = jnp.concatenate(
            [write_gid, jnp.where(since, read_gid, -1), read_gid], axis=-1
        )  # [r_blk, W, 3*KW]

        # 3. MCollectAck fan-in over each key slot's *shard* fast quorum =
        # the first fast_quorum member rows of the shard owning the slot's
        # bucket (distance-sorted quorum, base.rs:59-131; bucket b belongs
        # to shard b % shard_count).  For a multi-shard command the fast
        # path is every touched shard's quorum at once.  Pad slots have no
        # real bucket: their dep is -1 on every replica, so any shard's
        # quorum agrees.
        row = (
            jax.lax.axis_index(REPLICA_AXIS) * replica_blocks
            + jnp.arange(replica_blocks, dtype=jnp.int32)
        )  # global replica row ids of this block
        slot_shard = jnp.where(
            real_slot, key_cat % shard_count, 0
        )  # [W, KW]
        row_shard = (row // per_shard)[:, None, None]  # [r_blk, 1, 1]
        row_member = (row % per_shard)[:, None, None]
        in_fq = (row_shard == slot_shard[None]) & (
            row_member < fast_quorum
        )  # [r_blk, W, KW]
        in_fq3 = jnp.tile(in_fq, (1, 1, 3))
        fq_max = jax.lax.pmax(
            jnp.where(in_fq3, dep_gid, int_min).max(axis=0), REPLICA_AXIS
        )  # [W, 3*KW]
        # slow-path proposal: union of fast-quorum deps (= per-slot max
        # over latest-per-key singletons), Synod ballot 0 / skip-prepare
        # (synod single.rs:86) — same value either way, so the committed
        # deps are fq_max; what the slow path adds is the accept round.
        final_gid = fq_max[:, : 2 * key_width]  # [W, 2*KW]
        proposed = dep_gid[..., : 2 * key_width]
        in_fq2 = in_fq3[..., : 2 * key_width]
        if rule == "epaxos":
            # every quorum replica reported the same deps on every slot
            # (check_union, epaxos.rs:339-345)
            fq_min = jax.lax.pmin(
                jnp.where(in_fq2, proposed, int_max).min(axis=0), REPLICA_AXIS
            )
            fast = (final_gid == fq_min).all(axis=-1) & valid
        elif f == 1:
            fast = valid  # whoever reported a dependency is one of f
        else:
            # every dependency a quorum replica reported was reported by
            # f of its shard's quorum, on whichever of the shard's key
            # slots (QuorumDeps::check_threshold, atlas.rs: a replica
            # reports one set for all its keys)
            everyone = jax.lax.all_gather(
                jnp.where(in_fq2, proposed, -1), REPLICA_AXIS, tiled=True
            )  # [R, W, 2*KW]
            shard2 = jnp.tile(slot_shard, (1, 2))
            same_shard = shard2[:, :, None] == shard2[:, None, :]  # [W, 2*KW, 2*KW]
            said = (
                (everyone[:, None, :, None, :] == proposed[None, :, :, :, None])
                & same_shard[None, None]
            ).any(axis=-1)  # [R, r_blk, W, 2*KW]: that replica, this report
            enough = ~in_fq2 | (proposed < 0) | (said.sum(axis=0) >= f)
            fast = (
                jax.lax.pmin(
                    enough.all(axis=(0, 2)).astype(jnp.int32), REPLICA_AXIS
                ).astype(bool)
                & valid
            )

        # Synod accept round for fast-path misses: every *live* replica
        # of a slot's shard accepts the ballot-0 proposal (no competing
        # coordinator within a round; crashed replicas don't respond);
        # acks are a per-shard psum and a command commits once EVERY
        # touched shard reaches write_quorum (f+1).  This is the
        # MConsensusAck fan-in (+ the per-shard aggregation of
        # partial.rs:37-142, collapsed into the same round).
        live = (row < live_replicas)[:, None]  # [r_blk, 1]
        shard_live_local = jnp.zeros((shard_count,), jnp.int32).at[
            row // per_shard
        ].add(live[:, 0].astype(jnp.int32))
        shard_live = jax.lax.psum(shard_live_local, REPLICA_AXIS)  # [S]
        acks_slot = shard_live[slot_shard]  # [W, KW]
        slow_ok = jnp.where(
            real_slot, acks_slot >= write_quorum, True
        ).all(axis=-1)
        committed = (fast | slow_ok) & valid
        slow_paths = ((~fast) & valid).sum().astype(jnp.int32)

        # 4. batched resolution of the committed working set.  A final dep
        # is either a working row or already executed (pruned to TERMINAL);
        # uncommitted commands are MISSING: they stay unresolved and so does
        # everything dependency-chained to them.  The clocks hold executed
        # gids only (step 5), so a dep that is a working row is always the
        # intra-batch chain's and never a clock's.
        if key_width == 1:
            # one key a command: level and blocking are read off the key
            # runs of step 2, the sort that built the chains
            order, resolved = _resolve_run_position(
                perm, head, valid & ~committed, read_at
            )
        else:
            # the latest read is a dependency only where the union kept it
            chain = jnp.concatenate(
                [
                    chain_w,
                    jnp.where(
                        final_gid[:, key_width:] >= 0, chain_r, jnp.int32(TERMINAL)
                    ),
                ],
                axis=-1,
            )
            dep_idx = jnp.where(committed[:, None], chain, jnp.int32(MISSING))
            dep_idx = jnp.where(valid[:, None], dep_idx, jnp.int32(TERMINAL))
            # general resolver; max_iters = 2*W+8 guarantees convergence
            # for committed acyclic rows (>= one vertex finalizes per
            # iteration) and the while_loop's changed-flag exits early on
            # the typical round, so degraded rounds cannot strand
            # committed commands past the pending buffer
            res = resolve_general(
                dep_idx, dot_src_f, dot_seq_f, max_iters=2 * work + 8
            )
            order, resolved = res.order, res.resolved
        executed = resolved & committed

        # 5. state update: every *live* replica learns the *executed* dots
        # on the buckets of ITS OWN shard (scatter-max by key slot; later
        # commands in the batch win), writes into its key clock and reads
        # into its read clock — a shard's replicas never store other
        # shards' key state (partial replication).  Only executed gids
        # enter the clocks, and an executed row is never carried, so the
        # clocks never hold a gid of a working set.  The next round leans
        # on that twice (step 4): a dep read from a clock is already
        # executed and prunes to TERMINAL, and it is never a working row,
        # so no join of committed dep gids against the working set's gids
        # is needed to find one.
        own_slot = row_shard == slot_shard[None]  # [r_blk, W, KW]
        done_slot = executed[:, None] & real_slot  # [W, KW]
        learns = live[..., None] & own_slot & done_slot[None]
        reads = read_f[None, :, None]
        new_clock = key_clock.at[:, safe_key].max(
            jnp.where(learns & ~reads, gid[None, :, None], jnp.int32(-1))
        )
        # (a table's scatter costs the table, whatever it scatters: a
        # round that executed no read leaves the read clock alone)
        new_read_clock = jax.lax.cond(
            (executed & read_f).any(),
            lambda clock: clock.at[:, safe_key].max(
                jnp.where(learns & reads, gid[None, :, None], jnp.int32(-1))
            ),
            lambda clock: clock,
            read_clock,
        )
        new_frontier = frontier + jnp.where(
            live[:, 0], executed.sum().astype(jnp.int32), 0
        )
        # GC stability watermark: the meet of all replicas' executed
        # frontiers (gc.rs stable()), here a pmin over the replica axis.
        stable = jax.lax.pmin(new_frontier.min(), REPLICA_AXIS)

        # the round's tallies over its executed rows: dependency slots
        # committed, key slots with a command before them on the bucket
        # (in the working set or the quorum's clocks) and those of them
        # where both are reads, reads, rows on more than one shard
        def count(mask):
            return mask.sum().astype(jnp.int32)

        before_w, before_r = final_gid[:, :key_width], fq_max[:, 2 * key_width:]
        linked = done_slot & ((before_w >= 0) | (before_r >= 0))
        shards_lo = jnp.where(real_slot, slot_shard, shard_count).min(axis=-1)
        shards_hi = jnp.where(real_slot, slot_shard, -1).max(axis=-1)
        tallies = jnp.stack(
            [
                count(jnp.tile(done_slot, (1, 2)) & (final_gid >= 0)),
                count(linked),
                count(linked & read_f[:, None] & (before_r > before_w)),
                count(executed & read_f),
                count(executed & (shards_hi > shards_lo)),
            ]
        )  # ROUND_TALLIES

        # 6. pending carry (the liveness fix): valid-but-unexecuted rows
        # survive into the next round's buffer, oldest first; overflow
        # beyond the capacity is dropped *loudly* (pend_dropped).
        carry = valid & ~executed
        # stable sort: carried rows first, in working order
        carry_order = jnp.argsort(jnp.where(carry, widx, int_max)).astype(jnp.int32)
        take = carry_order[:pend_cap]
        is_carry = carry[take]
        new_pend_gid = jnp.where(is_carry, gid[take], -1)
        new_pend_key = jnp.where(is_carry[:, None], key_cat[take], KEY_PAD)
        new_pend_src = jnp.where(is_carry, dot_src_f[take], -1)
        new_pend_seq = jnp.where(is_carry, dot_seq_f[take], -1)
        new_pend_read = is_carry & read_f[take]
        pending = carry.sum().astype(jnp.int32)
        pend_dropped = jnp.maximum(pending - pend_cap, 0).astype(jnp.int32)

        return (
            new_clock,
            new_frontier,
            next_gid + batch,
            new_pend_key,
            new_pend_src,
            new_pend_seq,
            new_pend_gid,
            new_read_clock,
            new_pend_read,
            order,
            executed,
            fast,
            jnp.where(jnp.tile(real_slot, (1, 2)), final_gid, -1),
            jnp.where(valid, gid, -1),
            slow_paths,
            stable,
            jnp.minimum(pending, pend_cap),
            pend_dropped,
            tallies,
        )

    state_specs = (
        P(REPLICA_AXIS, None),  # key_clock
        P(REPLICA_AXIS),  # frontier
        P(),  # next_gid
        P(),  # pend_key
        P(),  # pend_src
        P(),  # pend_seq
        P(),  # pend_gid
        P(REPLICA_AXIS, None),  # read_clock
        P(),  # pend_read
    )
    # key, dot_src, dot_seq, read
    specs_in = state_specs + (P(BATCH_AXIS),) * 4
    # the outputs are replicated: order (the full working set) ... the tallies
    specs_out = state_specs + (P(),) * len(StepOutput._fields)
    # check_vma=False: outputs derived from all_gather/pmax results are
    # replicated by construction, but the static VMA analysis cannot see
    # through the gather+argsort chain.
    fn = shard_map(
        step, mesh=mesh, in_specs=specs_in, out_specs=specs_out, check_vma=False
    )
    out = fn(*state, key, dot_src, dot_seq, read)
    n_state = len(ReplicaState._fields)
    return ReplicaState(*out[:n_state]), StepOutput(*out[n_state:])


def jit_protocol_step(
    mesh: Mesh,
    live_replicas: int | None = None,
    shard_count: int = 1,
    f: int = 1,
    rule: str = "epaxos",
    sites: int = 1,
    site_base: int = 1,
):
    """jit-compiled step with donated device-resident state (``sites``:
    the round with a coordinator at every site, :func:`protocol_step`)."""
    import functools

    static = dict(
        mesh=mesh, live_replicas=live_replicas, shard_count=shard_count, f=f, rule=rule
    )
    if sites != 1:
        static.update(sites=sites, site_base=site_base)
    return jax.jit(functools.partial(protocol_step, **static), donate_argnums=(0,))


def _atlas_threshold(said: jax.Array, slot_shard: jax.Array, f: int):
    """Atlas's fast-path test at ``f`` >= 2 with a coordinator at every site
    (``QuorumDeps::check_threshold`` over the reports, each joined with the
    coordinator's own: ``tests/sites_reference.py``, departure 9), by working
    row.  ``said``: int32[W, KW, fast_quorum, 2], the ring members' words on
    each key slot (gids, -1 none; member 0 is the coordinator); ``slot_shard``:
    int32[W, KW].  Returns ``(enough, short, split)``: whether, in every shard
    the row touches, every dependency of the union was reported by at least
    ``f`` members of the shard's ring; how many distinct dependencies were not;
    whether the members' reports were not one set (EPaxos's ``check_union``).

    A member's report in a shard is its words on the row's slots of that
    shard joined with the coordinator's, so a word is compared with every
    word on a slot of the same shard, ``(KW * fast_quorum * 2)^2`` a row, and
    whatever the coordinator found counts ``fast_quorum`` times."""
    work, key_width, fast_quorum, _ = said.shape
    entries = key_width * fast_quorum * 2
    with jax.named_scope("atlas_threshold"):
        # (the rows on the minor axis: the other axes are a few long)
        word = said.reshape(work, entries).T  # [E, W]
        equal = word[:, None] == word[None]  # [E, E, W]
        same = equal
        if key_width > 1:  # a report holds the words of one shard's slots
            shard = jnp.repeat(slot_shard, 2 * fast_quorum, axis=1).T  # [E, W]
            same = equal & (shard[:, None] == shard[None])
        # the members whose own word on a slot of the shard is this word
        reported = same.reshape(
            entries, key_width, fast_quorum, 2, work
        ).any(axis=(1, 3))  # [E, fast_quorum, W]
        count = jnp.where(reported[:, 0], fast_quorum, reported.sum(axis=1))
        none = word < 0
        short = ~none & (count < f)  # [E, W]
        # a dependency counts once a row, however many words hold it
        earlier = jnp.tril(jnp.ones((entries, entries), bool), -1)
        repeat = (equal & short[None] & earlier[:, :, None]).any(axis=1)
        return (
            ~short.any(axis=0),
            (short & ~repeat).sum(axis=0).astype(jnp.int32),
            ~(none | reported[:, 0]).all(axis=0),
        )


def _protocol_step_sites(
    state: ReplicaState,
    key: jax.Array,  # int32[B] or int32[B, KW]
    dot_src: jax.Array,  # int32[B] — the coordinator's process: site_base + site
    dot_seq: jax.Array,  # int32[B]
    read: jax.Array | None,
    *,
    mesh: Mesh,
    live_replicas: int | None,
    site_base: int,
    shard_count: int,
    f: int,
    rule: str,
) -> Tuple[ReplicaState, "SiteStepOutput"]:
    """The dep-commit round with a coordinator at every site, for any key
    width and shard count and under either rule (the plain reference is
    ``tests/sites_reference.py``, semantics and departures there).

    A command's coordinator is the replica at its site, ``dot_src -
    site_base`` (upstream's ``dot.source()``; the pending buffer carries
    it as ``pend_src``, so the state is :func:`protocol_step`'s own), in
    every shard the command touches: the replica of shard ``k`` at site
    ``s`` is row ``k * n + s``.  What differs from the round with one
    coordinator:

      * a replica's view of the working set: the pending rows in their
        order, then the batch's rows of its own site, then the batch's
        other rows, each in arrival order.  A replica's own word on a key
        slot of its shard is ``KeyDeps::add_cmd`` over that view (the
        latest write before it and, for a write, the latest read since
        that write; else the replica's clocks); its report is its words on
        the command's slots of its shard, joined with the coordinator's;
      * the fast quorum of a slot is a ring from the coordinator's site
        inside the slot's shard, the rows ``k * n + (s + j) % n``, ``j <
        fast_quorum``; under EPaxos's rule the fast path is taken iff, in
        every touched shard, every member's report is the same set, which
        is iff every member's own word is within the coordinator's
        (``check_union``); under Atlas's iff, in every touched shard,
        every dependency of the union was reported by at least ``f``
        members (``check_threshold``, :func:`_atlas_threshold`; at ``f`` =
        1 always, and nothing is compared); a command that missed it
        takes the accept round, and commits when every shard it touches
        has (``partial.rs``);
      * the committed dependencies are the union of the members' words as
        a set, ``2 * fast_quorum`` a key slot, and they point both ways
        along a key's run and, with several keys a command, across runs.
        One key a command: every dependency lies in the row's own run, and
        the runs go to ``ops/graph_resolve.resolve_key_runs`` (components
        in dependency order, each in dot order), with the rows of a run it
        does not cut marked ``finish`` for the host's Tarjan.  Several:
        ``ops/graph_resolve.resolve_general``'s components pass, which
        marks ``finish`` what its residual cannot hold.  ``finish`` rows
        execute this round too: the clocks learn them and they are not
        carried.

    What a site's replicas have seen before a slot is computed once a
    site (the view is the site's, whichever the shard), where the key
    slots stand sorted by key (a key's slots one run, pending rows first,
    then arrival order: the order of a view but for where the site's own
    rows stand): ``[n, slots]``, not a row of it a replica.  A slot's
    ``fast_quorum`` members then each take their site's view and, where
    it holds nothing, their own row's clocks (one gather of ``[slots,
    fast_quorum]`` entries a table), and the words are scattered back to
    working rows for the quorums."""
    num_replicas, key_buckets = state.key_clock.shape
    if key.ndim == 1:
        key = key[:, None]
    batch, key_width = key.shape
    assert key_width == state.pend_key.shape[1], (
        "key width must match init_state(key_width=...)"
    )
    assert num_replicas % shard_count == 0
    per_shard = num_replicas // shard_count  # replicas a shard: the sites
    if read is None:
        read = jnp.zeros((batch,), bool)
    pend_cap = state.pend_gid.shape[0]
    work = pend_cap + batch
    slots = work * key_width
    fast_quorum, write_quorum = quorum_sizes(per_shard, f, rule)
    width = 2 * key_width * fast_quorum  # committed dependencies a row
    if live_replicas is None:
        live_replicas = num_replicas
    replica_blocks = num_replicas // mesh.shape[REPLICA_AXIS]
    int_max = jnp.iinfo(jnp.int32).max

    def step(
        key_clock, frontier, next_gid, pend_key, pend_src, pend_seq, pend_gid,
        read_clock, pend_read, key_l, dot_src_l, dot_seq_l, read_l,
    ):
        key_new = jax.lax.all_gather(key_l, BATCH_AXIS, tiled=True)  # [B, KW]
        src_new = jax.lax.all_gather(dot_src_l, BATCH_AXIS, tiled=True)
        seq_new = jax.lax.all_gather(dot_seq_l, BATCH_AXIS, tiled=True)
        read_new = jax.lax.all_gather(read_l, BATCH_AXIS, tiled=True)

        # 1. the working set, as protocol_step has it
        widx = jnp.arange(work, dtype=jnp.int32)
        gid = jnp.concatenate(
            [pend_gid, next_gid + jnp.arange(batch, dtype=jnp.int32)]
        )
        valid = gid >= 0
        key_cat = jnp.concatenate([pend_key, key_new], axis=0)  # [W, KW]
        real_slot = valid[:, None] & (key_cat != KEY_PAD)
        slot_iota = jnp.arange(slots, dtype=jnp.int32).reshape(work, key_width)
        key_full = jnp.where(real_slot, key_cat, key_buckets + slot_iota)
        dot_src_f = jnp.where(valid, jnp.concatenate([pend_src, src_new]), 0)
        dot_seq_f = jnp.where(valid, jnp.concatenate([pend_seq, seq_new]), 0)
        read_f = valid & jnp.concatenate([pend_read, read_new])
        slot_shard = jnp.where(real_slot, key_cat % shard_count, 0)  # [W, KW]

        # ... and its key slots sorted by key: ``perm[p]`` is the slot at
        # position ``p``, ``row_at[p]`` its working row
        perm, head, read_at = _key_runs(key_full, read_f)
        row_at = perm // key_width if key_width > 1 else perm
        pos = jnp.arange(slots, dtype=jnp.int32)
        run_start = _run_start(head)
        gid_at, real_at = gid[row_at], real_slot.reshape(-1)[perm]
        key_at = jnp.minimum(key_full.reshape(-1)[perm], key_buckets - 1)
        shard_at = slot_shard.reshape(-1)[perm]
        site_at = jnp.mod(dot_src_f[row_at] - site_base, per_shard)
        new_at = row_at >= pend_cap  # a slot of this round's batch

        # 2. what each site's replicas have seen before a slot.  The view
        # is the site's, whichever the shard: a slot of the first part of
        # the view (pending, or the site's own) has seen the first part's
        # slots before it; a slot of the second part the second part's
        # slots before it, else the whole of the first part
        site = jnp.arange(per_shard, dtype=jnp.int32)
        second = new_at[None] & (site_at[None] != site[:, None])  # [n, P]

        def before(x):  # the value one position earlier
            return jnp.concatenate(
                [jnp.full(x.shape[:-1] + (1,), -1, jnp.int32), x[..., :-1]], axis=-1
            )

        def in_run(p):  # a position of another run is no predecessor
            return jnp.where(p >= run_start[None], p, -1)

        # runs counted down, above a position's bits: in a running maximum
        # from the far end the nearest run wins, and in it the latest position
        bits = slots.bit_length()
        run_down = (slots - jnp.cumsum(head.astype(jnp.int32)))[None]

        def whole_run(v, so_far):  # the latest of ``v`` (positions, -1 none) in the run
            if 2 * bits > 31:  # a working set too long to pack into an int32
                return in_run(so_far[:, _run_end(head)])
            later = jax.lax.cummax(
                jnp.where(v >= 0, (run_down << bits) | (v + 1), 0), axis=1, reverse=True
            )
            after = jnp.where(
                (later >> bits) == run_down, (later & ((1 << bits) - 1)) - 1, -1
            )
            return jnp.maximum(in_run(so_far), after)

        def latest_in_view(member):  # member: bool[P], by position
            first = jnp.where(member[None] & ~second, pos[None], -1)
            a = jax.lax.cummax(first, axis=1)
            b = jax.lax.cummax(jnp.where(member[None] & second, pos[None], -1), axis=1)
            b_before = in_run(before(b))
            late = second & (b_before >= 0)  # found in the view's second part
            at = jnp.where(
                second,
                jnp.where(late, b_before, whole_run(first, a)),
                in_run(before(a)),
            )  # [n, P] the position, -1 where the view holds none
            # ... and where it stands in the view
            return at, at + slots * late.astype(jnp.int32)

        at_w, rank_w = latest_in_view(~read_at)
        at_r, rank_r = latest_in_view(read_at)
        # the latest read of the view came after its latest write
        read_later = (at_w < 0) | (rank_r > rank_w)  # [n, P], where at_r >= 0

        # 3. the fast quorum of each slot's coordinator, a ring from its
        # site inside the slot's shard; the coordinator is member 0.  A
        # member's word on the slot: what its site's view holds, else what
        # the replica itself has learnt (its clocks: a row of this block,
        # or of another device's)
        # (the members stand on the leading axis here: the slots are the
        # long one)
        ring = jnp.mod(
            site_at[None] + jnp.arange(fast_quorum, dtype=jnp.int32)[:, None], per_shard
        )  # [fast_quorum, P] sites

        def of_ring(x, rows, picked):  # x[rows[k, p], p], where picked; else -1 or False
            mine = (jnp.arange(x.shape[0], dtype=jnp.int32)[:, None, None] == rows[None]) & picked
            if x.dtype == jnp.bool_:
                return (mine & x[:, None]).any(axis=0)
            return jnp.where(mine, x[:, None], -1).max(axis=0)  # values >= -1

        row0 = jax.lax.axis_index(REPLICA_AXIS) * replica_blocks
        row = row0 + jnp.arange(replica_blocks, dtype=jnp.int32)  # this block's rows
        # the member's row of the tables, where this block holds it: each
        # table is read once a slot for every row held (a column a slot)
        # and the member's entry picked out of the column
        held = (shard_at * per_shard)[None] + ring - row0
        here = (held >= 0) & (held < replica_blocks) & real_at[None]
        learnt = jax.lax.pmax(
            jnp.stack(
                [
                    of_ring(key_clock[:, key_at], held, here),
                    of_ring(read_clock[:, key_at], held, here),
                ]
            ),
            REPLICA_AXIS,
        )  # [2, fast_quorum, P]: nothing learnt is -1, and no row holds less
        everywhere = jnp.ones_like(here)
        seen_w, seen_r = of_ring(at_w, ring, everywhere), of_ring(at_r, ring, everywhere)
        write_gid = jnp.where(seen_w >= 0, gid_at[jnp.maximum(seen_w, 0)], learnt[0])
        read_gid = jnp.where(seen_r >= 0, gid_at[jnp.maximum(seen_r, 0)], learnt[1])
        # the latest read came after the latest write: by the view where
        # the read is a working row (a working row is after anything
        # learnt), by arrival where both were learnt
        since = jnp.where(
            seen_r >= 0,
            of_ring(read_later, ring, everywhere),
            (seen_w < 0) & (learnt[1] > learnt[0]),
        )
        kept = since & ~read_at[None]  # a write depends on it
        members_at = jnp.stack(
            [
                write_gid,
                jnp.where(kept, read_gid, -1),
                seen_w,
                jnp.where(kept, seen_r, -1),
                # for the tallies: the replica knew a command on the key,
                # and the latest it knew was a read
                ((write_gid >= 0) | (read_gid >= 0)).astype(jnp.int32),
                (since & (read_gid >= 0)).astype(jnp.int32),
            ],
            axis=-1,
        ).transpose(1, 0, 2)  # [P, fast_quorum, 6]
        # ... by working row and key slot
        members = (
            jnp.zeros_like(members_at).at[perm].set(members_at)
            .reshape(work, key_width, fast_quorum, 6)
        )
        said = members[..., :2]  # [W, KW, fast_quorum, 2] gids
        threshold = None  # where Atlas's threshold is taken: (short, split) by row
        if rule == "epaxos":
            # every report of a shard (a member's words joined with the
            # coordinator's) is the same set iff every member's word is
            # within the coordinator's words on the command's slots of
            # that shard
            # (the rows on the minor axis: the other axes are a few long)
            said_t = said.transpose(1, 2, 3, 0)  # [KW, fast_quorum, 2, W]
            mine = said_t[:, 0]  # [KW, 2, W]
            shard_t = slot_shard.T  # [KW, W]
            same_shard = shard_t[:, None] == shard_t[None]  # [KW, KW, W]
            found = (
                (said_t[:, :, :, None, None] == mine[None, None, None])
                & same_shard[:, None, None, :, None]
            ).any(axis=(3, 4))
            fast = ((said_t < 0) | found).all(axis=(0, 1, 2)) & valid
        elif f == 1:
            fast = valid  # whoever reported a dependency is one of f
        else:
            enough, *threshold = _atlas_threshold(said, slot_shard, f)
            fast = enough & valid
        deps_gid = jnp.where(
            real_slot[:, :, None, None], said, -1
        ).reshape(work, width)

        # the accept round, as protocol_step has it: every live replica
        # of a slot's shard accepts the union at ballot 0, and a command
        # commits once every shard it touches has its write quorum
        live = (row < live_replicas)[:, None]  # [r_blk, 1]
        shard_live = jax.lax.psum(
            jnp.zeros((shard_count,), jnp.int32).at[row // per_shard].add(
                live[:, 0].astype(jnp.int32)
            ),
            REPLICA_AXIS,
        )  # [S]
        slow_ok = jnp.where(
            real_slot, shard_live[slot_shard] >= write_quorum, True
        ).all(axis=-1)
        committed = (fast | slow_ok) & valid
        slow_paths = ((~fast) & valid).sum().astype(jnp.int32)

        def count(mask):
            return mask.sum().astype(jnp.int32)

        # 4. the components of the committed graph, and their order
        if key_width == 1:
            # every dependency of a row is a row of its key's run
            res = resolve_key_runs(
                members_at[..., 2:4].reshape(slots, 2 * fast_quorum), head,
                valid[perm], committed[perm], dot_src_f[perm], dot_seq_f[perm],
            )

            def by_row(x_at):  # a column by position, back by working row
                return jnp.zeros_like(x_at).at[perm].set(x_at)

            executed = by_row(res.resolved | res.finish)
            finish = by_row(res.finish)
            order = perm[res.order]
            graph = [
                res.scc_rows, res.scc_count, res.iters, count(finish),
                jnp.int32(0), jnp.int32(0),  # a component lies in one key's run
            ]
            largest = res.scc_rows_max
        else:
            at = members[..., 2:4].reshape(work, width)  # sorted positions
            dep_row = jnp.where(at >= 0, row_at[jnp.maximum(at, 0)], jnp.int32(TERMINAL))
            dep_idx = jnp.where(committed[:, None], dep_row, jnp.int32(MISSING))
            dep_idx = jnp.where(valid[:, None], dep_idx, jnp.int32(TERMINAL))
            res = resolve_general(
                dep_idx, dot_src_f, dot_seq_f, residual=components_residual(work)
            )
            finish = res.stuck & committed
            executed = (res.resolved & committed) | finish
            order = res.order
            # the components of several rows, by their first row
            in_one = res.resolved & committed
            size = jnp.zeros((work,), jnp.int32).at[
                jnp.where(in_one, res.leader, work)
            ].add(1, mode="drop")
            several = in_one & (size[res.leader] > 1)
            lead = jnp.where(several, res.leader, work)  # dropped where none
            # ... whose rows hold more than one key bucket, and buckets of
            # more than one shard
            lead_real = jnp.where(real_slot, lead[:, None], work)

            def lowest(values, above):
                return jnp.full((work,), above, jnp.int32).at[lead_real].min(values, mode="drop")

            def highest(values):
                return jnp.full((work,), -1, jnp.int32).at[lead_real].max(values, mode="drop")

            spans = highest(key_cat) > lowest(key_cat, int_max)
            crosses = highest(slot_shard) > lowest(slot_shard, shard_count)
            safe_lead = jnp.minimum(lead, work - 1)
            graph = [
                count(several), count(size > 1), res.iters, count(finish),
                count(several & spans[safe_lead]), count(several & crosses[safe_lead]),
            ]
            largest = jnp.where(size > 1, size, 0).max().astype(jnp.int32)

        # 5. every live replica learns what executed on the buckets of its
        # own shard, the host-ordered rows too (they execute this round)
        done_at = executed[row_at] & real_at
        learns = (
            live & ((row // per_shard)[:, None] == shard_at[None]) & done_at[None]
        )  # [r_blk, P]
        new_clock = key_clock.at[:, key_at].max(
            jnp.where(learns & ~read_at[None], gid_at[None], jnp.int32(-1))
        )
        new_read_clock = jax.lax.cond(
            (done_at & read_at).any(),
            lambda clock: clock.at[:, key_at].max(
                jnp.where(learns & read_at[None], gid_at[None], jnp.int32(-1))
            ),
            lambda clock: clock,
            read_clock,
        )
        new_frontier = frontier + jnp.where(
            live[:, 0], executed.sum().astype(jnp.int32), 0
        )
        stable = jax.lax.pmin(new_frontier.min(), REPLICA_AXIS)

        # distinct committed dependencies: a sorted row's repeats stand
        # side by side
        ranked = jnp.sort(deps_gid, axis=-1)
        distinct = (ranked >= 0) & jnp.concatenate(
            [jnp.ones((work, 1), bool), ranked[:, 1:] != ranked[:, :-1]], axis=-1
        )
        done_slot = executed[:, None] & real_slot  # [W, KW]
        linked = done_slot & (members[:, :, 0, 4] > 0)
        shards_lo = jnp.where(real_slot, slot_shard, shard_count).min(axis=-1)
        shards_hi = jnp.where(real_slot, slot_shard, -1).max(axis=-1)
        if threshold is None:  # nothing was compared
            threshold = [jnp.int32(0)] * 3
        else:
            short_deps, split = threshold
            with jax.named_scope("atlas_threshold"):
                threshold = [
                    jnp.where(executed, short_deps, 0).sum().astype(jnp.int32),
                    count(executed & split),
                    count(executed & split & fast),
                ]
        tallies = jnp.stack(
            [
                count(distinct & executed[:, None]),
                count(linked),
                count(linked & read_f[:, None] & (members[:, :, 0, 5] > 0)),
                count(executed & read_f),
                count(executed & (shards_hi > shards_lo)),
            ]
            + graph + threshold + [largest]
        )  # SITE_ROUND_TALLIES, SITE_ROUND_GAUGES

        # 6. pending carry, as protocol_step has it
        carry = valid & ~executed
        carry_order = jnp.argsort(jnp.where(carry, widx, int_max)).astype(jnp.int32)
        take = carry_order[:pend_cap]
        is_carry = carry[take]
        pending = carry.sum().astype(jnp.int32)
        return (
            new_clock,
            new_frontier,
            next_gid + batch,
            jnp.where(is_carry[:, None], key_cat[take], KEY_PAD),
            jnp.where(is_carry, dot_src_f[take], -1),
            jnp.where(is_carry, dot_seq_f[take], -1),
            jnp.where(is_carry, gid[take], -1),
            new_read_clock,
            is_carry & read_f[take],
            order,
            executed,
            fast,
            deps_gid,
            jnp.where(valid, gid, -1),
            slow_paths,
            stable,
            jnp.minimum(pending, pend_cap),
            jnp.maximum(pending - pend_cap, 0).astype(jnp.int32),
            tallies,
            finish,
        )

    state_specs = (
        P(REPLICA_AXIS, None), P(REPLICA_AXIS), P(), P(), P(), P(), P(),
        P(REPLICA_AXIS, None), P(),
    )  # ReplicaState, as protocol_step shards it
    fn = shard_map(
        step, mesh=mesh,
        in_specs=state_specs + (P(BATCH_AXIS),) * 4,
        out_specs=state_specs + (P(),) * len(SiteStepOutput._fields),
        check_vma=False,
    )
    out = fn(*state, key, dot_src, dot_seq, read)
    n_state = len(ReplicaState._fields)
    return ReplicaState(*out[:n_state]), SiteStepOutput(*out[n_state:])


# ---------------------------------------------------------------------------
# Newt/Tempo on the mesh: timestamp consensus + stability
# ---------------------------------------------------------------------------


class NewtMeshState(NamedTuple):
    """Device-resident Newt replica state over the mesh.

    ``key_clock[R, K]``: per-replica timestamp clock per key bucket (the
    SequentialKeyClocks map, fantoch_ps/src/protocol/common/table/clocks/
    keys/sequential.rs:9-105).  ``vote_frontier[R, K]``: per-replica
    contiguous vote frontier per key (the RangeEventSet frontier of the
    VotesTable, collapsed to a watermark in this dense round-based regime
    where votes are always consumed contiguously).

    Pending buffer: commands a previous round could not *execute* —
    either uncommitted (degraded quorum; ``pend_clock == -1``) or
    committed-but-unstable (their timestamp above the stability
    watermark; ``pend_clock`` holds the committed clock).  Slot empty iff
    ``pend_key == KEY_PAD``.
    """

    key_clock: jax.Array  # int32[R, K]
    vote_frontier: jax.Array  # int32[R, K]
    pend_key: jax.Array  # int32[Pcap, KW] (KEY_PAD = empty slot/row)
    pend_src: jax.Array  # int32[Pcap]
    pend_seq: jax.Array  # int32[Pcap]
    pend_clock: jax.Array  # int32[Pcap] (-1 = not committed)


class NewtStepOutput(NamedTuple):
    """Outputs over the W = Pcap + B working rows (pending first)."""

    order: jax.Array  # int32[W] — stable rows first, (clock, dot) sorted
    executed: jax.Array  # bool[W] — committed AND stable this round
    committed: jax.Array  # bool[W]
    fast_path: jax.Array  # bool[W]
    clock: jax.Array  # int32[W] — committed timestamp (-1 uncommitted)
    slow_paths: jax.Array  # int32[]
    stable_watermark: jax.Array  # int32[] — min stable clock over keys seen
    pending: jax.Array  # int32[]
    pend_dropped: jax.Array  # int32[]
    # working-row dot identity (pending buffer + this round's batch):
    # the drivers key their registries on these, so a drain never needs a
    # host-side mirror of the device pending buffer — which is what lets
    # a dispatched round be drained later (dispatch/drain pipelining)
    work_src: jax.Array  # int32[W]
    work_seq: jax.Array  # int32[W]


class NewtSiteStepOutput(NamedTuple):
    """What the Newt round with a coordinator at every site gives
    (``newt_protocol_step(sites=n)``): :class:`NewtStepOutput`'s fields, so
    a drain reads it as it reads that, and the round's tallies."""

    order: jax.Array
    executed: jax.Array
    committed: jax.Array
    fast_path: jax.Array
    clock: jax.Array
    slow_paths: jax.Array
    stable_watermark: jax.Array
    pending: jax.Array
    pend_dropped: jax.Array
    work_src: jax.Array
    work_seq: jax.Array
    tallies: jax.Array  # int32[3], by the names of NEWT_SITE_ROUND_TALLIES


# NewtSiteStepOutput.tallies, in order: over the rows committed this round,
# the sum of the timestamp less the lowest proposal of the row's fast quorum
# (how far the views disagree), and the rows that share (key, clock) with
# another of them (the dot decided); the rows executed this round that came
# out before a row of their key, executed this round too, that stood earlier
# in the working set (timestamp order is not arrival order there)
NEWT_SITE_ROUND_TALLIES = ("site_clock_spread", "clock_ties", "arrival_reordered")


def newt_quorum_sizes(
    num_replicas: int, f: int, tiny_quorums: bool = False
) -> Tuple[int, int, int]:
    """(fast_quorum, write_quorum, stability_threshold) — the shared
    protocol-fact formula (Config.newt_quorum_sizes, newt.rs:90-100)."""
    from fantoch_tpu.core.config import Config

    return Config(
        num_replicas, f, newt_tiny_quorums=tiny_quorums
    ).newt_quorum_sizes()


def init_newt_state(
    mesh: Mesh,
    num_replicas: int,
    key_buckets: int = 4096,
    pending_capacity: int = 256,
    key_width: int = 1,
) -> NewtMeshState:
    sharding = NamedSharding(mesh, P(REPLICA_AXIS, None))
    zeros_rk = jax.device_put(
        jnp.zeros((num_replicas, key_buckets), dtype=jnp.int32), sharding
    )
    rep = NamedSharding(mesh, P())
    cap = pending_capacity

    def pend(shape, value):
        return jax.device_put(jnp.full(shape, value, dtype=jnp.int32), rep)

    return NewtMeshState(
        zeros_rk,
        jax.device_put(jnp.zeros((num_replicas, key_buckets), jnp.int32), sharding),
        pend((cap, key_width), KEY_PAD),
        pend((cap,), -1), pend((cap,), -1), pend((cap,), -1),
    )


def _segmented_proposal(prior_of_row, key_full, work):
    """Per-replica batched clock proposal over the working set: same-key
    rows receive consecutive clocks continuing from the replica's prior —
    the tensorized ``SequentialKeyClocks::proposal`` over one round,
    built on the same segmented max-scan core as the device votes-table
    plane (ops/table_ops.segmented_running_max).

    ``prior_of_row``: int32[r_blk, W] — the proposing replica's current
    clock for each row's key.  Returns proposals of the same shape.
    """
    from fantoch_tpu.ops.table_ops import segmented_running_max

    widx = jnp.arange(work, dtype=jnp.int32)
    perm = jnp.argsort(key_full, stable=True).astype(jnp.int32)
    k_sorted = key_full[perm]
    seg_start = jnp.concatenate(
        [jnp.ones((1,), bool), k_sorted[1:] != k_sorted[:-1]]
    )
    seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
    group_first = jax.lax.associative_scan(
        jnp.maximum, jnp.where(seg_start, widx, 0)
    )
    rank = widx - group_first

    base = prior_of_row[:, perm] + 1  # [r_blk, W] in sorted order
    running = segmented_running_max(seg_id, base - rank, axis=-1)
    clock_sorted = rank + running
    return jnp.zeros_like(base).at[:, perm].set(clock_sorted)


def _site_proposals(clock_at, perm, head, run_id, propose_at, site_at, fast_quorum, f):
    """The proposals of a Newt round with a coordinator at every site, over
    the working set sorted by key (:func:`_key_runs`: a key's rows one run,
    in working order): ``(timestamp, fast, lowest)`` by working row, the
    highest proposal of each row's fast quorum, whether at least ``f``
    members reported it, and the lowest proposal of the quorum.

    ``clock_at``: int32[n, W], every replica's clock for the key at each
    sorted position; ``run_id``: the number of each position's run;
    ``propose_at`` / ``site_at``: by position, whether the
    row proposes this round and the site of its coordinator.  Replica
    ``r``'s view is its own site's rows in working order, then the others'
    (the plain reference is ``tests/tempo_sites_reference.py``):

      * the coordinator's proposal ``c(x)`` is its clock plus its own rows
        of the key up to ``x`` (it proposes ``clock + 1`` each time, and
        nothing of another site stands before them in its view);
      * a member ``r`` of the ring ``(s + j) % n``, ``j < fast_quorum``, that
        is not the coordinator starts, for a key, from its clock plus its
        own rows of the key, and proposes ``v = max(c(x), v + 1)`` at each
        other row whose ring holds it, in working order: with ``i`` such
        rows so far, ``i + max(start, the running max of c - i)``, the
        segmented scan of :func:`_segmented_proposal` with a base a row.

    No recurrence couples two replicas: ``c`` depends on the site's own run
    alone."""
    from fantoch_tpu.ops.table_ops import segmented_running_max

    n, work = clock_at.shape
    int_min, int_max = jnp.iinfo(jnp.int32).min, jnp.iinfo(jnp.int32).max

    replica = jnp.arange(n, dtype=jnp.int32)[:, None]
    own = propose_at[None] & (site_at[None] == replica)  # [n, W]
    member = propose_at[None] & (jnp.mod(replica - site_at[None], n) < fast_quorum)
    other = member & ~own  # a member that is not the coordinator
    own_so_far = _count_in_run(head, own)
    coordinator = jnp.where(own, clock_at + own_so_far, 0).sum(axis=0)  # c(x), [W]
    start = clock_at + own_so_far[:, _run_end(head)]
    turn = _count_in_run(head, other)
    lifted = segmented_running_max(
        run_id, jnp.where(other, coordinator[None] - turn, int_min), axis=-1
    )
    proposal = jnp.where(
        own, coordinator[None], jnp.where(other, turn + jnp.maximum(start, lifted), int_min)
    )  # [n, W]: a replica outside the ring proposes nothing
    timestamp = proposal.max(axis=0)
    reports = (member & (proposal == timestamp[None])).sum(axis=0)
    lowest = jnp.where(member, proposal, int_max).min(axis=0)
    by_row = jnp.zeros((work, 3), jnp.int32).at[perm].set(
        jnp.stack(
            [timestamp, (propose_at & (reports >= f)).astype(jnp.int32), lowest], axis=-1
        )
    )
    return by_row[:, 0], by_row[:, 1] > 0, by_row[:, 2]


def newt_protocol_step(
    state: NewtMeshState,
    key: jax.Array,  # int32[B] or int32[B, KW] key buckets (KEY_PAD pads)
    dot_src: jax.Array,  # int32[B]
    dot_seq: jax.Array,  # int32[B]
    *,
    mesh: Mesh,
    f: int = 1,
    tiny_quorums: bool = False,
    live_replicas: int | None = None,
    shard_count: int = 1,
    sites: int = 1,
    site_base: int = 1,
) -> Tuple[NewtMeshState, NewtStepOutput]:
    """One batched Newt round: timestamp proposal, max aggregation over
    the fast quorum, count-of-max fast path, Synod accept for misses, and
    stability-ordered execution (newt.rs:272-338 + 527-546; stability =
    fantoch_ps/src/executor/table/mod.rs:247-270).

    Collective layout: proposals are per-replica local work on the
    key-clock shard; the commit clock is a ``pmax`` over the fast quorum;
    the fast-path count-of-max and the Synod ack count are ``psum``s; the
    stable clock of each key slot is an order statistic over an
    ``all_gather`` along ``replica`` of the vote frontiers at the round's
    slots (``[R, W, KW]``): but for the two donated tables, scattered in
    place, and the hold-back's fill nothing is as long as the key space.

    Multi-key commands (KW > 1): each key slot proposes within its key's
    run independently and the row's proposal is the max over its slots —
    within one round two conflicting commands may therefore tie, breaking
    by dot in the (clock, dot) sort id (the host twin's strictly
    sequential within-round clocks are a refinement; across rounds the
    committed clock still strictly dominates every key it touched).  A
    command executes when its clock is stable on EVERY key it touches.

    ``shard_count`` (partial replication, mirroring the sharded epaxos
    round above and the reference's MShardCommit clock aggregation —
    fantoch_ps/src/protocol/partial.rs + newt.rs mcollect_actions): the
    replica rows factor into ``shard_count`` shards of
    ``R / shard_count`` each; key bucket ``b`` belongs to shard
    ``b % shard_count``; quorums (fast count-of-max, Synod acks) and the
    stability order statistic are per shard *per key slot*; a
    multi-shard command's commit clock is the max over its slots'
    shard-local commit clocks and it executes only when that clock is
    stable on every key it touches (each key judged by its own shard's
    frontiers).  A replica's key-clock/frontier learn only its own
    shard's buckets.

    ``sites`` (static, like the key width): 1 is the round above, every
    command coordinated by replica 0, every replica seeing the working
    set in the same order and the fast quorum the first ``fast_quorum``
    rows.  ``sites == n`` is the round with a coordinator at every site
    (one shard, one key a command; the plain reference is
    ``tests/tempo_sites_reference.py``, semantics and departures there):
    a command's coordinator is the replica at site ``dot_src -
    site_base``, a replica has its own site's commands before every
    other's, the coordinator's proposal floors its quorum's, the quorum
    is a ring from the coordinator's site (:func:`_site_proposals`), and
    the count-of-max test can fail at ``f`` = 2.  Everything from the
    commit on is the round above.  Same state, same columns, another
    program, which also gives :data:`NEWT_SITE_ROUND_TALLIES`
    (:class:`NewtSiteStepOutput`).
    """
    num_replicas, key_buckets = state.key_clock.shape
    if key.ndim == 1:
        key = key[:, None]
    batch, key_width = key.shape
    assert key_width == state.pend_key.shape[1], (
        "key width must match init_newt_state(key_width=...)"
    )
    assert sites in (1, num_replicas // shard_count), (
        "a coordinator at every site: a site a replica"
    )
    if sites != 1:
        assert key_width == 1 and shard_count == 1, (
            "the Newt round with a coordinator at every site: one key a "
            "command, one shard"
        )
    pend_cap = state.pend_key.shape[0]
    work = pend_cap + batch
    assert num_replicas % shard_count == 0, (
        "replica rows must factor into shard_count equal shards"
    )
    per_shard = num_replicas // shard_count
    fast_quorum, write_quorum, stability_threshold = newt_quorum_sizes(
        per_shard, f, tiny_quorums
    )
    if live_replicas is None:
        live_replicas = num_replicas
    replica_blocks = num_replicas // mesh.shape[REPLICA_AXIS]
    int_min, int_max = jnp.iinfo(jnp.int32).min, jnp.iinfo(jnp.int32).max

    def step(
        key_clock, vote_frontier, pend_key, pend_src, pend_seq, pend_clock,
        key_l, src_l, seq_l,
    ):
        key_new = jax.lax.all_gather(key_l, BATCH_AXIS, tiled=True)  # [B, KW]
        src_new = jax.lax.all_gather(src_l, BATCH_AXIS, tiled=True)
        seq_new = jax.lax.all_gather(seq_l, BATCH_AXIS, tiled=True)

        widx = jnp.arange(work, dtype=jnp.int32)
        key_cat = jnp.concatenate([pend_key, key_new], axis=0)  # [W, KW]
        valid = (key_cat != KEY_PAD).any(axis=-1)
        src_f = jnp.where(valid, jnp.concatenate([pend_src, src_new]), 0)
        seq_f = jnp.where(valid, jnp.concatenate([pend_seq, seq_new]), 0)
        prior_clock = jnp.concatenate(
            [pend_clock, jnp.full((batch,), -1, jnp.int32)]
        )  # committed clock carried from earlier rounds, -1 = none
        already_committed = prior_clock >= 0

        # pad slots / already-committed rows must not consume proposals:
        # private out-of-range keys make them singleton runs
        propose = valid & ~already_committed
        real_slot = valid[:, None] & (key_cat != KEY_PAD)  # [W, KW]
        propose_slot = propose[:, None] & real_slot
        slot_iota = jnp.arange(work * key_width, dtype=jnp.int32).reshape(
            work, key_width
        )
        key_full = jnp.where(propose_slot, key_cat, key_buckets + slot_iota)
        safe_key = jnp.minimum(key_full, key_buckets - 1)  # [W, KW]

        # shard geometry: bucket b belongs to shard b % shard_count; a
        # replica row r is member (r % per_shard) of shard (r // per_shard)
        row = (
            jax.lax.axis_index(REPLICA_AXIS) * replica_blocks
            + jnp.arange(replica_blocks, dtype=jnp.int32)
        )
        slot_shard = jnp.where(real_slot, key_cat % shard_count, 0)  # [W, KW]
        row_shard = (row // per_shard)[:, None, None]  # [r_blk, 1, 1]
        own_slot = row_shard == slot_shard[None]  # [r_blk, W, KW]

        if sites != 1:
            # a coordinator at every site: the working set sorted by key
            # (a carried, committed row stays in its key's run and
            # proposes nothing), every replica's clock at each position,
            # and the rings' proposals
            perm, head, _ = _key_runs(
                jnp.where(valid[:, None], key_cat, key_buckets + slot_iota)
            )
            clock_at = jax.lax.all_gather(
                key_clock[:, jnp.minimum(key_cat[perm, 0], key_buckets - 1)],
                REPLICA_AXIS, tiled=True,
            )  # [n, W]; a pad's position reads any entry and proposes nothing
            run_id = jnp.cumsum(head.astype(jnp.int32)) - 1
            timestamp, fast, lowest = _site_proposals(
                clock_at, perm, head, run_id, propose[perm],
                jnp.mod(src_f - site_base, num_replicas)[perm], fast_quorum, f,
            )
            fq_max = jnp.where(propose, timestamp, 0)
            shard_ids = jnp.zeros((1,), jnp.int32)  # one shard
        else:
            # per-replica-block per-slot proposals over the flattened slots
            # (only the owning shard's replicas read their key clock; other
            # replicas' lanes compute masked-out garbage)
            prior_rows = jnp.where(
                propose_slot[None] & own_slot, key_clock[:, safe_key], 0
            )  # [r_blk, W, KW]
            slot_prop = _segmented_proposal(
                prior_rows.reshape(replica_blocks, work * key_width),
                key_full.reshape(work * key_width),
                work * key_width,
            ).reshape(replica_blocks, work, key_width)

            # MCollectAck aggregation: a replica's proposal for a row is ONE
            # clock per shard it owns — the max over the row's slots in that
            # shard (the reference's proposal is per command, newt.rs:272-338)
            # — aggregated over that shard's fast quorum (its first
            # fast_quorum member rows).  Fast path iff EVERY touched shard's
            # max was reported by >= f of its quorum members (newt.rs:527-546
            # via QuorumClocks max_count; the multi-shard fast path needs
            # every touched shard fast).  For shard_count == 1 this is
            # exactly the row-level aggregation of the unsharded round, for
            # every key width.
            shard_ids = jnp.arange(shard_count, dtype=jnp.int32)
            slot_onehot = (
                propose_slot[:, :, None] & (slot_shard[:, :, None] == shard_ids)
            )  # [W, KW, S]
            touched = slot_onehot.any(axis=1)  # [W, S]
            shard_prop = jnp.where(
                slot_onehot[None], slot_prop[..., None], int_min
            ).max(axis=2)  # [r_blk, W, S] — this replica's per-shard row clock
            rep_shard = (row // per_shard)[:, None] == shard_ids[None]  # [r_blk, S]
            in_fq_rs = (
                ((row % per_shard) < fast_quorum)[:, None] & rep_shard
            )[:, None, :]  # [r_blk, 1, S]
            shard_fq_max = jax.lax.pmax(
                jnp.where(in_fq_rs, shard_prop, int_min).max(axis=0), REPLICA_AXIS
            )  # [W, S]
            shard_reports = jax.lax.psum(
                (in_fq_rs & (shard_prop == shard_fq_max[None]))
                .astype(jnp.int32)
                .sum(axis=0),
                REPLICA_AXIS,
            )  # [W, S]
            fast = (
                jnp.where(touched, shard_reports >= f, True).all(axis=-1)
                & propose
            )
            # the commit clock: max over the touched shards' commit clocks
            # (the MShardCommit max aggregation, partial.rs:37-142);
            # propose rows always have >= 1 real slot, others read 0
            fq_max = jnp.where(
                propose,
                jnp.where(touched, shard_fq_max, int_min).max(axis=-1),
                0,
            )  # [W]

        # Synod ballot-0 accept round for fast-path misses: every touched
        # shard must reach write_quorum (f + 1) live acks
        live = (row < live_replicas)[:, None]
        shard_live_local = jnp.zeros((shard_count,), jnp.int32).at[
            row // per_shard
        ].add(live[:, 0].astype(jnp.int32))
        shard_live = jax.lax.psum(shard_live_local, REPLICA_AXIS)  # [S]
        slow_ok = jnp.where(
            propose_slot, shard_live[slot_shard] >= write_quorum, True
        ).all(axis=-1)
        newly_committed = (fast | slow_ok) & propose
        committed = already_committed | newly_committed
        clock = jnp.where(
            newly_committed, fq_max, jnp.where(already_committed, prior_clock, -1)
        )
        slow_paths = (propose & ~fast).sum().astype(jnp.int32)

        # vote/frontier update: each slot's OWNING shard's live replicas
        # chase every committed clock with (detached) votes — scatter-max
        # by the real key (a committed carried row's key_full is private);
        # other shards' replicas never learn foreign buckets
        upd = jnp.where(
            live[..., None]
            & own_slot
            & (committed[None, :, None] & real_slot[None]),
            clock[None, :, None],
            0,
        )  # [r_blk, W, KW]
        real_key = jnp.minimum(
            jnp.where(real_slot, key_cat, 0), key_buckets - 1
        )  # [W, KW]
        new_frontier = vote_frontier.at[:, real_key].max(upd)
        # from here on the tables are read only at the round's key slots
        slot_frontier = new_frontier[:, real_key]  # [r_blk, W, KW]
        # a live replica's key clock chases its votes, this round's
        # consumed proposals among them (upd <= slot_frontier): equal to
        # maximum(key_clock, new_frontier) over the whole table, by the
        # invariant 0 <= vote_frontier <= key_clock on live rows (both
        # start at 0, votes rise only at real_key, live_replicas is static)
        new_key_clock = key_clock.at[:, real_key].max(
            jnp.where(live[..., None], slot_frontier, 0)
        )

        # stability: per-slot (n - threshold)-th smallest frontier across
        # the key's OWNING shard's replicas (mod.rs:247-270; n = shard
        # size) — gather the slots' frontiers along replica, sort within
        # each shard's row block, each slot reads its owner's statistic
        shard_stable = jnp.sort(
            jax.lax.all_gather(
                slot_frontier.reshape(replica_blocks, -1), REPLICA_AXIS, tiled=True
            ).reshape(shard_count, per_shard, work * key_width),  # slots minor
            axis=1,
        )[:, per_shard - stability_threshold].reshape(shard_count, work, key_width)
        slot_stable_clock = jnp.where(
            slot_shard == shard_ids[:, None, None], shard_stable, 0
        ).max(axis=0)  # [W, KW]; a select, not a gather: frontiers are >= 0
        slot_stable = jnp.where(real_slot, clock[:, None] <= slot_stable_clock, True)
        fully_stable = committed & valid & slot_stable.all(axis=-1)
        # per-key holdback (multi-key only matters): a command stable on
        # key A but blocked by its other key must also block every
        # HIGHER-(clock, dot) command on A, or A's timestamp order breaks
        # across rounds (the reference avoids this by executing per-key
        # ops independently; whole-command execution needs the gate).
        # rank = position in the global (clock, dot) order; a key's
        # holdback is the min rank among its committed-but-blocked rows.
        safe_clock = jnp.where(committed & valid, clock, int_max)
        order_cd = jnp.lexsort((seq_f, src_f, safe_clock)).astype(jnp.int32)
        rank_of = jnp.zeros((work,), jnp.int32).at[order_cd].set(
            jnp.arange(work, dtype=jnp.int32)
        )
        blocked = committed & valid & ~fully_stable
        hold = jnp.full((key_buckets,), work, jnp.int32).at[real_key].min(
            jnp.where(
                blocked[:, None] & real_slot, rank_of[:, None], jnp.int32(work)
            )
        )
        clear = jnp.where(
            real_slot, rank_of[:, None] < hold[real_key], True
        ).all(axis=-1)
        executed = fully_stable & clear

        # execution order: stable rows by (clock, dot) — the VotesTable
        # sort id (mod.rs:18)
        sort_key = jnp.where(executed, clock, int_max)
        order = jnp.lexsort((seq_f, src_f, sort_key)).astype(jnp.int32)

        # pending carry: valid unexecuted rows (uncommitted or unstable).
        # Committed rows take priority — their clocks already entered the
        # key/vote tables, so dropping one would force a re-proposal at a
        # higher clock and break the committed (clock, dot) order; an
        # uncommitted drop merely retries.  Within each class, working
        # order is preserved (stable sort keys).
        carry = valid & ~executed
        work32 = jnp.int32(work)
        carry_rank = jnp.where(
            carry,
            jnp.where(committed, widx, widx + work32),
            int_max,
        )
        carry_order = jnp.argsort(carry_rank).astype(jnp.int32)
        take = carry_order[:pend_cap]
        is_carry = carry[take]
        new_pend_key = jnp.where(is_carry[:, None], key_cat[take], KEY_PAD)
        new_pend_src = jnp.where(is_carry, src_f[take], -1)
        new_pend_seq = jnp.where(is_carry, seq_f[take], -1)
        new_pend_clock = jnp.where(is_carry, clock[take], -1)
        pending = carry.sum().astype(jnp.int32)
        pend_dropped = jnp.maximum(pending - pend_cap, 0).astype(jnp.int32)

        # min over the buckets seen; int_max = no keys this round
        watermark = jnp.where(real_slot, slot_stable_clock, int_max).min()

        outputs = (
            new_key_clock, new_frontier,
            new_pend_key, new_pend_src, new_pend_seq, new_pend_clock,
            order, executed, committed, fast & valid, clock,
            slow_paths, watermark,
            jnp.minimum(pending, pend_cap), pend_dropped,
            src_f, seq_f,
        )
        if sites == 1:
            return outputs
        # what the views cost, NEWT_SITE_ROUND_TALLIES: how far a quorum's
        # proposals lie apart; the rows committed this round whose (key,
        # clock) another of them has, neighbours once sorted by both (a row
        # not of them a key of its own); the executed rows with a row of
        # their key, earlier in the working set, later in (clock, dot) order
        from fantoch_tpu.ops.table_ops import segmented_running_max

        spread = jnp.where(newly_committed, clock - lowest, 0).sum()
        tie_key, tie_clock = jax.lax.sort(
            (jnp.where(newly_committed, key_cat[:, 0], key_buckets + widx), clock),
            num_keys=2,
        )
        same = (tie_key[1:] == tie_key[:-1]) & (tie_clock[1:] == tie_clock[:-1])
        no = jnp.zeros((1,), bool)
        ties = (jnp.concatenate([no, same]) | jnp.concatenate([same, no])).sum()
        place_at = jnp.where(executed, rank_of, -1)[perm]
        before_at = segmented_running_max(
            run_id, place_at
        )  # the latest place so far in the key's run, this position's among them
        reordered = ((place_at >= 0) & (place_at < before_at)).sum()
        tallies = jnp.stack([spread, ties, reordered]).astype(jnp.int32)
        return outputs + (tallies,)

    specs_in = (
        P(REPLICA_AXIS, None),  # key_clock
        P(REPLICA_AXIS, None),  # vote_frontier
        P(), P(), P(), P(),  # pending buffer
        P(BATCH_AXIS), P(BATCH_AXIS), P(BATCH_AXIS),
    )
    specs_out = (
        P(REPLICA_AXIS, None),
        P(REPLICA_AXIS, None),
        P(), P(), P(), P(),  # pending buffer
        P(), P(), P(), P(), P(),  # order/executed/committed/fast/clock
        P(), P(), P(), P(),  # slow/watermark/pending/dropped
        P(), P(),  # work identity columns
    ) + ((P(),) if sites != 1 else ())  # the site round's tallies
    fn = shard_map(
        step, mesh=mesh, in_specs=specs_in, out_specs=specs_out, check_vma=False
    )
    out = fn(
        state.key_clock, state.vote_frontier,
        state.pend_key, state.pend_src, state.pend_seq, state.pend_clock,
        key, dot_src, dot_seq,
    )
    n_state = len(NewtMeshState._fields)
    output = NewtStepOutput if sites == 1 else NewtSiteStepOutput
    return NewtMeshState(*out[:n_state]), output(*out[n_state:])


def _newt_static(mesh, f, tiny_quorums, live_replicas, shard_count, sites, site_base):
    """The static arguments of a Newt program: those of the round with one
    coordinator, and ``sites`` / ``site_base`` only where every site has one
    (the program with one is built without them, as it always was)."""
    static = dict(
        mesh=mesh, f=f, tiny_quorums=tiny_quorums,
        live_replicas=live_replicas, shard_count=shard_count,
    )
    if sites != 1:
        static.update(sites=sites, site_base=site_base)
    return static


def jit_newt_step(
    mesh: Mesh,
    f: int = 1,
    tiny_quorums: bool = False,
    live_replicas: int | None = None,
    shard_count: int = 1,
    sites: int = 1,
    site_base: int = 1,
):
    """jit-compiled Newt round with donated device-resident state
    (``sites``: the round with a coordinator at every site,
    :func:`newt_protocol_step`)."""
    import functools

    static = _newt_static(
        mesh, f, tiny_quorums, live_replicas, shard_count, sites, site_base
    )
    return jax.jit(
        functools.partial(newt_protocol_step, **static), donate_argnums=(0,)
    )


def newt_protocol_multi_step(
    state: NewtMeshState,
    keys: jax.Array,  # int32[S, B] or int32[S, B, KW] — S chained rounds
    dot_srcs: jax.Array,  # int32[S, B]
    dot_seqs: jax.Array,  # int32[S, B]
    *,
    mesh: Mesh,
    f: int = 1,
    tiny_quorums: bool = False,
    live_replicas: int | None = None,
    shard_count: int = 1,
    sites: int = 1,
    site_base: int = 1,
) -> Tuple[NewtMeshState, NewtStepOutput]:
    """S chained Newt rounds in ONE dispatch via ``lax.scan`` — the
    votes-table plane's in-dispatch chaining (ops/table_ops.
    fused_table_rounds) applied to the mesh serving family: replica
    state threads round-to-round on device and the host pays one
    dispatch round-trip for the whole chain, which is what drops
    ``serving_newt_round_ms`` on dispatch-dominated rigs.

    Outputs are the per-round :class:`NewtStepOutput` arrays stacked on a
    leading ``S`` axis; the caller drains all S rounds afterwards (the
    dispatch/drain pipelining contract of ``work_src``/``work_seq``).
    ``sites`` / ``site_base``: the round's (a chain of rounds with a
    coordinator at every site stacks :class:`NewtSiteStepOutput`).
    """
    static = _newt_static(
        mesh, f, tiny_quorums, live_replicas, shard_count, sites, site_base
    )

    def body(carry, xs):
        key, src, seq = xs
        new_state, out = newt_protocol_step(carry, key, src, seq, **static)
        return new_state, out

    return jax.lax.scan(body, state, (keys, dot_srcs, dot_seqs))


def jit_newt_multi_step(
    mesh: Mesh,
    f: int = 1,
    tiny_quorums: bool = False,
    live_replicas: int | None = None,
    shard_count: int = 1,
    sites: int = 1,
    site_base: int = 1,
):
    """The multi-round Newt chain with donated state, jitted: S rides the
    inputs' leading axis, so each chain length is a program of its own.
    A server lowers and compiles (or loads) the lengths its tuner may
    pick on the real shapes before it serves
    (run/device_runner.py ``NewtDeviceDriver.precompile_chains``)."""
    import functools

    static = _newt_static(
        mesh, f, tiny_quorums, live_replicas, shard_count, sites, site_base
    )
    return jax.jit(
        functools.partial(newt_protocol_multi_step, **static), donate_argnums=(0,)
    )


# ---------------------------------------------------------------------------
# leader-based (FPaxos / MultiPaxos) slot round: the third consensus class
# ---------------------------------------------------------------------------


class PaxosMeshState(NamedTuple):
    """Device state for the leader-based slot round.

    ``next_slot``: the leader's next log slot.  ``exec_frontier``: slots
    executed so far (execution is in contiguous slot order — the
    SlotExecutor contract, fantoch_tpu/executor/slot.py).  Pending buffer
    carries accepted-but-uncommitted commands with their slots (a leader
    retries the SAME slot after a failed accept round — MultiPaxos
    slot stickiness, fantoch_tpu/protocol/common/multi_synod.py)."""

    next_slot: jax.Array  # int32[]
    exec_frontier: jax.Array  # int32[] — slots < this executed
    pend_slot: jax.Array  # int32[Pcap] (-1 empty)
    pend_src: jax.Array  # int32[Pcap]
    pend_seq: jax.Array  # int32[Pcap]


class PaxosStepOutput(NamedTuple):
    order: jax.Array  # int32[W] — executed rows in slot order first
    executed: jax.Array  # bool[W]
    committed: jax.Array  # bool[W]
    slot: jax.Array  # int32[W] (-1 = pad row)
    pending: jax.Array  # int32[]
    pend_dropped: jax.Array  # int32[]
    # this round's exec frontier and working-row dot identity (see
    # NewtStepOutput.work_src) — the driver reads the round's own
    # frontier even when a later round has already been dispatched
    exec_frontier: jax.Array  # int32[]
    work_src: jax.Array  # int32[W]
    work_seq: jax.Array  # int32[W]


def init_paxos_state(
    mesh: Mesh, pending_capacity: int = 256
) -> PaxosMeshState:
    rep = NamedSharding(mesh, P())

    def pend(value):
        return jax.device_put(
            jnp.full((pending_capacity,), value, dtype=jnp.int32), rep
        )

    return PaxosMeshState(
        jax.device_put(jnp.int32(0), rep),
        jax.device_put(jnp.int32(0), rep),
        pend(-1), pend(-1), pend(-1),
    )


def paxos_protocol_step(
    state: PaxosMeshState,
    valid: jax.Array,  # bool[B] — real command rows (pads False)
    dot_src: jax.Array,  # int32[B]
    dot_seq: jax.Array,  # int32[B]
    *,
    mesh: Mesh,
    f: int = 1,
    num_replicas: int | None = None,
    live_replicas: int | None = None,
) -> Tuple[PaxosMeshState, PaxosStepOutput]:
    """One leader-based accept round for a batch of commands
    (fantoch_tpu/protocol/fpaxos.py over MultiSynod; quorum = f + 1).

    Replica 0 is the leader: it assigns consecutive slots (pending rows
    keep their previous slots — MultiPaxos slot stickiness) and runs the
    accept round for the whole batch at once — acceptor acks are a
    ``psum`` over the live replicas; a slot commits at f + 1 acks.
    Execution is strictly contiguous in slot order: committed slots above
    a gap (an uncommitted earlier slot) wait in the pending buffer,
    exactly the SlotExecutor semantics.
    """
    if num_replicas is None:
        num_replicas = 2 * mesh.shape[REPLICA_AXIS]
    batch = valid.shape[0]
    pend_cap = state.pend_slot.shape[0]
    work = pend_cap + batch
    quorum = f + 1
    if live_replicas is None:
        live_replicas = num_replicas
    replica_blocks = num_replicas // mesh.shape[REPLICA_AXIS]
    int_max = jnp.iinfo(jnp.int32).max

    def step(
        next_slot, exec_frontier, pend_slot, pend_src, pend_seq,
        valid_l, src_l, seq_l,
    ):
        valid_new = jax.lax.all_gather(valid_l, BATCH_AXIS, tiled=True)
        src_new = jax.lax.all_gather(src_l, BATCH_AXIS, tiled=True)
        seq_new = jax.lax.all_gather(seq_l, BATCH_AXIS, tiled=True)

        widx = jnp.arange(work, dtype=jnp.int32)
        carried = pend_slot >= 0
        valid_cat = jnp.concatenate([carried, valid_new])
        src_f = jnp.concatenate([pend_src, src_new])
        seq_f = jnp.concatenate([pend_seq, seq_new])

        # leader slot assignment: pending rows keep their slots; new valid
        # rows get consecutive slots from next_slot (prefix-sum ranks)
        is_new = jnp.concatenate([jnp.zeros((pend_cap,), bool), valid_new])
        new_rank = jnp.cumsum(is_new.astype(jnp.int32)) - 1
        slot_pend = jnp.concatenate(
            [pend_slot, jnp.full((batch,), -1, jnp.int32)]
        )
        slot = jnp.where(
            slot_pend >= 0,
            slot_pend,
            jnp.where(is_new, next_slot + new_rank, -1),
        )

        # accept round: every live replica acks every proposed slot
        # (ballot-0 leader; crashed replicas stay silent) — the ack count
        # is one scalar psum of live acceptors
        row = (
            jax.lax.axis_index(REPLICA_AXIS) * replica_blocks
            + jnp.arange(replica_blocks, dtype=jnp.int32)
        )
        live = row < live_replicas  # [r_blk]
        acks = jax.lax.psum(live.astype(jnp.int32).sum(), REPLICA_AXIS)
        committed = valid_cat & (slot >= 0) & (acks >= quorum)

        # contiguous slot execution: sort committed slots and count the
        # run that extends exec_frontier without a gap
        sort_slot = jnp.where(committed, slot, int_max)
        order = jnp.argsort(sort_slot).astype(jnp.int32)
        ordered_slots = sort_slot[order]
        pos = jnp.arange(work, dtype=jnp.int32)
        contiguous = ordered_slots == exec_frontier + pos
        # prefix of the sorted committed slots with no gap
        run = jnp.cumprod(contiguous.astype(jnp.int32)) == 1
        executed_sorted = run & (ordered_slots < int_max)
        executed = jnp.zeros((work,), bool).at[order].set(executed_sorted)
        n_exec = executed_sorted.sum().astype(jnp.int32)
        new_frontier = exec_frontier + n_exec

        # pending carry in SLOT order (lowest first): the in-flight slots
        # are exactly [exec_frontier, next_slot), so keeping the lowest
        # pend_cap makes any overflow drop the top slots — which the slot
        # counter then ROLLS BACK, keeping the log dense.  Without the
        # rollback a dropped slot is an un-fillable hole that freezes the
        # contiguous frontier forever (livelock).  Dropped commands are
        # reported via pend_dropped and must be resubmitted by the caller
        # (in this dense round model no acceptor holds durable state for
        # an unexecuted slot, so reassigning it is safe).
        carry = valid_cat & ~executed
        carry_order = jnp.argsort(jnp.where(carry, slot, int_max)).astype(jnp.int32)
        take = carry_order[:pend_cap]
        is_carry = carry[take]
        new_pend_slot = jnp.where(is_carry, slot[take], -1)
        new_pend_src = jnp.where(is_carry, src_f[take], -1)
        new_pend_seq = jnp.where(is_carry, seq_f[take], -1)
        pending = carry.sum().astype(jnp.int32)
        dropped = jnp.maximum(pending - pend_cap, 0).astype(jnp.int32)

        new_next = next_slot + is_new.sum().astype(jnp.int32) - dropped
        return (
            new_next, new_frontier,
            new_pend_slot, new_pend_src, new_pend_seq,
            order, executed, committed, slot,
            jnp.minimum(pending, pend_cap),
            dropped,
            src_f, seq_f,
        )

    specs_in = (
        P(), P(), P(), P(), P(),
        P(BATCH_AXIS), P(BATCH_AXIS), P(BATCH_AXIS),
    )
    specs_out = (P(),) * 13
    fn = shard_map(
        step, mesh=mesh, in_specs=specs_in, out_specs=specs_out, check_vma=False
    )
    (
        next_slot, frontier, ps_, px, pq,
        order, executed, committed, slot, pending, dropped,
        work_src, work_seq,
    ) = fn(
        state.next_slot, state.exec_frontier,
        state.pend_slot, state.pend_src, state.pend_seq,
        valid, dot_src, dot_seq,
    )
    return (
        PaxosMeshState(next_slot, frontier, ps_, px, pq),
        PaxosStepOutput(
            order, executed, committed, slot, pending, dropped,
            frontier, work_src, work_seq,
        ),
    )


def jit_paxos_step(
    mesh: Mesh,
    f: int = 1,
    num_replicas: int | None = None,
    live_replicas: int | None = None,
):
    """jit-compiled leader-based slot round with donated state."""
    import functools

    return jax.jit(
        functools.partial(
            paxos_protocol_step,
            mesh=mesh,
            f=f,
            num_replicas=num_replicas,
            live_replicas=live_replicas,
        ),
        donate_argnums=(0,),
    )


# ---------------------------------------------------------------------------
# Caesar on the mesh: timestamp + predecessors with the wait condition —
# the fourth consensus shape (fantoch_ps/src/protocol/caesar.rs:216-451,
# execution = fantoch_ps/src/executor/pred/mod.rs:132-186)
# ---------------------------------------------------------------------------


class CaesarMeshState(NamedTuple):
    """Device-resident Caesar replica state over the mesh.

    ``key_clock[R, K]``: per-replica highest timestamp known per key
    bucket (the per-key clock index of caesar.rs:786-838, collapsed to a
    max in this dense round regime — predecessors below the executed
    frontier are GC'd, so only the ceiling matters to new proposals).

    Pending buffer: commands a previous round could not execute — either
    uncommitted (``pend_clock == -1``: retry quorum unreachable) or
    committed-but-blocked behind an uncommitted lower-clock conflict
    (the wait condition; ``pend_clock`` holds the committed timestamp).
    """

    key_clock: jax.Array  # int32[R, K]
    pend_key: jax.Array  # int32[Pcap, KW] (KEY_PAD = empty)
    pend_src: jax.Array  # int32[Pcap]
    pend_seq: jax.Array  # int32[Pcap]
    pend_clock: jax.Array  # int32[Pcap] (-1 = not committed)


class CaesarStepOutput(NamedTuple):
    """Outputs over the W = Pcap + B working rows (pending first)."""

    order: jax.Array  # int32[W] — executed rows first, (clock, dot) sorted
    executed: jax.Array  # bool[W]
    committed: jax.Array  # bool[W]
    fast_path: jax.Array  # bool[W]
    clock: jax.Array  # int32[W] — committed timestamp (-1 uncommitted)
    slow_paths: jax.Array  # int32[] — retry (counter-proposal) rounds
    watermark: jax.Array  # int32[] — max executed clock this round
    pending: jax.Array  # int32[]
    pend_dropped: jax.Array  # int32[]
    # working-row dot identity (see NewtStepOutput.work_src)
    work_src: jax.Array  # int32[W]
    work_seq: jax.Array  # int32[W]


class CaesarSiteStepOutput(NamedTuple):
    """What the Caesar round with a coordinator at every site gives
    (``caesar_protocol_step(sites=n)``): :class:`CaesarStepOutput`'s fields,
    so a drain reads it as it reads that, and the round's tallies."""

    order: jax.Array
    executed: jax.Array
    committed: jax.Array
    fast_path: jax.Array
    clock: jax.Array
    slow_paths: jax.Array
    watermark: jax.Array
    pending: jax.Array
    pend_dropped: jax.Array
    work_src: jax.Array
    work_seq: jax.Array
    # int32[5], by the names of CAESAR_SITE_ROUND_TALLIES, then _GAUGES
    tallies: jax.Array


# CaesarSiteStepOutput.tallies, in order: over the rows committed this round,
# those of whose ring at least one member met a blocker (a higher-timestamp
# conflict it had seen already: its answer waited for that one's fate), such
# members summed, the members that rejected, and the sum over the retried of
# the retry clock less the proposed one; then the gauge, the depth of the
# recursion that settles the verdicts (passes of ``_caesar_site_answers``'
# loop; 0 where nothing proposed)
CAESAR_SITE_ROUND_TALLIES = ("wait_rows", "wait_acks", "reject_acks", "retry_clock_lift")
CAESAR_SITE_ROUND_GAUGES = ("wait_passes",)


def _caesar_site_answers(
    clock_at, head, run_id, propose_at, carried_at, carried_clock_at,
    site_at, src_at, seq_at, live_of, fast_quorum,
):
    """The answers of a Caesar round with a coordinator at every site, over
    the working set sorted by key (:func:`_key_runs`: a key's rows one run, in
    working order).  By sorted position: ``(t0, fast, t1, waited, rejected,
    occupied, passes)``, the coordinator's proposal, whether every member of
    the row's ring said ok, the retry clock (the highest report of the ring),
    how many members met a blocker, how many rejected, every replica's
    clock for the key once the round's proposals and counter-proposals are
    made (``int32[n, W]``), and the depth of the recursion.

    ``clock_at``: int32[n, W], every replica's clock for the key at each
    position; ``propose_at``: the row proposes this round; ``carried_at`` /
    ``carried_clock_at``: the row was committed by an earlier round, and at
    what; ``site_at``: the site of its coordinator; ``live_of``: bool[n].
    The plain reference is ``tests/caesar_sites_reference.py``, rule by rule
    beside the handlers of ``protocol/caesar.py``; this is what its general
    rule comes to with every replica's view its own site's rows first, then
    the others' in working order, and a ring ``(s + j) % n, j < fast_quorum``
    of at least three:

      * ``T0(x)`` is the coordinator's clock plus its own rows of the key up
        to ``x``; rows are ranked by ``(T0, dot)`` (a carried, committed row
        by its clock), ``rank`` below: the two orders of the dominance test
        are ``rank`` and the position in the run.
      * ``y`` is a blocker of ``x`` that a member of ``x``'s ring cannot
        ignore iff ``y`` went fast, stands before ``x`` in the run, ranks
        higher, and ``x``'s coordinator is outside ``y``'s ring (inside, it
        met ``x`` first and reported it; every other member of ``y``'s ring
        met ``y`` first): then every member of ``x``'s ring but its
        coordinator rejects.  A retried ``y`` is harmless: a member that
        rejected it reported everything it had met.  That is a running max,
        inside the run, of the ranks of the fast rows a site has to mind.
      * the recursion runs along descending ranks: a row is settled once every
        row it may have to mind is, and a pass settles every row that can be,
        so the settled set grows only and the loop ends after as many passes
        as the longest such chain has rows.  A ``lax.while_loop``, as the
        execution gate's: the depth is the data's (1 with one site or a ring
        that is everyone; 5 to 7 on a hot key of 2,000 rows from seven
        coordinators: every link needs a fast row of the next site that
        stands earlier and ranks higher), a bound would be the working
        set's size.
      * a carried, committed row above ``T0(x)`` (its coordinator lags) makes
        every live member reject.
      * a replica's counter-proposals are made from the highest rank down, each
        one above its clock for the key once the whole view is met, so a
        report is that clock plus the replica's rejections at or above
        ``x``'s rank: a count in the runs sorted by descending rank.
    """
    from fantoch_tpu.ops.table_ops import segmented_running_max

    n, work = clock_at.shape
    int_max = jnp.iinfo(jnp.int32).max
    replica = jnp.arange(n, dtype=jnp.int32)[:, None]
    run_end = _run_end(head)
    own = propose_at[None] & (site_at[None] == replica)  # [n, W]
    member = propose_at[None] & (jnp.mod(replica - site_at[None], n) < fast_quorum)
    own_so_far = _count_in_run(head, own)
    t0 = jnp.where(own, clock_at + own_so_far, 0).sum(axis=0)  # [W]
    # every replica meets every row of the round: its clock for the key after
    # its walk is the highest of its own proposals and everyone's
    top = jnp.maximum(
        clock_at + own_so_far[:, run_end], segmented_running_max(run_id, t0)[run_end]
    )  # [n, W], one value a run
    stamp = jnp.where(propose_at, t0, jnp.where(carried_at, carried_clock_at, int_max))
    by_stamp = jnp.lexsort((seq_at, src_at, stamp)).astype(jnp.int32)
    rank = jnp.zeros((work,), jnp.int32).at[by_stamp].set(
        jnp.arange(work, dtype=jnp.int32)
    )

    def run_max(values):  # of ranks or -1, running inside the run
        return segmented_running_max(run_id, values, axis=-1)

    with jax.named_scope("caesar_wait"):
        lagging = propose_at & (
            run_max(jnp.where(carried_at, rank, -1))[run_end] > rank
        )  # a carried, committed row of the key ranks above
        # minds[t, y]: a row of site t has to mind y (t outside y's ring)
        minds = propose_at[None] & (jnp.mod(replica - site_at[None], n) >= fast_quorum)
        at_site = site_at[None] == replica  # [n, W]: a row reads its site's line

        def settle(state):
            settled, minded, passes = state
            fast = settled & propose_at & ~minded & ~lagging
            seen = run_max(
                jnp.concatenate(
                    [
                        jnp.where(minds & fast[None], rank[None], -1),
                        jnp.where(minds & ~settled[None], rank[None], -1),
                    ]
                )
            )  # [2n, W]: the highest fast rank so far, the highest unsettled
            fast_above = jnp.where(at_site, seen[:n], -1).max(axis=0) > rank
            open_above = jnp.where(at_site, seen[n:], -1).max(axis=0) > rank
            ready = ~settled & ~open_above
            return settled | ready, minded | (ready & fast_above), passes + 1

        _, minded, passes = jax.lax.while_loop(
            lambda state: ~state[0].all(),
            settle,
            (~propose_at, jnp.zeros((work,), bool), jnp.int32(0)),
        )
        fast = propose_at & ~minded & ~lagging
        # who met a blocker: a member but the coordinator that has a higher
        # rank among its own site's rows of the run, or among the rows
        # before this one; a live member under a lagging coordinator
        own_top = run_max(jnp.where(own, rank[None], -1))[:, run_end]
        before = run_max(jnp.where(propose_at, rank, -1))
        late = lagging[None] & live_of[:, None]
        met = member & (
            (~own & (jnp.maximum(own_top, before[None]) > rank[None])) | late
        )
    with jax.named_scope("caesar_retry"):
        # a row that has a fast one to mind is rejected by every member of
        # its ring but its coordinator; a lagging one by the live members
        rejects = member & ((minded[None] & ~own) | late)
        # the runs again, each by descending rank: a replica's rejections so
        # far are its counter-proposals made
        _, _, down = jax.lax.sort(
            (run_id, -rank, jnp.arange(work, dtype=jnp.int32)), num_keys=2
        )
        made = _count_in_run(head, rejects[:, down])  # the run's extents are the same
        so_far = jnp.zeros((n, work), jnp.int32).at[:, down].set(made)
        t1 = jnp.maximum(jnp.where(rejects, top + so_far, 0).max(axis=0), t0)
        occupied = top + made[:, run_end]  # ... and its last position has them all
    return t0, fast, t1, met.sum(axis=0), rejects.sum(axis=0), occupied, passes


def init_caesar_state(
    mesh: Mesh,
    num_replicas: int,
    key_buckets: int = 4096,
    pending_capacity: int = 256,
    key_width: int = 1,
) -> CaesarMeshState:
    sharding = NamedSharding(mesh, P(REPLICA_AXIS, None))
    key_clock = jax.device_put(
        jnp.zeros((num_replicas, key_buckets), dtype=jnp.int32), sharding
    )
    rep = NamedSharding(mesh, P())

    def pend(shape, value):
        return jax.device_put(
            jnp.full(shape, value, dtype=jnp.int32), rep
        )

    cap = pending_capacity
    return CaesarMeshState(
        key_clock,
        pend((cap, key_width), KEY_PAD),
        pend((cap,), -1),
        pend((cap,), -1),
        pend((cap,), -1),
    )


def caesar_protocol_step(
    state: CaesarMeshState,
    key: jax.Array,  # int32[B] or int32[B, KW] key buckets (KEY_PAD pads)
    dot_src: jax.Array,  # int32[B]
    dot_seq: jax.Array,  # int32[B]
    *,
    mesh: Mesh,
    num_replicas: int | None = None,
    live_replicas: int | None = None,
    sites: int | None = None,
    site_base: int = 1,
) -> Tuple[CaesarMeshState, CaesarStepOutput]:
    """One batched Caesar round: timestamp proposal, fast-quorum (3n/4+1)
    agreement, the MRetry counter-proposal as a second masked aggregation
    in the same step, and wait-condition-gated execution in (clock, dot)
    order (caesar.rs:216-451).

    Collective layout: proposals are per-replica local work on the
    key-clock shard; fast agreement is ``pmax == pmin`` over the fast
    quorum; the retry clock is a ``pmax`` over the LIVE replicas (the
    aggregated counter-proposal of MProposeAck ok=false) and commits iff
    the live count reaches the write quorum (majority) — a ``psum``.

    Execution models the PredecessorsExecutor's two phases in the dense
    regime: per key bucket, committed rows execute in (clock, dot) order
    up to the first uncommitted conflict (phase 1: a predecessor of
    unknown fate blocks; phase 2: lower-clock predecessors execute
    first); a multi-key row blocked on one bucket holds back every
    higher-(clock, dot) row on its other buckets — the same gate the
    Newt round uses, with commit-ness in place of vote stability.

    ``sites`` (static): ``None`` is the round above, every command numbered
    by every replica from one view of the round, the fast quorum the first
    ``fast_quorum`` rows.  ``sites == n`` is the round with a coordinator at
    every site (one key a command; the plain reference is
    ``tests/caesar_sites_reference.py``, semantics and departures there): a
    command's coordinator is the replica at site ``dot_src - site_base``, a
    replica has its own site's commands before every other's, a member of
    the command's ring (``(s + j) % n``, ``j < fast_quorum``) that has met a
    higher-timestamp conflict holds its answer back until that one's fate is
    known and rejects if it went fast without depending on the command (the
    wait condition), and the coordinator retries at the highest
    counter-proposal (:func:`_caesar_site_answers`).  Everything from the
    commit on is the round above.  Same state, same columns, another program,
    which also gives :data:`CAESAR_SITE_ROUND_TALLIES`
    (:class:`CaesarSiteStepOutput`).
    """
    R, key_buckets = state.key_clock.shape
    if num_replicas is None:
        num_replicas = R
    if key.ndim == 1:
        key = key[:, None]
    batch, key_width = key.shape
    assert key_width == state.pend_key.shape[1]
    assert sites in (None, num_replicas), (
        "a coordinator at every site: a site a replica"
    )
    if sites is not None:
        assert key_width == 1, (
            "the Caesar round with a coordinator at every site: one key a "
            "command, one shard"
        )
    pend_cap = state.pend_key.shape[0]
    work = pend_cap + batch
    from fantoch_tpu.core.config import Config

    fast_quorum, write_quorum = Config(num_replicas, 0).caesar_quorum_sizes()
    if live_replicas is None:
        live_replicas = num_replicas
    replica_blocks = num_replicas // mesh.shape[REPLICA_AXIS]
    int_min = jnp.iinfo(jnp.int32).min
    int_max = jnp.iinfo(jnp.int32).max

    def step(
        key_clock, pend_key, pend_src, pend_seq, pend_clock,
        key_l, src_l, seq_l,
    ):
        key_new = jax.lax.all_gather(key_l, BATCH_AXIS, tiled=True)
        src_new = jax.lax.all_gather(src_l, BATCH_AXIS, tiled=True)
        seq_new = jax.lax.all_gather(seq_l, BATCH_AXIS, tiled=True)

        widx = jnp.arange(work, dtype=jnp.int32)
        key_cat = jnp.concatenate([pend_key, key_new], axis=0)  # [W, KW]
        valid = (key_cat != KEY_PAD).any(axis=-1)
        src_f = jnp.where(valid, jnp.concatenate([pend_src, src_new]), 0)
        seq_f = jnp.where(valid, jnp.concatenate([pend_seq, seq_new]), 0)
        prior_clock = jnp.concatenate(
            [pend_clock, jnp.full((batch,), -1, jnp.int32)]
        )
        already_committed = prior_clock >= 0

        # timestamp proposal per replica block (clock ceiling + 1, with
        # within-round same-bucket runs taking consecutive values) — the
        # coordinator's Clock(seq, pid) assignment, computed by every
        # replica from its own clock index (caesar.rs:247-263)
        propose = valid & ~already_committed
        real_slot = valid[:, None] & (key_cat != KEY_PAD)
        propose_slot = propose[:, None] & real_slot
        if sites is None:
            slot_iota = jnp.arange(work * key_width, dtype=jnp.int32).reshape(
                work, key_width
            )
            key_full = jnp.where(propose_slot, key_cat, key_buckets + slot_iota)
            safe_key = jnp.minimum(key_full, key_buckets - 1)
            prior_rows = jnp.where(
                propose_slot[None], key_clock[:, safe_key], 0
            )  # [r_blk, W, KW]
            slot_prop = _segmented_proposal(
                prior_rows.reshape(replica_blocks, work * key_width),
                key_full.reshape(work * key_width),
                work * key_width,
            ).reshape(replica_blocks, work, key_width)
            proposal = jnp.where(
                propose_slot[None], slot_prop, int_min
            ).max(axis=-1)
            proposal = jnp.where(propose[None, :], proposal, 0)  # [r_blk, W]

            # fast path: the whole fast quorum (3n/4 + 1) reports the same
            # timestamp — everyone said ok to the coordinator's proposal
            # (caesar.rs MProposeAck ok=true unanimously)
            row = (
                jax.lax.axis_index(REPLICA_AXIS) * replica_blocks
                + jnp.arange(replica_blocks, dtype=jnp.int32)
            )
            in_fq = (row < fast_quorum)[:, None]
            fq_max = jax.lax.pmax(
                jnp.where(in_fq, proposal, int_min).max(axis=0), REPLICA_AXIS
            )
            fq_min = jax.lax.pmin(
                jnp.where(in_fq, proposal, int_max).min(axis=0), REPLICA_AXIS
            )
            fast = (fq_max == fq_min) & propose

            # MRetry as a second masked aggregation in the same step: the
            # counter-proposal clock is the max over every LIVE replica's
            # proposal, and it commits iff a write quorum (majority) is live
            # to ack it (caesar.rs:367-405 + MRetryAck counting)
            live = (row < live_replicas)[:, None]
            retry_clock = jax.lax.pmax(
                jnp.where(live, proposal, int_min).max(axis=0), REPLICA_AXIS
            )
        else:
            # a coordinator at every site: the working set sorted by key (a
            # carried, committed row stays in its key's run and proposes
            # nothing), every replica's clock at each position, and the
            # rings' answers
            row = (
                jax.lax.axis_index(REPLICA_AXIS) * replica_blocks
                + jnp.arange(replica_blocks, dtype=jnp.int32)
            )
            live = (row < live_replicas)[:, None]
            perm, head, _ = _key_runs(
                jnp.where(valid[:, None], key_cat, key_buckets + widx[:, None])
            )
            key_at = jnp.minimum(key_cat[perm, 0], key_buckets - 1)
            clock_at = jax.lax.all_gather(
                key_clock[:, key_at], REPLICA_AXIS, tiled=True
            )  # [n, W]; a pad's position reads any entry and proposes nothing
            propose_at = propose[perm]
            t0_at, fast_at, t1_at, met_at, rejected_at, occupied_at, passes = (
                _caesar_site_answers(
                    clock_at, head, jnp.cumsum(head.astype(jnp.int32)) - 1,
                    propose_at, already_committed[perm], prior_clock[perm],
                    jnp.mod(src_f - site_base, num_replicas)[perm],
                    src_f[perm], seq_f[perm],
                    jnp.arange(num_replicas) < live_replicas, fast_quorum,
                )
            )
            by_row = jnp.zeros((work, 5), jnp.int32).at[perm].set(
                jnp.stack(
                    [t0_at, fast_at.astype(jnp.int32), t1_at, met_at, rejected_at],
                    axis=-1,
                )
            )
            fq_max, fast, retry_clock = by_row[:, 0], by_row[:, 1] > 0, by_row[:, 2]
        live_count = jax.lax.psum(
            live[:, 0].astype(jnp.int32).sum(), REPLICA_AXIS
        )
        slow_ok = (live_count >= write_quorum) & propose & ~fast
        newly_committed = fast | slow_ok
        committed = already_committed | newly_committed
        clock = jnp.where(
            newly_committed,
            jnp.where(fast, fq_max, retry_clock),
            jnp.where(already_committed, prior_clock, -1),
        )
        slow_paths = (propose & ~fast).sum().astype(jnp.int32)

        # wait-condition-gated execution (the PredecessorsExecutor dense
        # twin): per bucket, committed rows execute in (clock, dot) order
        # up to the first blocked conflict.  Uncommitted rows hold their
        # current (only-growing) counter-proposal clock — blocking
        # higher-clock commits behind them is exactly phase 1's
        # unknown-fate wait, and can only be conservative.
        #
        # Unlike Newt's gate, one pass is NOT enough here: commitment is
        # not clock-monotone per bucket (an uncommitted retry can sit at
        # a LOWER clock than a committed multi-key row), so a committed
        # row held back on one bucket must transitively hold back every
        # higher-(clock, dot) row on its OTHER buckets — a monotone
        # fixpoint over the blocked set (grows only; <= W iterations,
        # typically 1-2).
        order_clock = jnp.where(committed, clock, retry_clock)
        safe_clock = jnp.where(valid, order_clock, int_max)
        order_cd = jnp.lexsort((seq_f, src_f, safe_clock)).astype(jnp.int32)
        rank_of = jnp.zeros((work,), jnp.int32).at[order_cd].set(
            jnp.arange(work, dtype=jnp.int32)
        )
        real_key = jnp.minimum(
            jnp.where(real_slot, key_cat, 0), key_buckets - 1
        )

        def gate_clear(blocked):
            hold = jnp.full((key_buckets,), work, jnp.int32).at[real_key].min(
                jnp.where(
                    blocked[:, None] & real_slot,
                    rank_of[:, None],
                    jnp.int32(work),
                )
            )
            return jnp.where(
                real_slot, rank_of[:, None] < hold[real_key], True
            ).all(axis=-1)

        def gate_body(state):
            blocked, _changed = state
            clear = gate_clear(blocked)
            new_blocked = valid & (~committed | ~clear)
            return new_blocked, (new_blocked & ~blocked).any()

        blocked0 = valid & ~committed
        blocked1, changed0 = gate_body((blocked0, jnp.bool_(True)))
        blocked, _ = jax.lax.while_loop(
            lambda s: s[1], gate_body, (blocked1, changed0)
        )
        clear = gate_clear(blocked)
        executed = committed & valid & clear

        # execution order among the executed: (clock, dot) — timestamp
        # order among conflicts, the executor's contract
        sort_key = jnp.where(executed, clock, int_max)
        order = jnp.lexsort((seq_f, src_f, sort_key)).astype(jnp.int32)

        # clock-index update: live replicas learn every committed
        # timestamp on its buckets (clock_join) and their own consumed
        # proposals — uncommitted proposals occupy the index too, which
        # is what keeps later proposals strictly above them
        # (the key-clock add of caesar.rs:786-838)
        if sites is None:
            learn = jnp.maximum(
                jnp.where(
                    committed[None, :, None] & real_slot[None],
                    clock[None, :, None],
                    0,
                ),
                jnp.where(propose_slot[None], proposal[..., None], 0),
            )  # [r_blk, W, KW]
            upd = jnp.where(live[..., None] & real_slot[None], learn, 0)
            new_key_clock = key_clock.at[:, real_key].max(upd)
        else:
            # ... and with a coordinator at every site what a live replica
            # occupied: the timestamps it met and its counter-proposals
            local = jnp.where(
                live & propose_at[None], occupied_at[row], 0
            )  # [r_blk, W], by sorted position
            learnt = jnp.where(
                live & (committed & valid)[None], clock[None], 0
            )  # [r_blk, W], by row
            new_key_clock = key_clock.at[
                :, jnp.concatenate([key_at, real_key[:, 0]])
            ].max(jnp.concatenate([local, learnt], axis=1))

        # pending carry: committed rows first (their timestamps are
        # final — dropping one would have to re-propose at a different
        # clock, breaking committed order), then uncommitted, working
        # order within each class
        carry = valid & ~executed
        work32 = jnp.int32(work)
        carry_rank = jnp.where(
            carry,
            jnp.where(committed, widx, widx + work32),
            int_max,
        )
        carry_order = jnp.argsort(carry_rank).astype(jnp.int32)
        take = carry_order[:pend_cap]
        is_carry = carry[take]
        new_pend_key = jnp.where(is_carry[:, None], key_cat[take], KEY_PAD)
        new_pend_src = jnp.where(is_carry, src_f[take], -1)
        new_pend_seq = jnp.where(is_carry, seq_f[take], -1)
        new_pend_clock = jnp.where(is_carry, clock[take], -1)
        pending = carry.sum().astype(jnp.int32)
        pend_dropped = jnp.maximum(pending - pend_cap, 0).astype(jnp.int32)

        watermark = jnp.where(executed, clock, 0).max()

        outputs = (
            new_key_clock,
            new_pend_key, new_pend_src, new_pend_seq, new_pend_clock,
            order, executed, committed, fast, clock,
            slow_paths, watermark,
            jnp.minimum(pending, pend_cap), pend_dropped,
            src_f, seq_f,
        )
        if sites is None:
            return outputs
        # what the wait condition and the retry did to the rows committed
        # this round (CAESAR_SITE_ROUND_TALLIES), and the recursion's depth
        met, rejected = by_row[:, 3], by_row[:, 4]
        tallies = jnp.stack(
            [
                (newly_committed & (met > 0)).sum(),
                jnp.where(newly_committed, met, 0).sum(),
                jnp.where(newly_committed, rejected, 0).sum(),
                jnp.where(newly_committed & ~fast, clock - fq_max, 0).sum(),
                passes,
            ]
        ).astype(jnp.int32)
        return outputs + (tallies,)

    specs_in = (
        P(REPLICA_AXIS, None),
        P(), P(), P(), P(),
        P(BATCH_AXIS), P(BATCH_AXIS), P(BATCH_AXIS),
    )
    specs_out = (
        P(REPLICA_AXIS, None),
        P(), P(), P(), P(),
        P(), P(), P(), P(), P(),
        P(), P(), P(), P(),
        P(), P(),  # work identity columns
    ) + (() if sites is None else (P(),))  # the site round's tallies
    fn = shard_map(
        step, mesh=mesh, in_specs=specs_in, out_specs=specs_out, check_vma=False
    )
    out = fn(
        state.key_clock,
        state.pend_key, state.pend_src, state.pend_seq, state.pend_clock,
        key, dot_src, dot_seq,
    )
    n_state = len(CaesarMeshState._fields)
    output = CaesarStepOutput if sites is None else CaesarSiteStepOutput
    return CaesarMeshState(*out[:n_state]), output(*out[n_state:])


def jit_caesar_step(
    mesh: Mesh,
    num_replicas: int | None = None,
    live_replicas: int | None = None,
    sites: int | None = None,
    site_base: int = 1,
):
    """jit-compiled Caesar round with donated device-resident state
    (``sites``: the round with a coordinator at every site,
    :func:`caesar_protocol_step`; the program with one is built without the
    two arguments, as it always was)."""
    import functools

    static = dict(mesh=mesh, num_replicas=num_replicas, live_replicas=live_replicas)
    if sites is not None:
        static.update(sites=sites, site_base=site_base)
    return jax.jit(
        functools.partial(caesar_protocol_step, **static), donate_argnums=(0,)
    )
