"""Property-based tests (hypothesis) — the analog of the reference's
quickcheck CI runs (.github/workflows: QUICKCHECK_TESTS=10000; quickcheck
dev-dependency across fantoch crates).

Targets the algebraic core where randomized inputs bite hardest:

* AboveExSet/AEClock against a plain set model (threshold crate semantics);
* VoteRange compression preserves the voted-integer set
  (fantoch_ps/src/protocol/common/table/votes.rs:133 try_compress);
* the keyed device resolver against the host Tarjan oracle on generated
  latest-per-key graphs with cycles (ops/graph_resolve.py vs
  executor/graph/deps_graph.py);
* dot packing round-trips (ops/frontier.pack_dots);
* the native C++ SCC resolver against the same oracle.
"""

import os
import sys

# the reference CI caps quickcheck at a budget (QUICKCHECK_TESTS); under
# CI=true we shrink hypothesis the same way
_CI = bool(os.environ.get("CI"))


import numpy as np
import pytest

# gate, don't error: containers without hypothesis skip the property
# suite instead of failing collection (the reference's quickcheck dep is
# likewise dev-only)
hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from fantoch_tpu.core.clocks import AboveExSet

# --- AboveExSet vs set model -------------------------------------------------


@settings(max_examples=300 // 4 if _CI else 300, deadline=None)
@given(st.lists(st.integers(min_value=1, max_value=64), max_size=64))
def test_above_ex_set_matches_set_model(events):
    eset = AboveExSet()
    model = set()
    for e in events:
        added = eset.add(e)
        assert added == (e not in model)
        model.add(e)
    for probe in range(1, 70):
        assert eset.contains(probe) == (probe in model), probe
    # frontier: largest f with 1..f all present
    f = 0
    while (f + 1) in model:
        f += 1
    assert eset.frontier == f


@settings(max_examples=200 // 4 if _CI else 200, deadline=None)
@given(
    st.lists(
        st.tuples(
            st.integers(min_value=1, max_value=40),
            st.integers(min_value=0, max_value=8),
        ),
        max_size=30,
    )
)
def test_above_ex_set_add_range_matches_model(ranges):
    eset = AboveExSet()
    model = set()
    for start, width in ranges:
        eset.add_range(start, start + width)
        model.update(range(start, start + width + 1))
    for probe in range(1, 55):
        assert eset.contains(probe) == (probe in model), probe


# --- VoteRange compression ---------------------------------------------------


@settings(max_examples=300 // 4 if _CI else 300, deadline=None)
@given(
    st.lists(
        st.tuples(
            st.integers(min_value=1, max_value=30),
            st.integers(min_value=0, max_value=5),
        ),
        min_size=1,
        max_size=20,
    )
)
def test_vote_range_compression_preserves_votes(ranges):
    from fantoch_tpu.protocol.common.table_clocks import VoteRange

    compressed = []
    model = set()
    for start, width in ranges:
        vr = VoteRange(by=1, start=start, end=start + width)
        model.update(range(start, start + width + 1))
        if compressed and compressed[-1].try_compress(vr):
            pass
        else:
            compressed.append(vr)
    got = set()
    for vr in compressed:
        got.update(range(vr.start, vr.end + 1))
    # compression joins adjacent/overlapping ranges in order; the union of
    # represented votes must never change
    assert got == model


# --- dot packing -------------------------------------------------------------


@settings(max_examples=200 // 4 if _CI else 200, deadline=None)
@given(
    st.lists(
        st.tuples(
            st.integers(min_value=1, max_value=255),
            st.integers(min_value=1, max_value=2**31 - 1),
        ),
        min_size=1,
        max_size=32,
    )
)
def test_pack_dots_roundtrip_and_order(pairs):
    from fantoch_tpu.ops.frontier import pack_dots

    src = np.array([p for p, _ in pairs], dtype=np.int64)
    seq = np.array([q for _, q in pairs], dtype=np.int64)
    packed = pack_dots(src, seq)
    assert ((packed >> 32) == src).all()
    assert ((packed & 0xFFFFFFFF) == seq).all()
    # packing is order-preserving on (src, seq) lexicographic order
    order = np.lexsort((seq, src))
    assert (packed[order] == np.sort(packed)).all()


# --- keyed resolver vs host oracle ------------------------------------------


@st.composite
def functional_graphs(draw):
    """Latest-per-key chains over a few keys, with optional cycles at the
    oldest end — the KeyDeps shape (sequential.rs:8-11)."""
    import random as _random

    from test_ops_resolve import random_functional_args

    seed = draw(st.integers(min_value=0, max_value=2**32 - 1))
    cmds_per_key = draw(st.integers(min_value=1, max_value=7))
    rng = _random.Random(seed)
    return random_functional_args(
        n=3, keys=["A", "B", "C"], cmds_per_key=cmds_per_key, rng=rng
    )


@settings(max_examples=60 // 4 if _CI else 60, deadline=None)
@given(functional_graphs())
@pytest.mark.slow
def test_keyed_resolver_matches_oracle_property(args):
    from test_ops_resolve import assert_keyed_matches_oracle

    assert_keyed_matches_oracle(3, args)


@settings(max_examples=60 // 4 if _CI else 60, deadline=None)
@given(functional_graphs())
def test_native_resolver_matches_oracle_property(args):
    from test_native import csr_from_args
    from test_ops_resolve import oracle_per_key_order

    from fantoch_tpu import native

    if not native.available():
        pytest.skip("native toolchain unavailable")
    offsets, targets, packed = csr_from_args(args)
    order, _sizes = native.resolve_sccs(offsets, targets, packed)
    per_key = {}
    for i in order.tolist():
        dot, keys, _ = args[i]
        for key in keys:
            per_key.setdefault(key, []).append(dot)
    expected, n_exec = oracle_per_key_order(3, args)
    assert len(order) == n_exec
    assert per_key == expected


# --- sharded Newt mesh round properties ---

_SHARDED_NEWT = {}


def _sharded_newt_step():
    """One jitted 2-shard Newt step + mesh, built once: hypothesis
    examples reuse the compiled program (fixed shapes)."""
    if not _SHARDED_NEWT:
        from fantoch_tpu.parallel import mesh_step

        m = mesh_step.make_mesh(num_replicas=6)
        _SHARDED_NEWT["mesh_step"] = mesh_step
        _SHARDED_NEWT["mesh"] = m
        _SHARDED_NEWT["step"] = mesh_step.jit_newt_step(m, f=1, shard_count=2)
    return _SHARDED_NEWT["mesh_step"], _SHARDED_NEWT["mesh"], _SHARDED_NEWT["step"]


@settings(max_examples=25 // 5 if _CI else 25, deadline=None)
@given(
    st.lists(
        st.one_of(
            st.none(),  # pad row
            st.integers(min_value=0, max_value=7),  # single bucket
            st.tuples(
                st.integers(min_value=0, max_value=7),
                st.integers(min_value=0, max_value=7),
            ).filter(lambda t: t[0] != t[1]),  # two distinct buckets
        ),
        min_size=8,
        max_size=8,
    )
)
@pytest.mark.slow
def test_sharded_newt_round_properties(rows):
    """Random single/multi-bucket batches through one healthy 2-shard
    Newt round: every valid row fast-commits and executes, per-bucket
    execution follows strictly increasing (clock, dot) sort ids — the
    VotesTable contract; multi-key rows may tie on clock within a round
    and break by dot (newt_protocol_step docstring) — and a shard's
    replicas never learn the other shard's buckets."""
    import jax
    import jax.numpy as jnp

    mesh_step, _m, step = _sharded_newt_step()
    KP = mesh_step.KEY_PAD
    state = mesh_step.init_newt_state(
        _SHARDED_NEWT["mesh"], 6, key_buckets=8, pending_capacity=8,
        key_width=2,
    )
    key = np.full((8, 2), KP, np.int32)
    for i, row in enumerate(rows):
        if row is None:
            continue
        if isinstance(row, tuple):
            key[i, 0], key[i, 1] = row
        else:
            key[i, 0] = row
    state, out = step(
        state, jnp.asarray(key), jnp.ones((8,), jnp.int32),
        jnp.arange(8, dtype=jnp.int32),
    )
    pend_cap = state.pend_key.shape[0]
    valid = [i for i, r in enumerate(rows) if r is not None]
    executed = np.asarray(out.executed)
    fast = np.asarray(out.fast_path)
    clock = np.asarray(out.clock)
    for i in valid:
        assert executed[pend_cap + i] and fast[pend_cap + i], f"row {i}"

    # per-bucket (clock, dot) sort ids strictly increase along the
    # execution order (clock alone may tie for multi-key rows in one
    # round; dot breaks the tie — the VotesTable SortId contract)
    order = np.asarray(out.order)
    last = {}
    for w in order.tolist():
        if not executed[w] or w < pend_cap:
            continue
        i = w - pend_cap
        row = rows[i]
        buckets = row if isinstance(row, tuple) else (row,)
        sort_id = (int(clock[w]), i)  # dot = (1, seq=i): seq orders
        for b in buckets:
            assert last.get(b, (0, -1)) < sort_id, (
                f"bucket {b}: {last.get(b)} !< {sort_id}"
            )
            last[b] = sort_id

    # ownership: shard 0 = rows 0..2 owns even buckets, shard 1 odd
    kc = np.asarray(state.key_clock)
    vf = np.asarray(state.vote_frontier)
    odd = np.arange(1, 8, 2)
    even = np.arange(0, 8, 2)
    assert (kc[0:3][:, odd] == 0).all() and (vf[0:3][:, odd] == 0).all()
    assert (kc[3:6][:, even] == 0).all() and (vf[3:6][:, even] == 0).all()


# --- the dep-commit round's invariant: the clock holds executed gids only ---

_EPAXOS_STEPS = {}


def _epaxos_steps():
    """The healthy and the degraded dep-commit round of one mesh, compiled
    once (as a driver's ``_programs[1]``): hypothesis examples reuse the
    programs."""
    if not _EPAXOS_STEPS:
        from fantoch_tpu.parallel import mesh_step
        from fantoch_tpu.run.device_runner import DeviceDriver

        probe = DeviceDriver(3, batch_size=8, key_buckets=16, pending_capacity=8)
        _EPAXOS_STEPS["mesh"] = probe._mesh
        _EPAXOS_STEPS[True] = probe._program()
        _EPAXOS_STEPS[False] = probe._precompile(
            mesh_step.jit_protocol_step(probe._mesh, live_replicas=1))
    return _EPAXOS_STEPS


@settings(max_examples=30 // 5 if _CI else 30, deadline=None)
@given(
    st.lists(
        st.tuples(
            # all replicas live, or (twice as often) one of three: below the write quorum
            st.sampled_from((True, False, False)),
            st.lists(st.integers(min_value=0, max_value=3), min_size=1, max_size=8),
        ),
        min_size=4,
        max_size=9,
    ),
    st.integers(min_value=1, max_value=5),
)
def test_the_key_clock_never_holds_a_gid_of_the_working_set(rounds, reset_before):
    """Step 4 of the one-key round reads a working-row dependency off the
    intra-batch chain alone, which is sound only while no key-clock entry
    is the gid of a carried or a new row.  Over sequences of healthy and
    degraded rounds (rows miss the fast path, fail the accept round and
    carry), with a ``_gid_epoch_reset`` in the middle: after
    every round, and after the reset, every clock entry is below
    ``next_gid`` and none is a pending gid; and every command is executed
    exactly once by the end."""
    from fantoch_tpu.core.command import Command
    from fantoch_tpu.core.ids import Dot, Rifl
    from fantoch_tpu.core.kvs import KVOp
    from fantoch_tpu.run.device_runner import DeviceDriver

    steps = _epaxos_steps()
    d = DeviceDriver(
        3, batch_size=8, key_buckets=16, pending_capacity=8, mesh=steps["mesh"],
        monitor_execution_order=True,
    )

    def holds(where):
        st_ = d._state
        clock = np.asarray(st_.key_clock)
        pending = np.asarray(st_.pend_gid)
        pending = set(pending[pending >= 0].tolist())
        assert clock.max() < int(st_.next_gid), where
        assert not pending & set(clock[clock >= 0].tolist()), where
        assert pending == set(d._cmds), where  # the registry mirrors the device's carry

    seq, done, backlog = 0, 0, []

    def step(batch, where):
        # what overflowed the device's pending buffer comes back through the
        # requeue and goes in again ahead of the new commands
        nonlocal done, backlog
        backlog = d.take_requeue() + backlog + batch
        batch, backlog = backlog[:8], backlog[8:]
        done += len(d.step(batch))
        holds(where)

    reset = False
    for at, (healthy, keys) in enumerate(rounds):
        # the reset: at the first round boundary from ``reset_before`` on with
        # rows carried (their gids, the registry and the clock rebase
        # together), else before the last round
        if not reset and at >= reset_before and (d._cmds or at == len(rounds) - 1):
            if min(d._cmds, default=d._next_gid) > 0:
                d._gid_epoch_reset()
                holds(f"after the reset before round {at}")
                reset = True
        d._programs[1] = steps[healthy]
        batch = []
        for key in keys:
            seq += 1
            batch.append((Dot(1, seq), Command.from_single(Rifl(1, seq), 0, f"k{key}", KVOp.put(str(seq)))))
        step(batch, f"after round {at}")
    d._programs[1] = steps[True]
    for _ in range(16):  # all live again: what was carried or requeued drains
        if not (d.in_flight or backlog or d.has_requeue):
            break
        step([], "while draining")
    assert d.in_flight == 0 and not backlog and done == seq == d.executed
