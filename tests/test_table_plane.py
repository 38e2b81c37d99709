"""Device-resident votes-table plane: the fused commit kernel
(ops/table_ops.fused_votes_commit), the resident frontier state
(executor/table_plane.DeviceTablePlane), the executor wired through it
(Config.device_table_plane), the resident clock-proposal table
(table_batched.BatchedKeyClocks over resident_clock_proposal), the fused
all-device round chain (fused_table_round/fused_table_rounds), and the
chained Newt serving dispatch (NewtDeviceDriver.serve of a chain) — each
oracle-checked bit-for-bit against the per-command host twins.
"""

import os
import pickle
import random

import numpy as np
import pytest

from fantoch_tpu.core import Command, Config, Dot, KVOp, Rifl, RunTime
from fantoch_tpu.core.clocks import RangeEventSet
from fantoch_tpu.executor.table import (
    TableDetachedVotes,
    TableExecutor,
    TableVotes,
    TableVotesArrays,
    TableVotesArraysBuilder,
)
from fantoch_tpu.executor.table_plane import ClockOverflowError, DeviceTablePlane
from fantoch_tpu.protocol.common.table_clocks import VoteRange

SHARD = 0


# ---------------------------------------------------------------------------
# the fused commit kernel vs the RangeEventSet frontier oracle
# ---------------------------------------------------------------------------


def oracle_frontiers(n_keys, n, applied):
    """Replay (key, by, start, end) votes through RangeEventSets and
    return the frontier matrix (by is 0-based here)."""
    sets = [[RangeEventSet() for _ in range(n)] for _ in range(n_keys)]
    for k, by, s, e in applied:
        sets[k][by].add_range(s, e)
    return np.array(
        [[sets[k][p].frontier for p in range(n)] for k in range(n_keys)],
        dtype=np.int64,
    )


def test_device_plane_matches_range_event_sets():
    """Random overlapping/adjacent/gapped vote ranges over several
    batches: the plane's resident frontiers equal the RangeEventSet
    oracle after every batch once its residual buffer has had the same
    votes (exactness contract: residuals re-feed until gaps fill)."""
    rng = random.Random(5)
    n, n_keys = 3, 8
    plane = DeviceTablePlane(n, stability_threshold=2, key_buckets=8)
    for k in range(n_keys):
        plane.bucket(f"k{k}")
    applied = []
    for _batch in range(12):
        vk, vb, vs, ve = [], [], [], []
        for _ in range(rng.randrange(1, 12)):
            k = rng.randrange(n_keys)
            by = rng.randrange(1, n + 1)
            s = rng.randrange(1, 25)
            e = s + rng.randrange(6)
            vk.append(k)
            vb.append(by)
            vs.append(s)
            ve.append(e)
            applied.append((k, by - 1, s, e))
        stable = plane.commit_votes(
            np.array(vk, np.int64), np.array(vb, np.int64),
            np.array(vs, np.int64), np.array(ve, np.int64),
        )
        oracle = oracle_frontiers(n_keys, n, applied)
        # a plane frontier may lag the oracle only where a residual run
        # is still buffered; with ranges drawn from [1, 31) every gap
        # eventually fills, so drive empty batches until residuals drain
        spins = 0
        while plane.residual_count and spins < 8:
            stable = plane.commit_votes(
                np.empty(0, np.int64), np.empty(0, np.int64),
                np.empty(0, np.int64), np.empty(0, np.int64),
            )
            spins += 1
        got = plane.frontiers()
        lag = got < oracle
        if lag.any():
            # residual runs that STILL start beyond a real gap: the
            # oracle's RangeEventSet also has not merged them into the
            # frontier (frontier = contiguous prefix only) — so the
            # frontiers must already agree; anything else is a bug
            assert (got == oracle).all(), f"plane lost votes:\n{got}\n{oracle}"
        assert (got <= oracle).all(), "plane frontier overtook the oracle"
        col = n - 2
        expect_stable = np.sort(oracle, axis=1)[:, col]
        assert (stable == expect_stable).all()


def test_device_plane_residual_gap_fill():
    """A beyond-gap run buffers as residual and lands exactly when the
    gap fills — the RangeEventSet add/merge sequence, replayed across
    dispatches."""
    plane = DeviceTablePlane(3, stability_threshold=2, key_buckets=4)
    b = plane.bucket("x")
    one = lambda s, e: (  # noqa: E731 — single-vote batch helper
        np.array([b], np.int64), np.array([1], np.int64),
        np.array([s], np.int64), np.array([e], np.int64),
    )
    plane.commit_votes(*one(5, 9))  # beyond the gap [1,4]
    assert plane.residual_count == 1
    assert plane.frontiers()[0].tolist() == [0, 0, 0]
    plane.commit_votes(*one(1, 4))  # fills the gap; residual coalesces
    assert plane.residual_count == 0
    assert plane.frontiers()[0].tolist() == [9, 0, 0]


def test_device_plane_bucket_growth_preserves_state():
    plane = DeviceTablePlane(3, stability_threshold=2, key_buckets=2)
    a = plane.bucket("a")
    plane.commit_votes(
        np.array([a], np.int64), np.array([1], np.int64),
        np.array([1], np.int64), np.array([4], np.int64),
    )
    for i in range(10):  # force capacity doublings past the resident state
        plane.bucket(f"grow{i}")
    assert plane.grows >= 2
    assert plane.frontiers()[a].tolist() == [4, 0, 0]


def test_device_plane_clock_overflow_rejected():
    plane = DeviceTablePlane(3, stability_threshold=2)
    b = plane.bucket("x")
    with pytest.raises(ClockOverflowError):
        plane.commit_votes(
            np.array([b], np.int64), np.array([1], np.int64),
            np.array([1], np.int64), np.array([1 << 31], np.int64),
        )


def test_config_rejects_plane_with_realtime_clocks():
    with pytest.raises(ValueError, match="device_table_plane"):
        Config(
            3, 1, device_table_plane=True, newt_clock_bump_interval_ms=10
        )


# ---------------------------------------------------------------------------
# satellite: the kernel-threshold knob (Config + env override) and the
# kernel/partition agreement it arbitrates
# ---------------------------------------------------------------------------


def test_kernel_threshold_config_knob_and_env(monkeypatch):
    base = Config(3, 1)
    explicit = Config(3, 1, table_kernel_threshold=123)
    assert TableExecutor(1, SHARD, base)._kernel_threshold == (1 << 20)
    assert TableExecutor(1, SHARD, explicit)._kernel_threshold == 123
    # the field is the one home: the environment moves neither
    monkeypatch.setenv("FANTOCH_TABLE_KERNEL_THRESHOLD", "77")
    assert TableExecutor(1, SHARD, base)._kernel_threshold == (1 << 20)
    assert TableExecutor(1, SHARD, explicit)._kernel_threshold == 123


def test_kernel_threshold_routes_both_branches_and_they_agree(monkeypatch):
    """threshold=1 routes _stable_clocks through the device kernel,
    a huge threshold through np.partition — same clocks either way."""
    rng = np.random.default_rng(3)
    frontiers = rng.integers(0, 1 << 20, size=(64, 5))
    kernel_cfg = Config(5, 1, table_kernel_threshold=1)
    host_cfg = Config(5, 1, table_kernel_threshold=1 << 60)
    ex_k = TableExecutor(1, SHARD, kernel_cfg)
    ex_h = TableExecutor(1, SHARD, host_cfg)
    col = 5 - ex_k._stability_threshold
    expected = np.sort(frontiers, axis=1)[:, col]
    assert (ex_k._stable_clocks(frontiers) == expected).all()
    assert (ex_h._stable_clocks(frontiers) == expected).all()


# ---------------------------------------------------------------------------
# satellite: randomized oracle equivalence across ALL four executor
# feeds — handle / handle_batch / handle_batch_arrays / device plane —
# covering execute_at_commit, TableDetachedVotes, non-contiguous ranges
# ---------------------------------------------------------------------------


def _random_rounds(rng, n, n_rounds=10):
    """Rounds of protocol-consistent infos: per-key consecutive clocks,
    the coordinator voting its consumed range, peers voting full,
    partial, gapped (non-contiguous), or no prefixes, plus occasional
    detached votes; a final all-votes flush stabilizes everything."""
    key_clock = {}
    seq = 1
    rounds = []
    for _ in range(n_rounds):
        infos = []
        for _ in range(rng.randrange(1, 12)):
            key = f"k{rng.randrange(4)}"
            c = key_clock.get(key, 0) + 1
            key_clock[key] = c
            votes = [VoteRange(1, c, c)]
            for p in range(2, n + 1):
                kind = rng.randrange(4)
                if kind == 0:
                    votes.append(VoteRange(p, 1, c))
                elif kind == 1 and c > 2:
                    votes.append(VoteRange(p, 2, c))  # gap below: residual
                elif kind == 2 and c > 1:
                    votes.append(VoteRange(p, 1, c - 1))
            infos.append(
                TableVotes(
                    Dot(1, seq), c, Rifl(1, seq), key,
                    (KVOp.put(f"v{seq}"),), votes,
                )
            )
            seq += 1
        if rng.randrange(3) == 0 and key_clock:
            key = rng.choice(sorted(key_clock))
            up = key_clock[key]
            infos.append(
                TableDetachedVotes(
                    key, [VoteRange(p, 1, up) for p in range(2, n + 1)]
                )
            )
        rounds.append(infos)
    flush = [
        TableDetachedVotes(k, [VoteRange(p, 1, c) for p in range(1, n + 1)])
        for k, c in sorted(key_clock.items())
    ]
    rounds.append(flush)
    return rounds


def _infos_to_arrays(infos):
    builder = TableVotesArraysBuilder()
    for info in infos:
        if isinstance(info, TableVotes):
            builder.add_row(
                info.dot, info.clock, info.rifl, info.key, info.ops,
                info.votes,
            )
        else:
            builder.add_detached(info.key, info.votes)
    return builder.take()


def _drain_per_key(ex):
    out = {}
    while (r := ex.to_clients()) is not None:
        out.setdefault(r.key, []).append((r.rifl, r.op_results))
    return out


@pytest.mark.parametrize("execute_at_commit", [False, True])
def test_four_feed_oracle_equivalence(execute_at_commit):
    """handle vs handle_batch vs handle_batch_arrays vs the device plane
    produce identical per-key executions and identical KVStore state on
    randomized rounds with detached votes and non-contiguous ranges."""
    rng = random.Random(11)
    n = 3
    time = RunTime()
    rounds = _random_rounds(rng, n)

    def build(batched, plane):
        return TableExecutor(
            1, SHARD,
            Config(
                n, 1,
                batched_table_executor=batched,
                device_table_plane=plane,
                execute_at_commit=execute_at_commit,
            ),
        )

    ex_handle = build(False, False)
    ex_batch = build(True, False)
    ex_arrays = build(True, False)
    ex_plane = build(True, True)
    results = {}
    executions = {}
    for name, ex in (
        ("handle", ex_handle), ("batch", ex_batch),
        ("arrays", ex_arrays), ("plane", ex_plane),
    ):
        per_key = {}
        for infos in rounds:
            if name == "handle":
                for info in infos:
                    ex.handle(info, time)
            elif name == "batch":
                ex.handle_batch(list(infos), time)
            else:
                arrays = _infos_to_arrays(infos)
                if arrays is not None:
                    ex.handle_batch_arrays(arrays, time)
            for key, rows in _drain_per_key(ex).items():
                per_key.setdefault(key, []).extend(rows)
        results[name] = ex._store._store
        executions[name] = per_key
    for name in ("batch", "arrays", "plane"):
        assert executions[name] == executions["handle"], (
            f"{name} diverged from the per-info oracle "
            f"(execute_at_commit={execute_at_commit})"
        )
        assert results[name] == results["handle"]


def test_plane_handles_mixed_info_stream():
    """A mixed stream (objects + pre-built arrays batches) through
    handle_batch on a plane executor equals the per-info oracle — the
    _as_arrays_batches funnel preserves relative order."""
    rng = random.Random(23)
    n = 3
    time = RunTime()
    rounds = _random_rounds(rng, n, n_rounds=6)
    ex_plane = TableExecutor(
        1, SHARD, Config(n, 1, batched_table_executor=True,
                         device_table_plane=True),
    )
    ex_oracle = TableExecutor(1, SHARD, Config(n, 1))
    got, want = {}, {}
    for r, infos in enumerate(rounds):
        if r % 2 == 0 and len(infos) > 1:
            half = len(infos) // 2
            mixed = list(infos[:half])
            arrays = _infos_to_arrays(infos[half:])
            if arrays is not None:
                mixed.append(arrays)
        else:
            mixed = list(infos)
        ex_plane.handle_batch(mixed, time)
        for info in infos:
            ex_oracle.handle(info, time)
        for key, rows in _drain_per_key(ex_plane).items():
            got.setdefault(key, []).extend(rows)
        for key, rows in _drain_per_key(ex_oracle).items():
            want.setdefault(key, []).extend(rows)
    assert got == want
    assert ex_plane._store._store == ex_oracle._store._store


# ---------------------------------------------------------------------------
# the resident clock-proposal table
# ---------------------------------------------------------------------------


def test_resident_proposal_interleaves_with_scalar_access():
    """proposal_batch_arrays keeps the clock table on device; scalar
    proposal/detached_all calls in between must see (and mutate) live
    clocks — parity against the sequential twin across the interleaving,
    plus a pickle round-trip mid-stream (device buffers must not leak
    into snapshots)."""
    from fantoch_tpu.protocol.common.table_batched import BatchedKeyClocks
    from fantoch_tpu.protocol.common.table_clocks import (
        SequentialKeyClocks,
        Votes,
    )

    rng = random.Random(2)
    bat = BatchedKeyClocks(1, SHARD)
    seq = SequentialKeyClocks(1, SHARD)
    next_id = 0
    for round_ in range(6):
        keys = [f"k{rng.randrange(5)}" for _ in range(rng.randrange(1, 30))]
        mins = [rng.randrange(0, 10) for _ in keys]
        clock_col, start_col = bat.proposal_batch_arrays(keys, mins)
        for i, key in enumerate(keys):
            cmd = Command.from_single(
                Rifl(1, next_id + 1), SHARD, key, KVOp.put("")
            )
            next_id += 1
            c, votes = seq.proposal(cmd, mins[i])
            assert c == int(clock_col[i])
            ((_k, ranges),) = list(votes)
            assert (ranges[0].start, ranges[0].end) == (
                int(start_col[i]), int(clock_col[i]),
            )
        if round_ == 2:
            bat = pickle.loads(pickle.dumps(bat))  # snapshot mid-stream
        # scalar interleave: a detached_all sweep on both sides
        up = 20 * (round_ + 1)
        vb, vs = Votes(), Votes()
        bat.detached_all(up, vb)
        seq.detached_all(up, vs)
        as_dict = lambda v: {  # noqa: E731
            k: [(r.by, r.start, r.end) for r in rs] for k, rs in v
        }
        assert as_dict(vb) == as_dict(vs)


def test_resident_rebuild_does_not_leak_pad_bucket_clock():
    """Regression: when the key registry outgrows the device table, the
    rebuild must NOT copy the old pad bucket's accumulated clock into
    the key that now occupies that index — its proposal would be
    inflated and this process's vote frontier would gain a permanent
    gap.  (Found by review: two calls on a fresh instance sufficed.)"""
    from fantoch_tpu.protocol.common.table_batched import BatchedKeyClocks
    from fantoch_tpu.protocol.common.table_clocks import SequentialKeyClocks

    bat = BatchedKeyClocks(1, SHARD)
    seq = SequentialKeyClocks(1, SHARD)
    rounds = [
        (["k2", "k0", "k1", "k0", "k0"], [1, 3, 0, 0, 3]),
        (["k4"], [0]),  # k4 lands on the old device table's pad slot
        (["k4", "k3", "k5", "k4"], [0, 2, 0, 0]),
    ]
    next_id = 0
    for keys, mins in rounds:
        clock_col, start_col = bat.proposal_batch_arrays(keys, mins)
        for i, key in enumerate(keys):
            cmd = Command.from_single(
                Rifl(1, next_id + 1), SHARD, key, KVOp.put("")
            )
            next_id += 1
            c, votes = seq.proposal(cmd, mins[i])
            assert c == int(clock_col[i]), (key, c, int(clock_col[i]))
            ((_k, ranges),) = list(votes)
            assert (ranges[0].start, ranges[0].end) == (
                int(start_col[i]), int(clock_col[i]),
            )


def test_resident_window_bound_drift_recovers_without_fallback():
    """The incrementally-grown window bound (+bcap per resident batch)
    eventually trips the guard even with tiny real clocks; materializing
    tightens it and the kernel path must continue — no sequential
    fallback, no wrong clocks."""
    from fantoch_tpu.protocol.common import table_batched
    from fantoch_tpu.protocol.common.table_batched import BatchedKeyClocks

    bat = BatchedKeyClocks(1, SHARD)
    out = bat.proposal_batch_arrays(["a", "b"], [0, 0])
    assert out is not None
    bat._host_max = table_batched._INT32_MAX - 1  # simulate long drift
    out = bat.proposal_batch_arrays(["a", "b"], [0, 0])
    assert out is not None, "tightened bound must keep the kernel path"
    assert out[0].tolist() == [2, 2]
    assert bat._host_max < 1 << 20  # bound reset to reality


def test_resident_proposal_window_overflow_falls_back():
    from fantoch_tpu.protocol.common.table_batched import BatchedKeyClocks

    bat = BatchedKeyClocks(1, SHARD)
    assert bat.proposal_batch_arrays(["a"], [5]) is not None
    # a min clock near the 31-bit cap forces the sequential fallback
    assert bat.proposal_batch_arrays(["a"], [(1 << 31) - 2]) is None
    # the host mirror was materialized before the fallback: scalar path
    # continues from the device-computed clock
    cmd = Command.from_single(Rifl(1, 1), SHARD, "a", KVOp.put(""))
    clock, _ = bat.proposal(cmd, 0)
    assert clock == 6


def test_resident_buffers_never_alias_host_numpy(monkeypatch):
    """Regression: buffers handed to the DONATED argnums of the resident
    kernels must be XLA-owned copies.  On the CPU backend
    jnp.asarray/device_put zero-copy alias numpy memory, and donating the
    alias hands numpy-owned memory to XLA — nondeterministic wrong
    clocks and heap corruption (glibc aborts under the persistent
    compile cache).  Spy on np.zeros to capture every host staging
    buffer the rebuilds allocate and assert the resident device arrays
    share memory with none of them."""
    from fantoch_tpu.protocol.common.table_batched import BatchedKeyClocks

    made = []
    orig_zeros = np.zeros

    def spy_zeros(*args, **kwargs):
        arr = orig_zeros(*args, **kwargs)
        made.append(arr)
        return arr

    monkeypatch.setattr(np, "zeros", spy_zeros)

    bat = BatchedKeyClocks(1, SHARD)
    assert bat.proposal_batch_arrays(["a", "b"], [0, 0]) is not None
    dev_prior = np.asarray(bat._dev_prior)
    assert not any(
        m.size and np.shares_memory(dev_prior, m) for m in made
    ), "resident clock table aliases a host numpy buffer (donation UAF)"

    made.clear()
    plane = DeviceTablePlane(3, 2, key_buckets=2)
    plane.commit_votes(
        np.array([plane.bucket("a")], dtype=np.int64),
        np.array([1], dtype=np.int64),
        np.array([1], dtype=np.int64),
        np.array([1], dtype=np.int64),
    )
    for i in range(4):  # outgrow cap=2: _grow re-stages via np.zeros
        plane.bucket(f"g{i}")
    assert plane.grows >= 1
    frontier = np.asarray(plane._frontier)
    assert not any(
        m.size and np.shares_memory(frontier, m) for m in made
    ), "resident frontier matrix aliases a host numpy buffer (donation UAF)"


# ---------------------------------------------------------------------------
# the fused all-device round chain
# ---------------------------------------------------------------------------


def test_fused_table_round_matches_host_twins():
    """fused_table_round (proposal + dense votes + stability in ONE
    dispatch) assigns the clocks the proposal kernel assigns and the
    stability the RangeEventSet oracle derives, round after round on
    donated state."""
    import jax.numpy as jnp

    from fantoch_tpu.ops.table_ops import fused_table_round
    from fantoch_tpu.protocol.common.table_batched import BatchedKeyClocks

    rng = np.random.default_rng(7)
    n, kcap, batch = 3, 16, 32
    threshold = Config(n, 1).newt_quorum_sizes()[2]
    prior = jnp.zeros((kcap,), jnp.int32)
    frontier = jnp.zeros((kcap, n), jnp.int32)
    clocks = BatchedKeyClocks(1, SHARD)
    sets = [[RangeEventSet() for _ in range(n)] for _ in range(kcap)]
    for _round in range(5):
        key_np = rng.integers(0, kcap - 1, size=batch).astype(np.int32)
        mins_np = rng.integers(0, 5, size=batch).astype(np.int32)
        prior, frontier, clock, vote_start, executable, gaps = (
            fused_table_round(
                prior, frontier, jnp.asarray(key_np), jnp.asarray(mins_np),
                threshold=threshold, voters=n,
            )
        )
        assert int(gaps) == 0  # dense regime: every voter contiguous
        key_strs = [f"k{k}" for k in key_np]
        expect_clock, expect_start = clocks.proposal_batch_arrays(
            key_strs, mins_np.tolist()
        )
        assert np.asarray(clock).tolist() == expect_clock.tolist()
        assert np.asarray(vote_start).tolist() == expect_start.tolist()
        # oracle stability: every process votes every consumed range
        for i in range(batch):
            for p in range(n):
                sets[key_np[i]][p].add_range(
                    int(expect_start[i]), int(expect_clock[i])
                )
        stable = np.array(
            [
                sorted(es.frontier for es in row)[n - threshold]
                for row in sets
            ],
            dtype=np.int64,
        )
        assert bool(np.asarray(executable).all()) == bool(
            (np.asarray(clock) <= stable[key_np]).all()
        )
        assert (np.asarray(executable) == (np.asarray(clock) <= stable[key_np])).all()


def test_fused_table_rounds_chain_equals_single_rounds():
    """S chained rounds in one dispatch == S sequential fused rounds."""
    import jax.numpy as jnp

    from fantoch_tpu.ops.table_ops import fused_table_round, fused_table_rounds

    rng = np.random.default_rng(13)
    n, kcap, batch, S = 3, 8, 16, 4
    threshold = Config(n, 1).newt_quorum_sizes()[2]
    keys_np = rng.integers(0, kcap - 1, size=(S, batch)).astype(np.int32)
    mins_np = rng.integers(0, 4, size=(S, batch)).astype(np.int32)

    prior_c, frontier_c, clock_c, start_c, exec_c, gaps_c = fused_table_rounds(
        jnp.zeros((kcap,), jnp.int32), jnp.zeros((kcap, n), jnp.int32),
        jnp.asarray(keys_np), jnp.asarray(mins_np),
        threshold=threshold, voters=n,
    )
    prior = jnp.zeros((kcap,), jnp.int32)
    frontier = jnp.zeros((kcap, n), jnp.int32)
    for r in range(S):
        prior, frontier, clock, start, execu, gaps = fused_table_round(
            prior, frontier, jnp.asarray(keys_np[r]), jnp.asarray(mins_np[r]),
            threshold=threshold, voters=n,
        )
        assert np.asarray(clock_c)[r].tolist() == np.asarray(clock).tolist()
        assert np.asarray(start_c)[r].tolist() == np.asarray(start).tolist()
        assert np.asarray(exec_c)[r].tolist() == np.asarray(execu).tolist()
    assert np.asarray(prior_c).tolist() == np.asarray(prior).tolist()
    assert np.asarray(frontier_c).tolist() == np.asarray(frontier).tolist()


# ---------------------------------------------------------------------------
# Newt end-to-end: the commit-arrays seam and the chained serving dispatch
# ---------------------------------------------------------------------------


def test_newt_set_commit_arrays_flushes_pending():
    from fantoch_tpu.protocol import Newt

    config = Config(
        3, 1, batched_table_executor=True, newt_detached_send_interval_ms=5
    )
    newt = Newt(1, SHARD, config)
    assert newt._commit_arrays is not None
    newt._commit_arrays.add_detached("x", [VoteRange(1, 1, 3)])
    newt.set_commit_arrays(False)  # multi-executor pools route per key
    assert newt._commit_arrays is None
    flushed = newt.to_executors()
    assert isinstance(flushed, TableVotesArrays)
    assert flushed.det_keys == ["x"]
    assert newt.to_executors() is None


@pytest.mark.parametrize("plane", [False, True])
def test_sim_newt_plane_matches_sequential(plane):
    from harness import sim_test

    from fantoch_tpu.protocol import Newt

    def cfg(batched, use_plane=False):
        return Config(
            n=3, f=1, newt_detached_send_interval_ms=100,
            batched_table_executor=batched,
            device_table_plane=use_plane,
        )

    assert sim_test(Newt, cfg(True, plane), seed=3, keys_per_command=1) == (
        sim_test(Newt, cfg(False), seed=3, keys_per_command=1)
    )


def test_newt_driver_step_chained_matches_sequential_steps():
    """S rounds through ONE chained dispatch == S sequential step()
    rounds: same execution order, same KVStore."""
    from fantoch_tpu.run.device_runner import NewtDeviceDriver

    rng = np.random.default_rng(5)
    B, rounds_n = 16, 6
    keys = rng.integers(0, 24, size=B * rounds_n)
    cmds = [
        (
            Dot(1, i + 1),
            Command.from_single(
                Rifl(1, i + 1), SHARD, f"c{keys[i]}", KVOp.put(f"v{i}")
            ),
        )
        for i in range(B * rounds_n)
    ]
    batches = [cmds[r * B : (r + 1) * B] for r in range(rounds_n)]

    seq_driver = NewtDeviceDriver(3, batch_size=B, key_buckets=64)
    seq_results = []
    for batch in batches:
        seq_results.extend(seq_driver.step(batch))

    chain_driver = NewtDeviceDriver(3, batch_size=B, key_buckets=64)
    chained = chain_driver.serve(batches[:3])
    chained += chain_driver.serve(batches[3:])

    assert [(r.rifl, r.key) for r in chained] == [
        (r.rifl, r.key) for r in seq_results
    ]
    assert chain_driver.store._store == seq_driver.store._store
    assert chain_driver.rounds == seq_driver.rounds
