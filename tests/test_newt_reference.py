"""The device Newt round against the plain reference of the mechanism
(``tests/newt_reference.py``): 4 shards x 5 replica rows, two keys a
command, pads and repeats of hot keys, on forced-host meshes ``1x1``,
``2x2`` and ``4x1`` (one shard a device), through the round itself and
through every chain length of the tuner's ladder.  Clocks, commit and
fast-path flags, what executed and in which order, what is carried: equal,
exactly, round by round; and the tables at the end."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from fantoch_tpu.parallel import mesh_step
from tests.newt_reference import PAD, NewtReference

N, SHARDS, BUCKETS, CAPACITY, BATCH, WIDTH = 5, 4, 64, 8, 16, 2
MESHES = {"1x1": (1, 1), "2x2": (2, 2), "4x1": (4, 1)}
LADDER = (1, 2, 4, 8)  # ChainAutoTuner's, at the default --serving-chain-max


def forced_mesh(shape):
    replica, batch = shape
    devices = np.array(jax.devices()[: replica * batch]).reshape(replica, batch)
    return Mesh(devices, (mesh_step.REPLICA_AXIS, mesh_step.BATCH_AXIS))


def batches(rng, rounds, first_seq=0):
    """Seeded rounds of commands: half of the keys from six hot buckets, a
    fifth of the commands over one key, a seventh of the rows empty."""
    hot = rng.choice(BUCKETS, size=6, replace=False)
    keys = np.full((rounds, BATCH, WIDTH), PAD, np.int32)
    for r in range(rounds):
        for row in range(BATCH):
            if rng.random() < 0.15:
                continue
            pool = hot if rng.random() < 0.5 else np.arange(BUCKETS)
            first = rng.choice(pool)
            keys[r, row, 0] = first
            if rng.random() < 0.8:
                second = rng.choice(np.setdiff1d(pool, [first]))
                keys[r, row, 1] = second
    srcs = rng.integers(1, 40, size=(rounds, BATCH)).astype(np.int32)
    seqs = (first_seq + np.arange(rounds * BATCH)).reshape(rounds, BATCH).astype(np.int32)
    return keys, srcs, seqs


def assert_round_equal(out, want, at):
    executed = np.asarray(out.executed)
    for name, got, expected in (
        ("clock", out.clock, want.clock), ("committed", out.committed, want.committed),
        ("fast_path", out.fast_path, want.fast_path), ("executed", executed, want.executed),
        ("order", np.asarray(out.order)[: int(executed.sum())], want.order),
        ("slow_paths", out.slow_paths, want.slow_paths), ("pending", out.pending, want.pending),
        ("pend_dropped", out.pend_dropped, want.dropped),
        ("stable_watermark", out.stable_watermark, want.watermark),
    ):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(expected),
                                      err_msg=f"{name}, round {at}")


def assert_state_equal(state, reference):
    np.testing.assert_array_equal(np.asarray(state.key_clock), reference.key_clock)
    np.testing.assert_array_equal(np.asarray(state.vote_frontier), reference.votes)
    carried = [(tuple(k), s, q, c) for k, s, q, c in zip(
        np.asarray(state.pend_key).tolist(), np.asarray(state.pend_src).tolist(),
        np.asarray(state.pend_seq).tolist(), np.asarray(state.pend_clock).tolist())
        if any(key != PAD for key in k)]
    assert carried == [tuple(cmd) for cmd in reference.pending]


def run_against_reference(shape, length, seed, dispatches=3, after_dispatch=None, **quorum):
    """``dispatches`` programs of ``length`` rounds each, every round held
    to the reference's; ``after_dispatch(state, reference)`` sees the state
    each program leaves."""
    mesh = forced_mesh(shape)
    state = mesh_step.init_newt_state(
        mesh, N * SHARDS, key_buckets=BUCKETS, pending_capacity=CAPACITY, key_width=WIDTH)
    kwargs = dict(f=quorum.get("f", 1), live_replicas=quorum.get("live_replicas"),
                  shard_count=SHARDS)
    program = (mesh_step.jit_newt_step if length == 1 else mesh_step.jit_newt_multi_step)(
        mesh, **kwargs)
    reference = NewtReference(N, kwargs["f"], SHARDS, BUCKETS, CAPACITY, WIDTH,
                              live_replicas=kwargs["live_replicas"])
    rng = np.random.default_rng([seed, length])
    seen = {"executed": 0, "slow_paths": 0, "dropped": 0}
    for dispatch in range(dispatches):
        keys, srcs, seqs = batches(rng, length, first_seq=dispatch * length * BATCH)
        if length == 1:
            state, out = program(state, keys[0], srcs[0], seqs[0])
            outs = [out]
        else:
            state, stacked = program(state, keys, srcs, seqs)
            stacked = jax.device_get(stacked)
            outs = [type(stacked)(*(column[r] for column in stacked)) for r in range(length)]
        for r, out in enumerate(outs):
            want = reference.round(keys[r], srcs[r], seqs[r])
            assert_round_equal(out, want, dispatch * length + r)
            seen["executed"] += len(want.order)
            seen["slow_paths"] += want.slow_paths
            seen["dropped"] += want.dropped
        if after_dispatch is not None:
            after_dispatch(state, reference)
    assert_state_equal(state, reference)
    return seen, reference


@pytest.mark.parametrize("length", LADDER)
@pytest.mark.parametrize("shape", MESHES.values(), ids=MESHES.keys())
def test_the_round_and_every_chain_length_equal_the_reference_on_every_layout(shape, length):
    seen, reference = run_against_reference(shape, length, seed=27)
    assert seen["executed"] > 10 * length and not reference.pending  # all live: nothing is held


@pytest.mark.parametrize("live_replicas", (None, 18), ids=("all_live", "lagging_minority"))
@pytest.mark.parametrize("shape", (MESHES["1x1"], MESHES["4x1"]), ids=("1x1", "4x1"))
def test_a_live_members_key_clock_never_lags_its_votes_after_any_round(
        shape, live_replicas):
    """What lets the round raise the key clock only where its commands have
    keys (``newt_protocol_step``'s invariant): over twenty rounds on hot
    keys, with every member live and with two of the last shard's five
    lagging, ``0 <= vote_frontier <= key_clock`` on every live row and
    bucket after every round, and both tables and the carried commands
    are the reference's, which takes the maximum over the whole table."""
    def held(state, reference):
        live = np.array(reference.live)
        key_clock, votes = np.asarray(state.key_clock), np.asarray(state.vote_frontier)
        assert (votes >= 0).all() and (key_clock[live] >= votes[live]).all()
        # a lagging member learns nothing
        assert not votes[~live].any() and not key_clock[~live].any()
        assert_state_equal(state, reference)

    seen, reference = run_against_reference(shape, 1, seed=33, dispatches=20,
                                            after_dispatch=held, live_replicas=live_replicas)
    # three of five still make a key stable
    assert seen["executed"] > 200 and reference.votes.max() > 20


@pytest.mark.parametrize("length", (1, 4))
@pytest.mark.parametrize("shape", MESHES.values(), ids=MESHES.keys())
def test_a_shard_short_of_votes_holds_its_commands_and_their_keys_as_the_reference_does(
        shape, length):
    """Two of the last shard's five members live: its commands commit (the
    fast quorum still agrees), never become stable on two votes of five,
    fill the pending buffer and overflow it; commands of other shards that
    share a key's order with them wait."""
    seen, reference = run_against_reference(shape, length, seed=5, dispatches=4,
                                            live_replicas=17)
    assert seen["executed"] > 0 and seen["dropped"] > 0 and len(reference.pending) == CAPACITY
    assert all(cmd.clock >= 0 for cmd in reference.pending)


@pytest.mark.parametrize("shape", MESHES.values(), ids=MESHES.keys())
def test_a_shard_short_of_a_write_quorum_leaves_its_commands_uncommitted_as_the_reference_does(
        shape):
    """f=2 and one live member in the last shard: once that member's clocks
    run ahead its fast quorum disagrees (slow paths), and the accept round
    has no quorum of three, so those commands stay uncommitted: carried
    while the buffer has room, dropped behind the committed ones after."""
    seen, reference = run_against_reference(shape, 2, seed=11, dispatches=3, f=2,
                                            live_replicas=16)
    assert seen["slow_paths"] > 0 and seen["dropped"] > 0 and seen["executed"] > 0
