"""The dependency round (`parallel/mesh_step.py` `protocol_step`) against the
plain reference `tests/atlas_reference.py`, on seeded random commands at small
sizes: under either quorum rule, at `f` 1 and 2, on one shard and several, at
key width 1 and 2, with no reads, half and nearly all, with every replica live,
with a stale member in the last shard's fast quorum and with that shard under
its write quorum.  Round by round: the committed dependencies slot for slot,
`fast_path`, what executed, the order a topological order of the reference's
graph, the slow paths and the round's five tallies."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fantoch_tpu.parallel import mesh_step
from tests import atlas_reference as plain

N, BATCH, PENDING = 5, 16, 48


@functools.lru_cache(maxsize=None)
def _mesh():
    return jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), (mesh_step.REPLICA_AXIS, mesh_step.BATCH_AXIS)
    )


@functools.lru_cache(maxsize=None)
def _step(rule, f, shards, live):
    return mesh_step.jit_protocol_step(
        _mesh(), live_replicas=live, shard_count=shards, f=f, rule=rule
    )


def _lives(rule, f, shards, live):
    """Rows live in the degraded rounds: every shard whole but the last."""
    fast, write = plain.quorum_sizes(rule, N, f)
    last = {"all": N, "fast_quorum_short": fast - 1, "under_write_quorum": write - 1}[live]
    return (shards - 1) * N + last


class Rounds:
    """The device round and the reference, fed the same commands."""

    def __init__(self, rule, f, shards, key_width, seed):
        self.rule, self.f, self.shards, self.key_width = rule, f, shards, key_width
        self.buckets = 32 * shards
        self.state = mesh_step.init_state(
            _mesh(), N * shards, key_buckets=self.buckets, pending_capacity=PENDING,
            key_width=key_width,
        )
        self.reference = plain.Reference(rule, N, f, shards, pending=PENDING)
        self.rng = np.random.default_rng(seed)
        self.sent = 0
        self.dot_of = {}  # gid -> dot
        self.commands = {}  # dot -> plain.Command
        self.slow_paths = 0

    def commands_for(self, fill, read_share, distinct):
        out = []
        for _ in range(fill):
            keys = self.rng.choice(distinct * self.shards, size=self.key_width, replace=False)
            self.sent += 1
            out.append(plain.Command(
                int(self.rng.integers(1, 6)), self.sent, tuple(sorted(int(k) for k in keys)),
                bool(self.rng.random() < read_share),
            ))
        return out

    def round(self, commands, live):
        """One round on both; everything compared; returns the device's output."""
        key = np.full((BATCH, self.key_width), mesh_step.KEY_PAD, np.int32)
        src, seq = np.zeros(BATCH, np.int32), np.zeros(BATCH, np.int32)
        read = np.zeros(BATCH, bool)
        first = int(self.state.next_gid)
        for i, cmd in enumerate(commands):
            key[i], src[i], seq[i], read[i] = cmd.keys, cmd.src, cmd.seq, cmd.read
            self.dot_of[first + i] = cmd.dot
            self.commands[cmd.dot] = cmd
        want = self.reference.round(commands, live)
        assert not want.resubmit
        self.state, out = _step(self.rule, self.f, self.shards, live)(
            self.state, jnp.asarray(key), jnp.asarray(src), jnp.asarray(seq), jnp.asarray(read)
        )
        gids = np.asarray(out.gids)
        deps = np.asarray(out.deps_gid)
        fast, executed = np.asarray(out.fast_path), np.asarray(out.resolved)
        rows = {self.dot_of[int(g)]: w for w, g in enumerate(gids) if int(g) in self.dot_of}
        assert set(rows) == set(want.verdicts)  # the working set: what was carried, what came
        for dot, verdict in want.verdicts.items():
            w, cmd = rows[dot], self.commands[dot]
            # keys in the row's slot order (the driver sorts a command's buckets)
            for slot, k in enumerate(cmd.keys):
                got = tuple(
                    None if g < 0 else self.dot_of[int(g)]
                    for g in (deps[w, slot], deps[w, self.key_width + slot])
                )
                assert got == verdict.slots[k], (dot, k, got, verdict)
                assert {d for d in got if d is not None} <= verdict.deps
            assert bool(fast[w]) == verdict.fast, (dot, verdict)
            assert bool(executed[w]) == verdict.executed, (dot, verdict)
        # the order: whatever a command depends on, and ran in this round, ran before it
        order = [self.dot_of[int(gids[w])] for w in np.asarray(out.order)
                 if executed[w] and int(gids[w]) in self.dot_of]
        assert sorted(order) == sorted(want.order)
        place = {dot: at for at, dot in enumerate(order)}
        for dot in order:
            for dep in want.verdicts[dot].deps:
                assert dep not in place or place[dep] < place[dot], (dot, dep)
        assert int(out.slow_paths) == want.slow_paths
        assert int(out.pending) == len(self.reference.carried) and int(out.pend_dropped) == 0
        assert dict(zip(mesh_step.ROUND_TALLIES, np.asarray(out.tallies).tolist())) == want.tally(
            self.commands, self.shards)
        self.slow_paths += want.slow_paths
        return out, want


@pytest.mark.parametrize("live", ("all", "fast_quorum_short", "under_write_quorum"))
@pytest.mark.parametrize("read_share", (0.0, 0.5, 0.95))
@pytest.mark.parametrize("key_width", (1, 2))
@pytest.mark.parametrize("shards", (1, 2, 4))
@pytest.mark.parametrize("rule, f", [("epaxos", 1), ("epaxos", 2), ("atlas", 1), ("atlas", 2)])
def test_the_round_agrees_with_the_plain_reference(rule, f, shards, key_width, read_share, live):
    """Two rounds with everyone live (the indexes fill), three degraded, the
    first of them by one replica less (so two stale members stopped learning
    at different rounds and report different things; under the write quorum
    the slow path commits nothing and rows are carried), three live again
    (what was carried commits and runs): every round equal to the
    reference's."""
    everyone = N * shards
    degraded = _lives(rule, f, shards, live)
    rounds = Rounds(rule, f, shards, key_width, seed=40 + 7 * shards + key_width)
    carried = 0
    lives = [everyone] * 2 + [min(degraded + 1, everyone)] + [degraded] * 2 + [everyone] * 3
    for r, alive in enumerate(lives):
        fill = BATCH if r % 2 == 0 else int(rounds.rng.integers(1, BATCH))
        out, _ = rounds.round(rounds.commands_for(fill, read_share, distinct=6), alive)
        carried += int(out.pending)
    assert not rounds.reference.carried and len(rounds.reference.executed) == rounds.sent
    if live == "all":
        assert rounds.slow_paths == 0
    elif read_share < 0.9:  # the case degrades what it says it does
        assert rounds.slow_paths > 0 or (rule, f) == ("atlas", 1)
        short = degraded - (shards - 1) * N < plain.quorum_sizes(rule, N, f)[1]
        if not short or (rule, f) == ("atlas", 1):
            assert carried == 0
        elif shards < 4 or key_width == 2:  # else too few commands meet in the last shard
            assert carried > 0
    if rule == "atlas" and f == 1:
        assert rounds.slow_paths == 0  # whoever reports a dependency is one of f


def test_atlas_at_f_1_never_takes_the_slow_path_where_epaxos_with_a_stale_member_does():
    """Member 2 of five is in both fast quorums (three each).  While it is not
    live it learns nothing, and reports what it knew: EPaxos's quorum then
    disagrees on every key written meanwhile and its write quorum of three is
    not there, so those commands wait; Atlas at `f` = 1 takes the union, every
    dependency of it reported by one, and commits everything fast."""
    taken = {}
    for rule in ("epaxos", "atlas"):
        rounds = Rounds(rule, 1, 1, 1, seed=5)
        carried = 0
        for alive in (5, 2, 2, 2):
            out, want = rounds.round(rounds.commands_for(BATCH, 0.5, distinct=4), alive)
            carried += int(out.pending)
        taken[rule] = (rounds.slow_paths, carried)
        assert all(v.fast for v in want.verdicts.values()) == (rule == "atlas")
    assert taken["atlas"] == (0, 0)
    assert taken["epaxos"][0] > 0 and taken["epaxos"][1] > 0


@pytest.mark.parametrize("shards, lives", [(1, (5, 2, 2, 5)), (2, (10, 7, 7, 10))])
def test_with_no_read_in_a_round_the_outputs_are_the_parents_bit_for_bit(shards, lives):
    """`tests/test_mesh_step.py` `_oracle_round` is the round as it was before
    it knew reads from writes (one clock, one dependency slot a key, EPaxos's
    quorums); a round given no read gives its outputs and its next state,
    element for element, and leaves the second slot and the read clock empty."""
    from tests.test_mesh_step import _oracle_round

    rounds = Rounds("epaxos", 1, shards, 1, seed=11)
    state = rounds.state
    for r, live in enumerate(lives):
        cmds = rounds.commands_for(BATCH - 3 * (r % 2), 0.0, distinct=5)
        key = np.full(BATCH, mesh_step.KEY_PAD, np.int32)
        src, seq = np.zeros(BATCH, np.int32), np.zeros(BATCH, np.int32)
        for i, cmd in enumerate(cmds):
            key[i], src[i], seq[i] = cmd.keys[0], cmd.src, cmd.seq
        want_state, want = _oracle_round(state, key, src, seq, shard_count=shards, live_replicas=live)
        state, out = _step("epaxos", 1, shards, live)(
            state, jnp.asarray(key), jnp.asarray(src), jnp.asarray(seq), jnp.zeros(BATCH, bool)
        )
        for name, got, expected in zip(out._fields + state._fields, out + state, want + want_state):
            assert np.array_equal(np.asarray(got), expected), (r, name)
        assert (np.asarray(out.deps_gid)[:, 1] == -1).all()
        assert (np.asarray(state.read_clock) == -1).all() and not np.asarray(out.tallies)[2:].any()
