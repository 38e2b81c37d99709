"""The round with a coordinator at every site (`parallel/mesh_step.py`
`protocol_step(sites=n)`, resolved by `ops/graph_resolve.resolve_key_runs`
with one key a command and by `resolve_general`'s components pass with
several, and finished by `executor/graph/deps_graph.tarjan_order`) against
the plain reference `tests/sites_reference.py`, on seeded rounds at small
sizes: batch 64 to 256, 16 to 64 keys a shard, conflict rates 0 / 50 / 100,
key width 1 / 2 / 3, 1 / 2 / 4 shards, both rules (Atlas's at f = 1, where
the fast path is unconditional, and at f = 2 and 3, where its threshold
decides), clients at 1 to 5 sites, with and without reads, with every replica
live and with rows carried by rounds under the write quorum.  Round by round:
each quorum member's report, `fast`, the committed dependencies as sets, what
executed, the execution order key bucket by key bucket, every component's
members contiguous and in dot order, the slow paths and the tallies.
Integers: no tolerance.

Then the same through `DeviceDriver.serve` (the registry, the drain, the
finisher on the served path), and, marked `slow`, at the benchmark cell's
shape for the chip."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fantoch_tpu.core.command import Command
from fantoch_tpu.core.ids import Dot, Rifl
from fantoch_tpu.core.kvs import KVOp
from fantoch_tpu.executor.graph.deps_graph import tarjan_order
from fantoch_tpu.parallel import mesh_step
from fantoch_tpu.run.device_drivers import DeviceDriver, _bucket, _sites_in_turn
from tests import sites_reference as plain

N = 5
FAST, WRITE = plain.quorum_sizes(N)
# what Atlas's threshold adds to the round's tallies (0 where none is taken)
THRESHOLD_TALLIES = ("threshold_short_deps", "split_quorum_rows", "threshold_fast_split_rows")


def test_the_reference_imports_nothing_of_the_round():
    with open(plain.__file__) as fh:
        source = fh.read()
    imports = [line for line in source.splitlines() if line.startswith(("import ", "from "))]
    assert imports == [
        "from __future__ import annotations",
        "from typing import Dict, FrozenSet, List, NamedTuple, Optional, Set, Tuple",
    ]


@functools.lru_cache(maxsize=None)
def _mesh():
    return jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), (mesh_step.REPLICA_AXIS, mesh_step.BATCH_AXIS)
    )


@functools.lru_cache(maxsize=None)
def _step(live, shards=1, rule="epaxos", f=1, n=N):
    return mesh_step.jit_protocol_step(
        _mesh(), live_replicas=live, shard_count=shards, f=f, rule=rule, sites=n, site_base=1
    )


def conflict_commands(rng, fill, keys, rate, sites, read_share, first_seq):
    """`fill` commands of the conflict-rate generator: key 0 at `rate` %,
    else the client's own key; a client is at one of `sites` sites."""
    out = []
    for at in range(fill):
        client = int(rng.integers(0, 4 * keys))
        site = client % sites
        key = 0 if rng.integers(0, 100) < rate else 1 + client % (keys - 1)
        out.append(plain.Command(1 + site, first_seq + at, key, bool(rng.random() < read_share), site))
    return out


def per_key(order, commands):
    """An execution order key by key.  Where a key has writes alone the graph
    orders every two of its commands (each has an edge to the one that
    arrived just before it), so its order is one; a read is ordered against
    the writes it or they depend on only (`locked.rs` keeps the latest read
    alone), so keys with reads are held to the graph, not to a sequence."""
    out = {}
    for dot in order:
        for key in commands[dot].keys:
            out.setdefault(key, []).append(dot)
    return {key: dots for key, dots in out.items() if not any(commands[d].read for d in dots)}


def several_keys_commands(rng, fill, keys, shards, width, sites, read_share, first_seq):
    """`fill` commands of `width` distinct keys each, drawn with a skew over
    `shards` x `keys` buckets (bucket `b` is shard `b % shards`'s); a command
    reads all its keys or writes them all, as `kv_multi`'s do."""
    out = []
    for at in range(fill):
        site = int(rng.integers(0, sites))
        chosen = set()
        while len(chosen) < width:
            chosen.add(int(shards * keys * rng.random() ** 2))
        out.append(plain.Command(1 + site, first_seq + at, tuple(sorted(chosen)),
                                 bool(rng.random() < read_share), site))
    return out


class Rounds:
    """The device round and the reference, fed the same commands."""

    def __init__(self, batch, keys, pending, seed, width=1, shards=1, rule="epaxos", f=1, n=N):
        self.batch, self.keys, self.pending = batch, keys, pending
        self.width, self.shards, self.rule, self.f, self.n = width, shards, rule, f, n
        self.state = mesh_step.init_state(
            _mesh(), n * shards, key_buckets=keys * shards, pending_capacity=pending,
            key_width=width,
        )
        self.reference = plain.Reference(n, shards, rule, f)
        self.rng = np.random.default_rng(seed)
        self.sent = 0
        self.dot_of = {}  # gid -> dot
        self.commands = {}  # dot -> plain.Command
        self.slow_paths = self.finished = self.scc_rows = self.span_rows = self.shard_rows = 0
        self.short_deps = self.split_rows = self.fast_split_rows = 0  # Atlas's threshold's

    def commands_for(self, fill, rate, sites, read_share):
        out = conflict_commands(self.rng, fill, self.keys, rate, sites, read_share, self.sent + 1)
        self.sent += fill
        return out

    def round(self, commands, live=None):
        """One round on both; everything compared; returns the device's output."""
        batch, shards, n = self.batch, self.shards, self.n
        live = n * shards if live is None else live
        key = np.full((batch, self.width), mesh_step.KEY_PAD, np.int32)
        src, seq = np.zeros(batch, np.int32), np.zeros(batch, np.int32)
        read = np.zeros(batch, bool)
        first = int(self.state.next_gid)
        for i, cmd in enumerate(commands):
            key[i, : len(cmd.keys)], src[i], seq[i], read[i] = cmd.keys, cmd.src, cmd.seq, cmd.read
            self.dot_of[first + i] = cmd.dot
            self.commands[cmd.dot] = cmd
        want = self.reference.round(commands, live)
        self.state, out = _step(live, shards, self.rule, self.f, n)(
            self.state, jnp.asarray(key), jnp.asarray(src), jnp.asarray(seq), jnp.asarray(read)
        )
        gids = np.asarray(out.gids)
        fast_quorum = self.reference.fast_quorum
        # by key slot, member of the slot's ring and class (write, read)
        deps = np.asarray(out.deps_gid).reshape(len(gids), self.width, fast_quorum, 2)
        fast, executed = np.asarray(out.fast_path), np.asarray(out.resolved)
        finish = np.asarray(out.finish)
        rows = {self.dot_of[int(g)]: w for w, g in enumerate(gids) if int(g) in self.dot_of}
        assert set(rows) == set(want.verdicts)  # the working set: what was carried, what came

        def dots(columns):
            return frozenset(self.dot_of[int(g)] for g in np.ravel(columns) if g >= 0)

        for dot, verdict in want.verdicts.items():
            w, cmd = rows[dot], self.commands[dot]
            # member k of a key slot's ring stands in that slot's columns 2k,
            # 2k + 1; a member's report is its words on the command's keys
            # of its shard, joined with the coordinator's
            for shard in {key % shards for key in cmd.keys}:
                at = [a for a, key in enumerate(cmd.keys) if key % shards == shard]
                ring = plain.fast_quorum(cmd.site, n, self.rule, self.f)
                for k, member in enumerate(ring):
                    got = dots(deps[w, at, k]) | dots(deps[w, at, 0])
                    assert got == verdict.reports[shard * n + member], (dot, member, got, verdict)
            for a, key in enumerate(cmd.keys):
                assert dots(deps[w, a]) == verdict.by_key[key], (dot, key, verdict)
            assert not (deps[w, len(cmd.keys):] >= 0).any()
            assert dots(deps[w]) == verdict.deps, (dot, verdict)
            assert bool(fast[w]) == verdict.fast, (dot, verdict)
            assert bool(executed[w]) == verdict.executed, (dot, verdict)
            assert not finish[w] or executed[w]
        # the order: the device's, then what the host's Tarjan makes of the rest
        ordered = [w for w in np.asarray(out.order).tolist()
                   if executed[w] and not finish[w] and int(gids[w]) in self.dot_of]
        left = [w for w in np.flatnonzero(finish).tolist()]
        if left:
            at = {int(gids[w]): i for i, w in enumerate(left)}
            ordered += [left[i] for i in tarjan_order(
                [Dot(*self.dot_of[int(gids[w])]) for w in left],
                [sorted({at[int(g)] for g in deps[w].ravel() if int(g) in at}) for w in left], n)[0]]
        order = [self.dot_of[int(gids[w])] for w in ordered]
        assert sorted(order) == sorted(want.order)
        assert per_key(order, self.commands) == per_key(want.order, self.commands)
        # every component contiguous and in dot order, after all it depends on
        place = {dot: at for at, dot in enumerate(order)}
        for component in want.components:
            places = [place[dot] for dot in component]
            assert places == list(range(places[0], places[0] + len(component))), component
            for dot in component:
                for dep in want.verdicts[dot].deps - set(component):
                    assert dep not in place or place[dep] < places[0], (dot, dep)
        assert int(out.slow_paths) == want.slow_paths
        assert int(out.pending) == len(self.reference.carried) and int(out.pend_dropped) == 0
        tallies = dict(zip(mesh_step.SITE_ROUND_TALLIES + mesh_step.SITE_ROUND_GAUGES,
                           np.asarray(out.tallies).tolist()))
        tally = want.tally()
        assert tallies["deps_committed"] == tally["deps_committed"]
        assert tallies["finisher_rows"] == len(left)
        assert tallies["read_rows"] == sum(self.commands[d].read for d in want.order)
        assert tallies["cross_shard_executed"] == tally["cross_shard_executed"]
        for name in THRESHOLD_TALLIES:
            assert tallies[name] == tally[name], (name, tallies, tally)
        if self.rule == "epaxos" or self.f == 1:  # no threshold is taken
            assert not any(tally[name] for name in THRESHOLD_TALLIES)
        if not left:  # else the finisher's components join the device's: the driver's sum
            for name in ("scc_rows", "scc_count", "scc_rows_max", "scc_span_rows", "scc_shard_rows"):
                assert tallies[name] == tally[name], (name, tallies, tally)
        assert tallies["resolve_iters"] >= (1 if self.width == 1 else 0)
        self.slow_paths += want.slow_paths
        self.finished += len(left)
        self.scc_rows += tally["scc_rows"]
        self.span_rows += tally["scc_span_rows"]
        self.shard_rows += tally["scc_shard_rows"]
        self.short_deps += tally["threshold_short_deps"]
        self.split_rows += tally["split_quorum_rows"]
        self.fast_split_rows += tally["threshold_fast_split_rows"]
        return out, want


@pytest.mark.parametrize("read_share", (0.0, 0.4))
@pytest.mark.parametrize("sites", (1, 2, 3, 4, 5))
@pytest.mark.parametrize("rate", (0, 50, 100))
@pytest.mark.parametrize("batch, keys", [(64, 16), (128, 32), (256, 64)])
def test_the_round_agrees_with_the_plain_reference(batch, keys, rate, sites, read_share):
    """Three rounds, the second part-full: every round equal to the reference's."""
    rounds = Rounds(batch, keys, pending=batch, seed=batch + 7 * sites + rate)
    for r in range(3):
        fill = batch if r != 1 else int(rounds.rng.integers(1, batch))
        rounds.round(rounds.commands_for(fill, rate, sites, read_share))
    assert not rounds.reference.carried and len(rounds.reference.executed) == rounds.sent
    if sites == 1:  # one coordinator, one view: nothing to disagree about
        assert rounds.slow_paths == 0 and rounds.scc_rows == 0 and rounds.finished == 0
    elif rate:
        assert rounds.slow_paths > 0 and rounds.scc_rows > 0  # the case is what it says
    if not read_share and sites >= 1:  # writes alone leave chains: the device cuts them all
        assert rounds.finished == 0


@pytest.mark.parametrize("read_share", (0.0, 0.4))
@pytest.mark.parametrize("sites", (2, 5))
@pytest.mark.parametrize("rate", (50, 100))
def test_rows_carried_by_rounds_under_the_write_quorum_agree_too(rate, sites, read_share):
    """Two rounds with everyone live, three with two of five (a command that
    missed the fast path is not accepted, and whatever reaches it waits: rows
    are carried, and the three that stopped learning report what they knew),
    three live again (what was carried commits and runs)."""
    rounds = Rounds(64, 16, pending=192, seed=3 * sites + rate)
    carried = 0
    for r, live in enumerate([N] * 2 + [WRITE - 1] * 3 + [N] * 3):
        fill = 64 if r % 2 == 0 else int(rounds.rng.integers(1, 64))
        out, _ = rounds.round(rounds.commands_for(fill, rate, sites, read_share), live)
        carried += int(out.pending)
    assert carried > 0
    assert not rounds.reference.carried and len(rounds.reference.executed) == rounds.sent


def test_the_references_components_are_the_host_tarjans():
    """`sites_reference.components_of` against `executor/graph/tarjan.py`
    (through `tarjan_order`) on seeded graphs: the same components, and an
    order that puts each after what it depends on."""
    rng = np.random.default_rng(17)
    for _ in range(20):
        size = int(rng.integers(2, 60))
        dots = [(1 + int(rng.integers(0, N)), at + 1) for at in range(size)]
        deps = [sorted({int(d) for d in rng.integers(0, size, int(rng.integers(0, 4))) if d != at})
                for at in range(size)]
        order, sizes = tarjan_order([Dot(*dot) for dot in dots], deps, N)
        found = plain.components_of({dots[at]: [dots[d] for d in deps[at]] for at in range(size)})
        assert sorted(map(len, found)) == sorted(sizes)
        cut, at = [], 0
        for component in sorted(found, key=lambda c: order.index(dots.index(c[0]))):
            cut.append(sorted(dots[row] for row in order[at: at + len(component)]))
            at += len(component)
        assert sorted(cut) == sorted(sorted(c) for c in found)


# --- several keys a command, shards, both rules ---------------------------------


@pytest.mark.parametrize("read_share", (0.0, 0.5))
@pytest.mark.parametrize("rule", ("epaxos", "atlas"))
@pytest.mark.parametrize("sites", (1, 2, 5))
@pytest.mark.parametrize("width, shards", [(1, 2), (1, 4), (2, 1), (2, 2), (2, 4), (3, 1), (3, 4)])
def test_the_round_of_several_keys_and_shards_agrees_with_the_plain_reference(
        width, shards, sites, rule, read_share):
    """Three rounds of skewed `width`-key commands over `shards` shards, the
    second part-full: every round equal to the reference's, member by member,
    key by key, component by component."""
    batch, keys = (64, 16) if width == 2 else (128, 32)
    rounds = Rounds(batch, keys, pending=batch, seed=7 * width + shards + sites,
                    width=width, shards=shards, rule=rule)
    for r in range(3):
        fill = batch if r != 1 else int(rounds.rng.integers(1, batch))
        rounds.round(several_keys_commands(
            rounds.rng, fill, keys, shards, width, sites, read_share, rounds.sent + 1))
        rounds.sent += fill
    assert not rounds.reference.carried and len(rounds.reference.executed) == rounds.sent
    if rule == "atlas":  # f = 1: whoever reported a dependency is one of f
        assert rounds.slow_paths == 0
    if sites == 1:
        assert rounds.slow_paths == 0 and rounds.scc_rows == 0 and rounds.finished == 0
    else:  # the case is what it says: cycles, and with several keys across them
        assert rounds.scc_rows > 0
        assert (rounds.span_rows > 0) == (width > 1)
        assert (rounds.shard_rows > 0) == (width > 1 and shards > 1)
    if width > 1:  # the whole working set fits the components pass's residual
        assert rounds.finished == 0


@pytest.mark.parametrize("rule", ("epaxos", "atlas"))
@pytest.mark.parametrize("width, shards", [(2, 1), (2, 4), (3, 2)])
def test_rows_of_several_keys_carried_under_the_write_quorum_agree_too(width, shards, rule):
    """Two rounds with everyone live, three with the last shard one short of
    its write quorum (a command of that shard that missed the fast path is not
    accepted there, so it commits on none of its shards, and whatever reaches
    it waits), three live again.  Under Atlas's rule at `f` = 1 nothing misses
    the fast path, so nothing is carried."""
    rounds = Rounds(64, 16, pending=192, seed=5 * width + shards, width=width,
                    shards=shards, rule=rule)
    short = N * (shards - 1) + rounds.reference.write_quorum - 1
    carried = 0
    for r, live in enumerate([None] * 2 + [short] * 3 + [None] * 3):
        fill = 64 if r % 2 == 0 else int(rounds.rng.integers(1, 64))
        out, _ = rounds.round(several_keys_commands(
            rounds.rng, fill, 16, shards, width, 5, 0.5, rounds.sent + 1), live)
        rounds.sent += fill
        carried += int(out.pending)
    assert (carried > 0) == (rule == "epaxos")
    assert not rounds.reference.carried and len(rounds.reference.executed) == rounds.sent


def test_a_working_set_beyond_the_residual_goes_to_the_finisher_whole():
    """1024 working rows give the components pass a residual of 256; 512
    two-key commands over 2 x 8 keys nearly all wait for another, so the pass
    hands every one that waits to the host's Tarjan (`finish`), the rows that
    wait for none run first, and the round still equals the reference's."""
    rounds = Rounds(512, 8, pending=512, seed=11, width=2, shards=2, rule="atlas")
    for _ in range(2):
        out, want = rounds.round(several_keys_commands(
            rounds.rng, 512, 8, 2, 2, 5, 0.5, rounds.sent + 1))
        rounds.sent += 512
        assert int(np.asarray(out.finish).sum()) > 512 - 32
    assert rounds.finished > 0 and not rounds.reference.carried


def test_three_commands_on_three_keys_of_two_shards_with_no_mutual_edge_run_in_dot_order():
    """Built by hand: x = write(k2, k3) and then z = write(k2, k3), both at
    site 0, then y = write(k1, k2) at site 1; k2 on shard 0, k1 and k3 on
    shard 1.  Replica 1 of shard 0, in x's quorum, has its own y first, so x
    finds y on k2; y's other members find z, the latest before it there, and
    not x; z finds x on both its keys.  x -> y -> z -> x: one component, no
    two of them finding each other, its dependencies on two keys, its rows on
    three keys of both shards.  (Two commands alone on a key always
    leave a mutual edge: the later one finds the earlier one.)  They execute
    together, in dot order."""
    rounds = Rounds(8, 2, pending=8, seed=0, width=2, shards=2, rule="atlas")
    x = plain.Command(1, 1, (2, 3), False, 0)
    z = plain.Command(1, 2, (2, 3), False, 0)
    y = plain.Command(2, 1, (1, 2), False, 1)
    out, want = rounds.round([x, z, y])
    found = {cmd.dot: want.verdicts[cmd.dot].deps for cmd in (x, y, z)}
    assert found == {x.dot: {y.dot}, y.dot: {z.dot}, z.dot: {x.dot}}
    assert want.components == [[x.dot, z.dot, y.dot]] == [want.order]
    assert want.verdicts[z.dot].by_key == {2: {x.dot}, 3: {x.dot}}
    tallies = dict(zip(mesh_step.SITE_ROUND_TALLIES, np.asarray(out.tallies).tolist()))
    assert tallies["scc_rows"] == tallies["scc_span_rows"] == tallies["scc_shard_rows"] == 3
    assert tallies["scc_count"] == 1 and tallies["finisher_rows"] == 0
    # the batch's rows stand after the 8 pending ones (its padding runs first)
    order = [w for w in np.asarray(out.order).tolist() if w in (8, 9, 10)]
    assert order == [8, 9, 10] and np.asarray(out.resolved)[8:11].all()  # x, z, y


# --- Atlas's threshold at f >= 2 --------------------------------------------------


@pytest.mark.parametrize("read_share", (0.0, 0.4))
@pytest.mark.parametrize("sites", (1, 2, 5))
@pytest.mark.parametrize("rate", (0, 50, 100))
@pytest.mark.parametrize("batch, keys", [(64, 16), (128, 32)])
def test_the_threshold_round_agrees_with_the_plain_reference(batch, keys, rate, sites, read_share):
    """Atlas at n = 5, f = 2 (a ring of four, write quorum three), one key a
    command: three rounds, the second part-full, every round equal to the
    reference's member by member, fast or slow row by row, tally by tally."""
    rounds = Rounds(batch, keys, pending=batch, seed=batch + 7 * sites + rate, rule="atlas", f=2)
    assert (rounds.reference.fast_quorum, rounds.reference.write_quorum) == (4, 3)
    for r in range(3):
        fill = batch if r != 1 else int(rounds.rng.integers(1, batch))
        rounds.round(rounds.commands_for(fill, rate, sites, read_share))
    assert not rounds.reference.carried and len(rounds.reference.executed) == rounds.sent
    if sites == 1:  # one coordinator, one view: every report is the coordinator's
        assert rounds.slow_paths == rounds.split_rows == rounds.short_deps == 0
        assert rounds.scc_rows == 0 and rounds.finished == 0
    elif rate:  # the case is what it says: the threshold decides, both ways
        assert 0 < rounds.slow_paths <= rounds.short_deps
        assert 0 < rounds.fast_split_rows < rounds.split_rows
        assert rounds.split_rows - rounds.fast_split_rows == rounds.slow_paths
        assert rounds.scc_rows > 0
    if not read_share:
        assert rounds.finished == 0


@pytest.mark.parametrize("read_share", (0.0, 0.5))
@pytest.mark.parametrize("sites", (2, 5))
@pytest.mark.parametrize("width, shards", [(1, 2), (1, 4), (2, 1), (2, 2), (2, 4), (3, 1), (3, 4)])
def test_the_threshold_round_of_several_keys_and_shards_agrees_with_the_plain_reference(
        width, shards, sites, read_share):
    """Atlas at f = 2 over `shards` shards of five with `width` keys a command:
    the threshold is a shard's (a report holds a member's words on the
    command's slots of its shard), a command is fast iff every shard it touches
    is, and a dependency that fell short on two shards counts once."""
    batch, keys = (64, 16) if width == 2 else (128, 32)
    rounds = Rounds(batch, keys, pending=batch, seed=7 * width + shards + sites,
                    width=width, shards=shards, rule="atlas", f=2)
    for r in range(3):
        fill = batch if r != 1 else int(rounds.rng.integers(1, batch))
        rounds.round(several_keys_commands(
            rounds.rng, fill, keys, shards, width, sites, read_share, rounds.sent + 1))
        rounds.sent += fill
    assert not rounds.reference.carried and len(rounds.reference.executed) == rounds.sent
    assert 0 < rounds.slow_paths <= rounds.short_deps
    assert 0 < rounds.fast_split_rows < rounds.split_rows
    assert rounds.scc_rows > 0 and (rounds.span_rows > 0) == (width > 1)
    if width > 1:
        assert rounds.finished == 0


@pytest.mark.parametrize("width, shards, read_share", [
    (1, 1, 0.0), (1, 1, 0.4), (2, 1, 0.5), (2, 4, 0.5), (3, 2, 0.5),
])
def test_threshold_rows_carried_under_the_write_quorum_agree_too(width, shards, read_share):
    """Atlas at f = 2: two rounds with everyone live, three with the last
    shard's live rows one under its write quorum of three (a command the
    threshold sent to the accept round is not accepted there, commits on none
    of its shards and is carried, and so is whatever reaches it), three live
    again: what was carried commits and runs."""
    rounds = Rounds(64, 16, pending=192, seed=5 * width + shards, width=width,
                    shards=shards, rule="atlas", f=2)
    assert rounds.reference.write_quorum == 3
    short = N * (shards - 1) + 2
    carried = 0
    for r, live in enumerate([None] * 2 + [short] * 3 + [None] * 3):
        fill = 64 if r % 2 == 0 else int(rounds.rng.integers(1, 64))
        if width == 1:
            commands = rounds.commands_for(fill, 50, 5, read_share)
        else:
            commands = several_keys_commands(
                rounds.rng, fill, 16, shards, width, 5, read_share, rounds.sent + 1)
            rounds.sent += fill
        out, _ = rounds.round(commands, live)
        carried += int(out.pending)
    assert carried > 0 and rounds.slow_paths > 0
    assert not rounds.reference.carried and len(rounds.reference.executed) == rounds.sent


def test_the_threshold_round_at_seven_replicas_tolerating_three_agrees_too():
    """n = 7, f = 3: a ring of six of the seven, a dependency needs three of
    them (or the coordinator), write quorum four."""
    rounds = Rounds(128, 32, pending=128, seed=73, rule="atlas", f=3, n=7)
    assert (rounds.reference.fast_quorum, rounds.reference.write_quorum) == (6, 4)
    for r in range(3):
        fill = 128 if r != 1 else 51
        commands = conflict_commands(rounds.rng, fill, 32, 50, 7, 0.2, rounds.sent + 1)
        rounds.sent += fill
        rounds.round(commands)
    assert not rounds.reference.carried and len(rounds.reference.executed) == rounds.sent
    assert 0 < rounds.slow_paths and 0 < rounds.fast_split_rows < rounds.split_rows


def test_a_dependency_of_one_member_is_slow_of_two_or_of_the_coordinator_alone_is_not():
    """Built by hand, n = 5, f = 2, one key, writes, in arrival order: d at
    site 0, e at site 4, x at site 0.

    * e's ring is 4, 0, 1, 2.  Its coordinator has e first and finds nothing;
      replica 0 has its own d and x first and finds x, the later; replicas 1
      and 2 have d, e, x and find d.  x was reported by one member: under f,
      the accept round.  d was reported by two: enough.
    * x's ring is 0, 1, 2, 3.  Its coordinator has d, x, e and finds d;
      replicas 1, 2 and 3 have d, e, x and find e.  d was found by the
      coordinator alone, and the coordinator's word is in every report: fast.
      e was reported by three.
    * d finds nothing anywhere.

    So of three commands one is slow, for one dependency; two had their quorum
    split, and the threshold kept one of the two fast.  (On one key with every
    replica live no command can have its one dependency from exactly two
    members and none from a third: whoever is not the coordinator has every
    earlier arrival before it.)"""
    rounds = Rounds(8, 4, pending=8, seed=0, rule="atlas", f=2)
    d, e, x = (plain.Command(1, 1, 2, False, 0), plain.Command(5, 1, 2, False, 4),
               plain.Command(1, 2, 2, False, 0))
    out, want = rounds.round([d, e, x])
    on_e, on_x = want.verdicts[e.dot], want.verdicts[x.dot]
    assert on_e.reports == {4: frozenset(), 0: {x.dot}, 1: {d.dot}, 2: {d.dot}}
    assert on_e.short == {x.dot} and not on_e.fast and on_e.split and on_e.committed
    assert on_x.reports == {0: {d.dot}, 1: {d.dot, e.dot}, 2: {d.dot, e.dot}, 3: {d.dot, e.dot}}
    assert not on_x.short and on_x.fast and on_x.split
    assert want.verdicts[d.dot].fast and not want.verdicts[d.dot].split
    tally = want.tally()
    assert (tally["threshold_short_deps"], tally["split_quorum_rows"],
            tally["threshold_fast_split_rows"], want.slow_paths) == (1, 2, 1, 1)
    assert np.asarray(out.fast_path)[8:11].tolist() == [True, False, True]  # d, e, x
    # under EPaxos's equality both would have taken the accept round
    same = plain.Reference(N).round([d, e, x])
    assert same.slow_paths == 2 and same.tally()["split_quorum_rows"] == 0


def test_f_two_takes_both_paths_in_one_run_and_f_one_never_the_slow_one():
    """The same commands (conflict rate 50, five sites) under Atlas's rule at
    f = 1 and at f = 2, and under EPaxos's: f = 1 is fast by rule; f = 2 sends
    some to the accept round and keeps most split quorums fast; what splits a
    quorum of four is what splits EPaxos's of three and more."""
    slow, split = {}, {}
    for rule, f in (("atlas", 1), ("atlas", 2), ("epaxos", 1)):
        rounds = Rounds(256, 64, pending=256, seed=9, rule=rule, f=f)
        for _ in range(3):
            out, want = rounds.round(rounds.commands_for(256, 50, 5, 0.0))
            fast = np.asarray(out.fast_path)[np.asarray(out.gids) >= 0]
            if (rule, f) == ("atlas", 2):
                assert 0 < fast.sum() < len(fast)  # both paths in one round
        slow[rule, f], split[rule, f] = rounds.slow_paths, rounds.split_rows
    assert slow["atlas", 1] == 0 and split["atlas", 1] == 0
    assert 0 < slow["atlas", 2] < slow["epaxos", 1] <= split["atlas", 2]
    assert rounds.sent == 768


# --- through the driver ---------------------------------------------------------


def _serve(driver, reference, commands, values):
    """One round through `DeviceDriver.serve` and the reference; the rifls
    the driver executed, in its order, beside the reference's dots."""
    batch = [
        (Dot(cmd.src, cmd.seq),
         Command.from_single(Rifl(cmd.src, cmd.seq), 0, str(cmd.key),
                             KVOp.get() if cmd.read else KVOp.put(values[cmd.dot])))
        for cmd in commands
    ]
    # the driver takes the sites' commands in turn: so is the reference given them
    by_dot = {cmd.dot: cmd for cmd in commands}
    want = reference.round([by_dot[dot.source, dot.sequence] for dot, _ in _sites_in_turn(batch)])
    results = driver.serve([batch])
    return [(r.rifl.source, r.rifl.sequence) for r in results], want


def _driver(batch, keys, **kwargs):
    return DeviceDriver(N, batch_size=batch, key_buckets=keys, pending_capacity=batch,
                        mesh=_mesh(), **kwargs)


def _bucket_keys(keys):
    """Key names whose buckets are `0 .. keys - 1`, one each (the driver
    hashes a key's name to its bucket)."""
    names = {}
    at = 0
    while len(names) < keys:
        names.setdefault(_bucket(0, str(at), keys, 1), at)
        at += 1
    return names


@pytest.mark.parametrize("read_share", (0.0, 0.4))
@pytest.mark.parametrize("rate", (0, 50, 100))
def test_the_served_round_executes_in_the_references_order(rate, read_share):
    """`DeviceDriver.serve` with clients at five sites: what it executes,
    round by round, is the reference's order key by key; its tallies are the
    reference's, the finisher's components counted in."""
    keys = 32
    names = _bucket_keys(keys)
    driver = _driver(128, keys)
    for site in range(N):
        driver.register_site(site)
    assert driver.sites_registered == N and driver.resolver == "key_runs"
    reference = plain.Reference(N)
    rng = np.random.default_rng(5 + rate)
    sent = scc_rows = scc_count = 0
    for r in range(4):
        fill = 128 if r != 2 else 57
        commands = [cmd._replace(key=names[cmd.key]) for cmd in
                    conflict_commands(rng, fill, keys, rate, N, read_share, sent + 1)]
        sent += fill
        values = {cmd.dot: f"{cmd.src}:{cmd.seq}" for cmd in commands}
        got, want = _serve(driver, reference, commands, values)
        by_dot = {cmd.dot: cmd for cmd in commands}
        assert per_key(got, by_dot) == per_key(want.order, by_dot)
        tally = want.tally()
        scc_rows += tally["scc_rows"]
        scc_count += tally["scc_count"]
        if tally["scc_rows_max"]:  # the gauge keeps the last round's that had a component
            assert driver.round_gauges["scc_rows_max"] == tally["scc_rows_max"]
    assert driver.executed == sent and driver.in_flight == 0
    assert driver.round_tallies["scc_rows"] == scc_rows
    assert driver.round_tallies["scc_count"] == scc_count
    assert driver.round_tallies["resolve_iters"] == driver.rounds
    if read_share and rate:
        assert driver.round_tallies["finisher_rows"] > 0
        assert driver.stages.n["finish"] > 0
    if not read_share:
        assert driver.round_tallies["finisher_rows"] == 0 and driver.stages.n["finish"] == 0


@pytest.mark.parametrize("read_share", (0.0, 0.4))
@pytest.mark.parametrize("rate", (50, 100))
def test_the_served_threshold_round_executes_in_the_references_order(rate, read_share):
    """`DeviceDriver.serve` under Atlas's rule at f = 2 after five
    `register_site`s: what it executes, round by round, is the reference's
    order key by key; the slow paths and the threshold's tallies it sums are
    the reference's."""
    keys = 32
    names = _bucket_keys(keys)
    driver = _driver(128, keys, rule="atlas", f=2)
    for site in range(N):
        driver.register_site(site)
    assert driver.sites_registered == N and driver.resolver == "key_runs"
    reference = plain.Reference(N, 1, "atlas", 2)
    rng = np.random.default_rng(11 + rate)
    sent = slow = scc_rows = 0
    sums = dict.fromkeys(THRESHOLD_TALLIES, 0)
    for r in range(4):
        fill = 128 if r != 2 else 57
        commands = [cmd._replace(key=names[cmd.key]) for cmd in
                    conflict_commands(rng, fill, keys, rate, N, read_share, sent + 1)]
        sent += fill
        values = {cmd.dot: f"{cmd.src}:{cmd.seq}" for cmd in commands}
        got, want = _serve(driver, reference, commands, values)
        by_dot = {cmd.dot: cmd for cmd in commands}
        assert per_key(got, by_dot) == per_key(want.order, by_dot)
        tally = want.tally()
        slow += want.slow_paths
        scc_rows += tally["scc_rows"]
        for name in sums:
            sums[name] += tally[name]
        assert {name: driver.round_tallies[name] for name in sums} == sums, r
    assert driver.executed == sent and driver.in_flight == 0
    assert driver.slow_paths == slow and driver.round_tallies["scc_rows"] == scc_rows
    assert 0 < slow == sums["split_quorum_rows"] - sums["threshold_fast_split_rows"]
    assert 0 < sums["threshold_fast_split_rows"]
    if not read_share:
        assert driver.round_tallies["finisher_rows"] == 0


def _names_of_buckets(keys, shards):
    """`{bucket: (shard, key name)}` for the buckets `0 .. keys * shards - 1`:
    bucket `b` is shard `b % shards`'s, and the driver hashes a key's name to
    one of its shard's."""
    names, at = {}, 0
    while len(names) < keys * shards:
        for shard in range(shards):
            names.setdefault(_bucket(shard, str(at), keys * shards, shards), (shard, str(at)))
        at += 1
    return names


@pytest.mark.parametrize("read_share", (0.0, 0.5))
@pytest.mark.parametrize("rule, width, shards", [
    ("atlas", 2, 4), ("epaxos", 2, 4), ("epaxos", 2, 1), ("atlas", 3, 2), ("epaxos", 1, 2),
])
def test_the_served_round_of_several_keys_executes_in_the_references_order(
        rule, width, shards, read_share):
    """`DeviceDriver.serve` under either rule with clients at five sites and
    commands of several keys over several shards: what it executes, round by
    round, is the reference's order key by key, every component together and
    in dot order; its tallies are the reference's."""
    keys, batch = 16, 128
    names = _names_of_buckets(keys, shards)
    driver = DeviceDriver(N, batch_size=batch, key_buckets=keys * shards, key_width=width,
                          shard_count=shards, pending_capacity=batch, mesh=_mesh(), rule=rule)
    assert driver.serves_sites
    for site in range(N):
        driver.register_site(site)
    assert driver.sites_registered == N
    assert driver.resolver == ("key_runs" if width == 1 else "general_components")
    reference = plain.Reference(N, shards, rule)
    rng = np.random.default_rng(3 * width + shards)
    sent, want_tally = 0, dict.fromkeys(
        ("scc_rows", "scc_count", "scc_span_rows", "scc_shard_rows", "cross_shard_executed"), 0)
    for r in range(4):
        fill = batch if r != 2 else 57
        commands = several_keys_commands(rng, fill, keys, shards, width, N, read_share, sent + 1)
        sent += fill
        batch_in = []
        for cmd in commands:
            op = KVOp.get() if cmd.read else KVOp.put(f"{cmd.src}:{cmd.seq}")
            by_shard = {}
            for key in cmd.keys:
                shard, name = names[key]
                by_shard.setdefault(shard, {})[name] = (op,)
            batch_in.append((Dot(cmd.src, cmd.seq), Command(Rifl(cmd.src, cmd.seq), by_shard)))
        by_dot = {cmd.dot: cmd for cmd in commands}
        want = reference.round([by_dot[d.source, d.sequence] for d, _ in _sites_in_turn(batch_in)])
        got = []
        for result in driver.serve([batch_in]):  # a result a key: a command's stand together
            dot = (result.rifl.source, result.rifl.sequence)
            if not got or got[-1] != dot:
                got.append(dot)
        assert sorted(got) == sorted(want.order) and len(got) == fill
        assert per_key(got, by_dot) == per_key(want.order, by_dot)
        place = {dot: at for at, dot in enumerate(got)}
        for component in want.components:
            places = [place[dot] for dot in component]
            assert places == list(range(places[0], places[0] + len(component))), component
            for dot in component:
                for dep in want.verdicts[dot].deps - set(component):
                    assert dep not in place or place[dep] < places[0], (dot, dep)
        for name, count in want.tally().items():
            if name in want_tally:
                want_tally[name] += count
    assert driver.executed == sent and driver.in_flight == 0
    assert driver.round_tallies["finisher_rows"] == 0 or width == 1
    got_tally = {name: driver.round_tallies[name] for name in want_tally}
    if width > 1:
        assert got_tally == want_tally and want_tally["scc_span_rows"] > 0
    assert (driver.slow_paths == 0) == (rule == "atlas")


def _two_hundred_rounds_at_the_cells_shape(rule, f, seed, slow_share):
    """200 rounds at the five-site cells' shape (n=5, 1,048,576 buckets, batch
    and pending 4096) of their traffic (`conflict50_5site_sat`: conflict rate
    50, 8192 clients over five sites, one key a command, writes) through
    `DeviceDriver.serve` under `rule` at `f`: the execution order compared with
    the reference's key by key, the slow paths and the tallies round by round;
    the share of slow rows inside `slow_share`."""
    buckets, batch, clients = 1_048_576, 4096, 8192
    driver = DeviceDriver(N, batch_size=batch, key_buckets=buckets, pending_capacity=batch,
                          rule=rule, f=f)
    for site in range(N):
        driver.register_site(site)
    reference = plain.Reference(N, 1, rule, f)
    rng = np.random.default_rng(seed)
    seqs = [0] * N
    next_of = {}  # a client's writes so far
    scc_rows = executed = slow = 0
    sums = dict.fromkeys(THRESHOLD_TALLIES, 0)
    for r in range(200):
        fill = batch if r % 7 else int(rng.integers(1, batch))
        commands, batch_in = [], []
        for client in rng.permutation(clients)[:fill].tolist():
            client += 1
            site = (client - 1) % N  # kv_sites: process p holds clients 1 + p, 6 + p, ...
            seqs[site] += 1
            name = "0" if rng.integers(0, 100) < 50 else str(client)
            cmd = plain.Command(1 + site, seqs[site], _bucket(0, name, buckets, 1), False, site)
            commands.append(cmd)
            next_of[client] = next_of.get(client, 0) + 1
            batch_in.append((Dot(cmd.src, cmd.seq), Command.from_single(
                Rifl(client, next_of[client]), 0, name, KVOp.put(f"{client}:{next_of[client]}"))))
        by_dot = {cmd.dot: cmd for cmd in commands}
        want = reference.round([by_dot[d.source, d.sequence] for d, _ in _sites_in_turn(batch_in)])
        rifl_of = {(c.rifl.source, c.rifl.sequence): d for d, c in batch_in}
        got = [rifl_of[r_.rifl.source, r_.rifl.sequence] for r_ in driver.serve([batch_in])]
        got = [(d.source, d.sequence) for d in got]
        assert per_key(got, by_dot) == per_key(want.order, by_dot), r
        assert len(got) == len(want.order) == fill
        tally = want.tally()
        scc_rows += tally["scc_rows"]
        executed += fill
        slow += want.slow_paths
        for name in sums:
            sums[name] += tally[name]
        assert driver.slow_paths == slow, r
        assert {name: driver.round_tallies[name] for name in sums} == sums, r
        if tally["scc_rows_max"]:
            assert driver.round_gauges["scc_rows_max"] == tally["scc_rows_max"], r
    assert driver.round_tallies["scc_rows"] == scc_rows and driver.executed == executed
    assert driver.round_tallies["finisher_rows"] == 0
    low, high = slow_share
    assert low * executed < driver.slow_paths < high * executed
    print(f"200 rounds under {rule} at f = {f}, {executed} commands, scc_rows {scc_rows}, "
          f"slow_paths {driver.slow_paths}, {sums}, on {jax.default_backend()}")


@pytest.mark.slow
def test_two_hundred_rounds_at_the_cells_shape_agree_with_the_reference():
    """On the chip, by hand, outside pytest (`tests/conftest.py` holds pytest
    to the CPU): `chiprun -- python3 -c "from tests.test_sites_reference import
    test_two_hundred_rounds_at_the_cells_shape_agree_with_the_reference as t;
    t()"`; under pytest (`-m slow`) it runs on the CPU, two minutes.  The
    shape and traffic of `epaxos_n5_1m_5site.conflict50_sat`."""
    # key 0 is half the rows; taken in turn, nearly every one of them finds its quorum split
    _two_hundred_rounds_at_the_cells_shape("epaxos", 1, 46, (0.4, 0.55))


@pytest.mark.slow
def test_two_hundred_rounds_at_the_atlas_f2_cells_shape_agree_with_the_reference():
    """As the case above, by hand on the chip: `chiprun -- python3 -c "from
    tests.test_sites_reference import
    test_two_hundred_rounds_at_the_atlas_f2_cells_shape_agree_with_the_reference
    as t; t()"`.  The shape and traffic of `atlas_n5_f2_1m_5site.conflict50_sat`:
    Atlas's rule at f = 2, a ring of four.  How many split quorums the
    threshold keeps fast hangs on the order a round takes its sites in
    (`_sites_in_turn`: the order they first appear in the batch): a row's
    predecessor in turn is reported by every member but the one at its own
    site, which has the row before that.  The reference, alone, at this mix
    reads 17.8% of the commands slow where the turn is the ring's order (the
    predecessor's site is the one replica outside the coordinator's ring),
    41.8% where it is the reverse, 32.4% where it is drawn anew each round, as
    here (30.2% over these 200 rounds); under EPaxos's rule 48-50% whatever
    the order.  The band is that range."""
    _two_hundred_rounds_at_the_cells_shape("atlas", 2, 55, (0.17, 0.42))


@pytest.mark.slow
def test_two_hundred_rounds_at_the_several_keys_cells_shape_agree_with_the_reference():
    """On the chip, by hand, outside pytest, as the case above: `chiprun --
    python3 -c "from tests.test_sites_reference import
    test_two_hundred_rounds_at_the_several_keys_cells_shape_agree_with_the_reference
    as t; t()"`.  200 rounds at the shape of
    `atlas_n5_4shard_2key_5site.ycsbt_w50_zipf07_5site_sat` (Atlas's rule, f =
    1, 4 shards x n=5, 4,194,304 buckets, key width 2, batch and pending 4096)
    of that cell's traffic (two distinct zipf-0.7 keys over 4 x 1,000,000, half
    the commands reads, 8192 clients over five sites) through
    `DeviceDriver.serve`: the execution order compared with the reference's key
    by key, every component together and in dot order, the components' tallies
    round by round."""
    from fantoch_tpu.utils import key_hash

    buckets, batch, clients, shards, total = 4_194_304, 4096, 8192, 4, 4_000_000
    driver = DeviceDriver(N, batch_size=batch, key_buckets=buckets, key_width=2, shard_count=shards,
                          pending_capacity=batch, rule="atlas")
    for site in range(N):
        driver.register_site(site)
    reference = plain.Reference(N, shards, "atlas")
    rng = np.random.default_rng(49)
    cdf = np.cumsum(np.arange(1, total + 1, dtype=np.float64) ** -0.7)
    cdf /= cdf[-1]
    seqs = [0] * N
    next_of = {}
    names = ("scc_rows", "scc_count", "scc_span_rows", "scc_shard_rows", "cross_shard_executed")
    sums, executed, largest = dict.fromkeys(names, 0), 0, 0
    for r in range(200):
        fill = batch if r % 7 else int(rng.integers(1, batch))
        ranks = 1 + np.searchsorted(cdf, rng.random((fill, 3)))
        commands, batch_in = [], []
        for client, drawn in zip(rng.permutation(clients)[:fill].tolist(), ranks.tolist()):
            client += 1
            site = (client - 1) % N  # kv_multi_sites: process p holds clients 1 + p, 6 + p, ...
            seqs[site] += 1
            chosen = list(dict.fromkeys(drawn))[:2]
            if len(chosen) < 2:
                chosen.append(chosen[0] % total + 1)
            read = bool(rng.random() < 0.5)
            next_of[client] = next_of.get(client, 0) + 1
            op = (KVOp.get() if read else KVOp.put(f"{client}:{next_of[client]}"),)
            by_shard, found = {}, []
            for key in chosen:
                shard = key_hash(str(key)) % shards
                by_shard.setdefault(shard, {})[str(key)] = op
                found.append(_bucket(shard, str(key), buckets, shards))
            if found[0] == found[1]:  # two keys of one bucket: the driver's row holds it once
                found = found[:1]
            cmd = plain.Command(1 + site, seqs[site], tuple(sorted(found)), read, site)
            commands.append(cmd)
            batch_in.append((Dot(cmd.src, cmd.seq), Command(Rifl(client, next_of[client]), by_shard)))
        by_dot = {cmd.dot: cmd for cmd in commands}
        want = reference.round([by_dot[d.source, d.sequence] for d, _ in _sites_in_turn(batch_in)])
        dot_of = {(c.rifl.source, c.rifl.sequence): (d.source, d.sequence) for d, c in batch_in}
        got = []
        for result in driver.serve([batch_in]):
            dot = dot_of[result.rifl.source, result.rifl.sequence]
            if not got or got[-1] != dot:
                got.append(dot)
        assert len(got) == len(want.order) == fill, r
        assert per_key(got, by_dot) == per_key(want.order, by_dot), r
        place = {dot: at for at, dot in enumerate(got)}
        for component in want.components:
            if len(component) > 1:
                places = [place[dot] for dot in component]
                assert places == list(range(places[0], places[0] + len(component))), (r, component)
        tally = want.tally()
        for name in names:
            sums[name] += tally[name]
        executed += fill
        largest = max(largest, tally["scc_rows_max"])
        if tally["scc_rows_max"]:
            assert driver.round_gauges["scc_rows_max"] == tally["scc_rows_max"], r
    assert {name: driver.round_tallies[name] for name in names} == sums
    assert driver.executed == executed and driver.slow_paths == 0
    assert driver.round_tallies["finisher_rows"] == 0
    assert 0.03 * executed < sums["scc_span_rows"] <= sums["scc_rows"] < 0.3 * executed
    print(f"200 rounds, {executed} commands, {sums}, largest component {largest}, "
          f"resolve_iters {driver.round_tallies['resolve_iters']}, finisher_rows 0, "
          f"on {jax.default_backend()}")
