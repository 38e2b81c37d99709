"""`CaesarDeviceDriver` against the plain reference (`tests/caesar_reference.py`)
at the benchmark cell's shape in small: n=7 (fast quorum 6, write quorum 4),
64 buckets, batch 32, pending 32, one key a command, a seeded stream of 600
writes of which half go to one hot key and the rest to their client's own,
in rounds of one full batch and of ragged sizes.  Driver and reference are
given the same rounds (what a round could not carry first, then new
commands); every command's committed clock, fast / slow verdict, executed or
carried, the execution order, every returned value and the final store are
compared exactly: integers and strings, no tolerance.

Three liveness cases: seven live (the cell's: all fast); five live (a stale
replica sits in the fast quorum, so a key's commands after its first round
commit on the retry); three live (those retries find no write quorum and
stay unanswered, carried or handed back under their dots, until a round
with seven live commits them).  At key width 2 the round is held to the
contract only (the reference file says why)."""

import random

import numpy as np
import pytest

from fantoch_tpu.core.command import Command
from fantoch_tpu.core.ids import Dot, Rifl
from fantoch_tpu.core.kvs import KVOp
from fantoch_tpu.parallel import mesh_step
from fantoch_tpu.run.device_drivers import CaesarDeviceDriver, _bucket
from tests import caesar_reference as ref

N, BUCKETS, BATCH, PENDING, CLIENTS, COMMANDS, SEED = 7, 64, 32, 32, 24, 600, 34


def distinct_keys(count):
    """Key names that fall in ``count`` different buckets, so that a key of
    the reference is a bucket of the device."""
    names, taken = [], set()
    for number in range(10 * BUCKETS):
        bucket = _bucket(0, f"k{number}", BUCKETS, 1)
        if bucket not in taken:
            taken.add(bucket)
            names.append(f"k{number}")
        if len(names) == count:
            return names
    raise AssertionError("not enough buckets")


def stream(seed=SEED, commands=COMMANDS):
    """``commands`` writes in submission order: the hot key with probability
    one half, else the client's own; a command's dot is (client, its n-th)."""
    rng = random.Random(seed)
    hot, *own = distinct_keys(CLIENTS + 1)
    sent = dict.fromkeys(range(1, CLIENTS + 1), 0)
    out = []
    for number in range(commands):
        client = rng.randrange(1, CLIENTS + 1)
        sent[client] += 1
        key = hot if rng.random() < 0.5 else own[client - 1]
        out.append(ref.Command(client, sent[client], (key,), f"{client}:{sent[client]}:{number}"))
    return hot, out


def to_program(cmd):
    rifl = Rifl(cmd.src, cmd.seq)
    return Dot(cmd.src, cmd.seq), Command.from_single(rifl, 0, cmd.keys[0], KVOp.put(cmd.value))


class Pair:
    """The driver and the reference, stepped together and compared."""

    def __init__(self, live):
        self.driver = CaesarDeviceDriver(N, batch_size=BATCH, key_buckets=BUCKETS,
                                         pending_capacity=PENDING, live_replicas=live)
        self.reference = ref.Reference(N, PENDING, live)
        self.outs = []
        sound = self.driver._execute

        def keep_the_rounds_output(tok, out):
            self.outs.append(out)
            return sound(tok, out)

        self.driver._execute = keep_the_rounds_output
        self.sent = {}  # dot -> the command, as the reference takes it
        self.answered = {}  # dot -> what the driver returned for it
        self.rounds = []  # the reference's, for the cases' own questions

    def set_live(self, live):
        self.driver._programs[1] = self.driver._precompile(
            mesh_step.jit_caesar_step(self.driver._mesh, num_replicas=N, live_replicas=live))
        self.reference.live = N if live is None else live

    def round(self, fresh):
        """One round of both over what the last one handed back and
        ``fresh``; returns the reference's round after comparing."""
        again = [self.sent[dot.source, dot.sequence] for dot, _ in self.driver.take_requeue()]
        assert again == self.handed_back, "the driver hands back what the reference does"
        batch = again + list(fresh)
        assert len(batch) <= BATCH
        self.sent.update((cmd.dot, cmd) for cmd in fresh)
        results = self.driver.step([to_program(cmd) for cmd in batch])
        want = self.reference.round(batch)
        out = self.outs[-1]

        # command by command, over the whole working set
        src, seq = np.asarray(out.work_src), np.asarray(out.work_seq)
        rows = {(int(src[w]), int(seq[w])): w for w in range(len(src)) if (src[w], seq[w]) != (0, 0)}
        assert set(rows) == set(want.verdicts)
        clock, committed = np.asarray(out.clock), np.asarray(out.committed)
        fast, executed = np.asarray(out.fast_path), np.asarray(out.executed)
        for dot, verdict in want.verdicts.items():
            w = rows[dot]
            got = (int(clock[w]) if committed[w] else None, bool(committed[w]), bool(fast[w]),
                   bool(executed[w]))
            assert got == verdict[:4], (dot, got, verdict)
        in_order = [(int(src[w]), int(seq[w])) for w in np.asarray(out.order).tolist() if executed[w]]
        assert in_order == want.order
        assert int(out.slow_paths) == want.slow_paths and int(out.watermark) == want.watermark
        # what the driver returned, in that order, is what the dict returned
        assert [(r.rifl.source, r.rifl.sequence) for r in results] == want.order
        for result in results:
            dot = (result.rifl.source, result.rifl.sequence)
            assert dot not in self.answered and result.op_results == want.verdicts[dot].returned
            self.answered[dot] = result.op_results
        self.rounds.append(want)
        return want

    @property
    def handed_back(self):
        return self.rounds[-1].resubmit if self.rounds else []

    def run(self, commands, sizes, rounds=10**6):
        """``commands`` in rounds of ``sizes`` (cycled), less what a round
        has to take back first, for at most ``rounds`` rounds: returns the
        commands not sent by then.  Given no limit, rounds of nothing new
        follow until nothing is left in flight."""
        at = 0
        for turn in range(rounds):
            if at >= len(commands):
                break
            room = max(0, min(sizes[turn % len(sizes)], BATCH - len(self.handed_back)))
            self.round(commands[at:at + room])
            at += room
        else:
            return commands[at:]
        for _ in range(50):
            if not (self.driver.in_flight or self.driver.has_requeue or self.handed_back):
                return
            self.round([])
        raise AssertionError("the backlog did not drain")

    def assert_the_same_end(self, commands):
        reference, driver = self.reference, self.driver
        assert driver.store._store == reference.store and set(self.sent) == {c.dot for c in commands}
        assert set(self.answered) == {cmd.dot for cmd in commands}  # each once
        assert driver.executed == len(commands) and driver.in_flight == 0
        # one chain a key: each write returned the value of the one before it
        by_dot = {cmd.dot: cmd for cmd in commands}
        for key, dots in reference.executed.items():
            values = [by_dot[dot].value for dot in dots]
            assert [self.answered[dot] for dot in dots] == [(v,) for v in [None] + values[:-1]]
        # every replica's clock on every key, the stale replicas' too
        table = np.asarray(driver._state.key_clock)
        keys = sorted(reference.executed)
        buckets = [_bucket(0, key, BUCKETS, 1) for key in keys]
        assert table[:, buckets].tolist() == [[clock.get(key, 0) for key in keys]
                                              for clock in reference.clock]
        assert table.sum() == table[:, buckets].sum()  # and nothing anywhere else

    def new_commands_by_key_age(self):
        """For each round, its new commands (not those it was handed again
        or had carried) on a key no earlier round had seen, and those on a
        key one had: ``(on_a_new_key, on_a_known_key)`` lists of verdicts."""
        keys_seen, dots_seen, out = set(), set(), []
        for want in self.rounds:
            new = [dot for dot in want.verdicts if dot not in dots_seen]
            out.append(([want.verdicts[d] for d in new if self.sent[d].keys[0] not in keys_seen],
                        [want.verdicts[d] for d in new if self.sent[d].keys[0] in keys_seen]))
            dots_seen.update(new)
            keys_seen.update(self.sent[d].keys[0] for d in new)
        return out


SIZES = {"one_full_batch_a_round": [BATCH], "ragged_rounds": [7, 32, 1, 19, 26, 3, 32, 11]}
by_sizes = pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())


@by_sizes
def test_seven_live_every_command_is_fast_and_the_hot_key_takes_one_tick_a_command(sizes):
    hot, commands = stream()
    pair = Pair(None)
    pair.run(commands, sizes)
    pair.assert_the_same_end(commands)
    on_hot = 0
    for want in pair.rounds:
        assert all(v.fast and v.executed for v in want.verdicts.values()) and not want.resubmit
        hot_now = sum(pair.sent[dot].keys == (hot,) for dot in want.verdicts)
        on_hot += hot_now
        if hot_now:  # the highest clock executed is the hot key's: one tick a command on it
            assert want.watermark == on_hot
    driver = pair.driver
    assert driver.fast_paths == len(commands) and driver.slow_paths == 0
    assert 0.4 < on_hot / len(commands) < 0.6 and driver.stable_watermark == on_hot
    assert all(clock == pair.reference.clock[0] for clock in pair.reference.clock)


@by_sizes
def test_five_live_a_keys_later_rounds_commit_on_the_retry_and_everything_is_answered(sizes):
    _, commands = stream()
    pair = Pair(5)
    pair.run(commands, sizes)
    pair.assert_the_same_end(commands)
    for want, (on_a_new_key, on_a_known_key) in zip(pair.rounds, pair.new_commands_by_key_age()):
        assert all(v.committed and v.executed for v in want.verdicts.values())  # all answered at once
        assert all(v.fast for v in on_a_new_key)  # every clock still agrees there
        # replica 5 is in the fast quorum and learnt nothing: it diverges on a key seen before
        assert not any(v.fast for v in on_a_known_key)
    driver = pair.driver
    assert driver.slow_paths == sum(want.slow_paths for want in pair.rounds) > 400
    assert driver.fast_paths + driver.slow_paths == len(commands)
    assert not pair.reference.clock[5] and not pair.reference.clock[6]  # a stale replica learns nothing


@by_sizes
def test_three_live_retries_find_no_write_quorum_until_seven_are_live_again(sizes):
    _, commands = stream()
    pair = Pair(3)
    rest = pair.run(commands, sizes, rounds=8)
    degraded = commands[: len(commands) - len(rest)]
    # a key's first round still agrees (all clocks equal) and commits fast; after it the
    # three stale replicas of the fast quorum diverge and four are not live to accept a retry
    for on_a_new_key, on_a_known_key in pair.new_commands_by_key_age():
        assert all(v.fast and v.executed for v in on_a_new_key)
        assert not any(v.committed or v.executed for v in on_a_known_key)
    stuck = {dot for want in pair.rounds for dot, v in want.verdicts.items() if not v.committed}
    assert stuck and not stuck & set(pair.answered)  # not committed, not answered
    assert set(pair.answered) | stuck == {cmd.dot for cmd in degraded}
    assert any(want.resubmit for want in pair.rounds)  # beyond the pending capacity: handed back
    assert len(pair.reference.carried) == PENDING
    assert pair.driver.in_flight + len(pair.handed_back) == len(stuck)

    pair.set_live(None)
    recovered_at = len(pair.rounds)
    pair.run(rest, sizes)
    pair.assert_the_same_end(commands)
    recovery = pair.rounds[recovered_at]
    carried = [dot for dot in recovery.order if dot in stuck]
    assert len(carried) >= PENDING  # what was carried commits on the retry ...
    assert all(recovery.verdicts[dot].committed and not recovery.verdicts[dot].fast for dot in carried)
    clocks = [recovery.verdicts[dot].clock for dot in recovery.order]
    assert clocks == sorted(clocks)  # ... and is answered in clock order
    assert stuck <= set(pair.answered)
    assert pair.rounds[-1].slow_paths == 0  # seven learnt the same again: the fast path is back


# --- key width 2: the contract, not the reference ---------------------------------


@pytest.mark.parametrize("live", [None, 5], ids=["seven_live", "five_live"])
def test_at_key_width_two_a_command_keeps_its_clock_and_two_commands_have_one_order_on_every_key_they_share(live):
    rng = random.Random(SEED + 2)
    keys = distinct_keys(12)
    driver = CaesarDeviceDriver(N, batch_size=BATCH, key_buckets=BUCKETS, key_width=2,
                                pending_capacity=PENDING, live_replicas=live,
                                monitor_execution_order=True)
    outs = []
    sound = driver._execute
    driver._execute = lambda tok, out: (outs.append(out), sound(tok, out))[1]
    commands, answered = {}, {}
    for number in range(1, 301):
        first = rng.choice(keys[:3]) if rng.random() < 0.5 else rng.choice(keys)
        touched = {first} if rng.random() < 0.3 else {first, rng.choice(keys)}
        commands[number] = touched
    numbers = list(commands)
    for start in range(0, len(numbers), BATCH):
        batch = [(Dot(1, n), Command.from_keys(Rifl(1, n), 0, {
            key: (KVOp.put(f"{n}:{key}"),) for key in sorted(commands[n])}))
            for n in numbers[start:start + BATCH]]
        for result in driver.step(batch):
            answered.setdefault(result.rifl.sequence, {})[result.key] = result.op_results[0]
    assert driver.in_flight == 0 and set(answered) == set(commands)
    # a command keeps the clock it committed at, so (clock, dot) is one place in one order
    clock_of = {}
    for out in outs:
        seq, clock, committed = (np.asarray(a) for a in (out.work_seq, out.clock, out.committed))
        for w in np.flatnonzero(committed & (np.asarray(out.work_src) == 1)).tolist():
            assert clock_of.setdefault(int(seq[w]), int(clock[w])) == int(clock[w])
    assert set(clock_of) == set(commands)
    # on every key, execution follows (clock, dot), each write returning the one before it
    for key in keys:
        order = [rifl.sequence for rifl in driver.store.monitor.get_order(key)]
        assert sorted(order) == sorted(n for n, touched in commands.items() if key in touched)
        assert order == sorted(order, key=lambda n: (clock_of[n], n))
        assert [answered[n][key] for n in order] == [None] + [f"{n}:{key}" for n in order[:-1]]
    # so two commands that share two keys come in the same order on both
    place = {key: {rifl.sequence: at for at, rifl in enumerate(driver.store.monitor.get_order(key))}
             for key in keys}
    pairs = 0
    for a in commands:
        for b in commands:
            shared = sorted(commands[a] & commands[b])
            if a < b and len(shared) == 2:
                pairs += 1
                assert (place[shared[0]][a] < place[shared[0]][b]) == (place[shared[1]][a] < place[shared[1]][b])
    assert pairs > 10
