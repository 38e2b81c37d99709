"""The one entry a driver runs rounds through (`PipelineCore.serve`, PR 44),
held to the synchronous `step` on every driver of the served path: the four
drivers x `overlap` x a chain of 1, 2 and 4 rounds.  (Its own file, so that
`--dist loadfile` does not lengthen `tests/test_device_runner.py`'s worker.)"""

import pytest

from tests.test_device_runner import DRAIN_DRIVERS, _flat, _puts


@pytest.mark.parametrize("length", [1, 2, 4])
@pytest.mark.parametrize("overlap", [False, True], ids=["drained", "overlap"])
@pytest.mark.parametrize("protocol", DRAIN_DRIVERS)
def test_serve_is_that_many_steps_in_order(protocol, overlap, length):
    """The one entry, every driver: ``serve`` of a chain gives, in order,
    what that many ``step``s give a twin driver, each call its own rounds'
    where it drains at once and the tail with the flush under overlap; a
    chain is one dispatch where the driver fuses it (Newt), a dispatch a
    round elsewhere, and counts its rounds either way."""
    cls, _walk, n, extra = DRAIN_DRIVERS[protocol]
    kw = {"batch_size": 8, "key_buckets": 64, "monitor_execution_order": True, **extra}
    served, stepped = cls(n, **kw), cls(n, **kw)
    got, want, seq = [], [], 0
    for fill in (8, 5, 8):
        chain = []
        for _round in range(length):
            chain.append(_puts(range(seq + 1, seq + 1 + fill)))
            seq += fill
        call = served.serve([mine for mine, _ in chain], overlap=overlap)
        steps = [r for _, theirs in chain for r in stepped.step(theirs)]
        if not overlap:
            assert _flat(call) == _flat(steps) and not served.has_outstanding
        got += call
        want += steps
    assert served.has_outstanding == overlap
    got += served.flush_pipeline()
    assert _flat(got) == _flat(want) and len(got) == seq
    assert served.executed == stepped.executed == seq and served.in_flight == 0
    assert served.rounds == stepped.rounds == 3 * length
    fused = length if served.fuses_chains else 1
    assert served.dispatches * fused == stepped.dispatches == 3 * length
    assert served.device_counters()["serving_chain_len"] == fused
    for key in stepped.store.monitor.keys():
        assert served.store.monitor.get_order(key) == stepped.store.monitor.get_order(key)


# --- a round's executed commands applied in one pass (PR 48) ---


def _seeded_batches(protocol, seed, batch_size):
    """Rounds of one- and two-key commands over a few keys (two shards where
    the driver has them), full and part-full: ``(dot, command)`` lists."""
    import random

    from fantoch_tpu.core import Command, Dot, KVOp, Rifl
    from fantoch_tpu.utils import key_hash

    rng = random.Random(seed)
    shards = 2 if protocol in ("epaxos", "newt") else 1
    batches, seq = [], 0
    for fill in (batch_size, 5, batch_size, 1, 3):
        batch = []
        for _ in range(fill):
            seq += 1
            read = rng.random() < 0.4
            by_shard = {}
            for key in rng.sample([f"k{i}" for i in range(6)], rng.choice((1, 2))):
                op = KVOp.get() if read else (
                    KVOp.delete() if rng.random() < 0.2 else KVOp.put(f"v{seq}"))
                by_shard.setdefault(key_hash(key) % shards, {})[key] = (op,)
            batch.append((Dot(1, seq), Command(Rifl(1, seq), by_shard)))
        batches.append(batch)
    return batches


class _PerCommand:
    """Mixed in ahead of a driver: a method of its own where the pass spells
    out ``_execute_entry``, so its drains run the per-command loop."""

    def _execute_entry(self, cmd):
        return super()._execute_entry(cmd)


@pytest.mark.parametrize("protocol", DRAIN_DRIVERS)
def test_serve_applies_a_round_in_one_pass_as_the_per_command_loop_does(protocol):
    """``serve`` of seeded batches, two-key and part-full rounds among them:
    the results and the store are those of replaying the returned order
    through ``Command.execute``, and the tallies read what a twin that runs
    the per-command loop counted."""
    from fantoch_tpu.core.kvs import KVStore

    cls, _walk, n, extra = DRAIN_DRIVERS[protocol]
    shards = {"shard_count": 2} if protocol in ("epaxos", "newt") else {}
    kw = {"batch_size": 8, "key_buckets": 64, **extra, **shards}
    if protocol != "fpaxos":  # the slot log takes a command of any width
        kw["key_width"] = 2
    passed, looped = cls(n, **kw), type("Looped", (_PerCommand, cls), {})(n, **kw)
    batches = _seeded_batches(protocol, 48, 8)
    cmds = {cmd.rifl: cmd for batch in batches for _dot, cmd in batch}
    got, want = [], []
    for at in range(0, len(batches), 2):
        got += passed.serve(batches[at: at + 2], overlap=True)
        want += looped.serve(batches[at: at + 2], overlap=True)
    got += passed.flush_pipeline()
    want += looped.flush_pipeline()
    assert got == want and passed.store._store == looped.store._store
    for tally in ("executed", "fast_paths", "slow_paths", "drain_rows_walked", "rounds"):
        assert getattr(passed, tally) == getattr(looped, tally), tally
    assert passed.executed == passed.executed_in_pass == len(cmds) and passed.in_flight == 0
    assert looped.executed_in_pass == 0
    # the returned order, replayed through the plain definition
    replay, order = KVStore(), list(dict.fromkeys(r.rifl for r in got))
    assert len(order) == len(cmds)
    assert got == [r for rifl in order for shard in cmds[rifl].shards()
                   for r in cmds[rifl].execute(shard, replay)]
    assert replay._store == passed.store._store


# --- a served command is what its frame carried (PR 50) ---


def _off_the_wire(batches):
    """The same rounds with every command as the server's way in gives it:
    restored from its frame, born with the frame's tuple and no dicts."""
    from fantoch_tpu.run import rw
    from fantoch_tpu.run.prelude import Submit

    return [[(dot, rw.deserialize(rw.serialize(Submit(cmd))).cmd) for dot, cmd in batch]
            for batch in batches]


@pytest.mark.parametrize("protocol", DRAIN_DRIVERS)
def test_rounds_of_commands_off_the_wire_are_served_from_their_tuples_alone(protocol):
    """The seeded rounds, one- and two-key, in each form: the same results,
    store and tallies; ``executed_off_wire`` counts every command of the
    driver fed from frames and none of the one stepped with commands the
    constructor made; and a command off the wire has been asked for its
    dicts by nothing from the key column to the store (a per-command twin
    asks every one)."""
    from tests.test_command_forms import has_dicts

    cls, _walk, n, extra = DRAIN_DRIVERS[protocol]
    shards = {"shard_count": 2} if protocol in ("epaxos", "newt") else {}
    kw = {"batch_size": 8, "key_buckets": 64, **extra, **shards}
    if protocol != "fpaxos":
        kw["key_width"] = 2
    built, framed = _seeded_batches(protocol, 50, 8), _off_the_wire(_seeded_batches(protocol, 50, 8))
    by_hand, served, looped = cls(n, **kw), cls(n, **kw), type("Looped", (_PerCommand, cls), {})(n, **kw)
    relooped = _off_the_wire(built)
    got, want, loop = [], [], []
    for at in range(0, len(built), 2):
        want += by_hand.serve(built[at: at + 2], overlap=True)
        got += served.serve(framed[at: at + 2], overlap=True)
        loop += looped.serve(relooped[at: at + 2], overlap=True)
    want += by_hand.flush_pipeline()
    got += served.flush_pipeline()
    loop += looped.flush_pipeline()
    assert got == want == loop and served.store._store == by_hand.store._store == looped.store._store
    count = sum(len(batch) for batch in built)
    for tally in ("executed", "executed_in_pass", "fast_paths", "slow_paths", "rounds"):
        assert getattr(served, tally) == getattr(by_hand, tally), tally
    assert served.executed_off_wire == served.executed_in_pass == served.executed == count
    assert by_hand.executed_off_wire == 0 and by_hand.executed_in_pass == count
    assert (looped.executed, looped.executed_in_pass, looped.executed_off_wire) == (count, 0, 0)
    assert not any(has_dicts(cmd) for batch in framed for _dot, cmd in batch)
    assert all(has_dicts(cmd) for batch in relooped for _dot, cmd in batch)
