"""The served path against the plain reference (`tests/ycsb_reference.py`):
a seeded YCSB stream (a load, then 95% reads and 5% whole-record updates,
half of them on three hot keys) through the dep-commit round on the CPU at a
small shape, in one batch a round and in several.  Every command returns
exactly what the reference returns when the stream is replayed in the order
the rounds executed it (`StepOutput.order`), and the reply stage's three
counters of PR 32 count what the stream held."""

import numpy as np
import pytest

from fantoch_tpu.core.command import Command
from fantoch_tpu.core.config import Config
from fantoch_tpu.core.ids import Dot, Rifl
from fantoch_tpu.core.kvs import KVOp, KVStore
from fantoch_tpu.run.device_runner import DeviceRuntime
from fantoch_tpu.run.device_session import _DeviceClientSession
from fantoch_tpu.run.rw import Rw
from tests import ycsb_reference as ref
from tests.test_device_runner import _CountingWriter, _frames

SEED, KEYS, OPERATIONS, RECORD_BYTES, BATCH = 32, 12, 500, 1000, 64


def served(per_step):
    """The stream through one runtime's driver and reply stage,
    ``per_step`` commands a round.  Returns the commands in the order the
    rounds executed them with what each returned, the replies the
    connection was written, and the runtime."""
    runtime = DeviceRuntime(Config(3, 1), ("127.0.0.1", 0), batch_size=BATCH, key_buckets=256,
                            pending_capacity=BATCH)
    driver = runtime.driver
    writer = _CountingWriter()
    session = _DeviceClientSession(runtime, Rw(None, writer))
    ops = ref.stream(SEED, OPERATIONS, KEYS, RECORD_BYTES)
    commands = {}
    for number, (kind, key, value) in enumerate(ops, start=1):
        op = KVOp.get() if kind == ref.READ else KVOp.put(value)
        commands[Rifl(1, number)] = (kind, key, value,
                                     Command.from_single(Rifl(1, number), 0, key, op))

    executed = []  # (rifl, what the store returned), in the rounds' order
    sound_execute = driver._execute

    def execute_in_the_rounds_order(tok, out):
        # the order the device gave this round's rows, read here and not
        # from what the driver makes of it
        gids, resolved = np.asarray(out.gids), np.asarray(out.resolved)
        rows = [w for w in np.asarray(out.order).tolist() if gids[w] >= 0 and resolved[w]]
        ordered = [driver._cmds[int(gids[w])][1].rifl for w in rows if int(gids[w]) in driver._cmds]
        results = sound_execute(tok, out)
        assert [result.rifl for result in results] == ordered
        executed.extend((result.rifl, result.op_results) for result in results)
        return results

    driver._execute = execute_in_the_rounds_order
    rifls = list(commands)
    for start in range(0, len(rifls), per_step):
        batch = []
        for rifl in rifls[start:start + per_step]:
            cmd = commands[rifl][3]
            session.track(cmd)
            runtime.rifl_sessions[rifl] = session
            batch.append((Dot(1, rifl.sequence), cmd))
        runtime._deliver(driver.step(batch))
    while driver.in_flight:
        runtime._deliver(driver.step([]))
    replies = [frame.cmd_result for data in writer.writes for frame in _frames(data)]
    assert not session._reads and not runtime.rifl_sessions  # nothing of a read is left behind
    return commands, executed, replies, runtime


@pytest.mark.parametrize("per_step", [BATCH, 7], ids=["one_full_batch_a_round", "seven_a_round"])
def test_every_command_returns_what_the_reference_returns_in_the_executed_order(per_step):
    commands, executed, replies, runtime = served(per_step)
    assert sorted(rifl for rifl, _ in executed) == sorted(commands)  # each once
    reference = ref.Reference()
    for rifl, returned in executed:
        kind, key, value, _ = commands[rifl]
        assert returned == (reference.apply(kind, key, value),), (rifl, kind, key)
    # a command submitted after another on its key was acknowledged comes after it
    place = {rifl: at for at, (rifl, _) in enumerate(executed)}
    for start in range(per_step, len(commands), per_step):
        earlier, later = Rifl(1, start), Rifl(1, start + 1)
        if commands[earlier][1] == commands[later][1]:
            assert place[earlier] < place[later]
    # the replies on the connection say the same, a reply a command, in that order
    assert [(reply.rifl, reply.results) for reply in replies] == [
        (rifl, {commands[rifl][1]: returned}) for rifl, returned in executed]
    # every read hit a loaded record, whole
    reads = [returned[0] for rifl, returned in executed if commands[rifl][0] == ref.READ]
    assert len(reads) == reference.reads > 0.9 * OPERATIONS
    assert all(len(record.encode()) == RECORD_BYTES for record in reads)

    # the three counters count what the stream held
    runtime._publish_tallies()
    tallies = runtime._tallies
    assert tallies["gets_replied"] == reference.reads
    assert tallies["get_value_bytes"] == reference.read_bytes == RECORD_BYTES * reference.reads
    assert tallies["store_records"] == len(reference.records) == KEYS == len(runtime.driver.store)
    assert tallies["replied"] == tallies["commands_completed"] == len(commands)


def test_a_read_of_a_record_nobody_wrote_carries_nothing_and_counts_no_bytes():
    runtime = DeviceRuntime(Config(3, 1), ("127.0.0.1", 0), batch_size=8, key_buckets=64)
    writer = _CountingWriter()
    session = _DeviceClientSession(runtime, Rw(None, writer))
    batch = []
    for number, (key, op) in enumerate([("a", KVOp.get()), ("a", KVOp.put("é" * 10)),
                                        ("a", KVOp.get()), ("b", KVOp.get())], start=1):
        cmd = Command.from_single(Rifl(1, number), 0, key, op)
        session.track(cmd)
        runtime.rifl_sessions[cmd.rifl] = session
        batch.append((Dot(1, number), cmd))
    runtime._deliver(runtime.driver.step(batch))
    runtime._publish_tallies()
    tallies = runtime._tallies
    assert tallies["gets_replied"] == 3 and tallies["store_records"] == 1
    assert tallies["get_value_bytes"] == 20  # one read of ten two-byte letters: bytes, not letters
    assert not session._reads and len(KVStore()) == 0


def test_the_reference_is_a_dict_one_operation_at_a_time():
    reference = ref.Reference()
    assert reference.apply(ref.READ, "k") is None
    assert reference.apply(ref.UPDATE, "k", "v1") is None
    assert reference.apply(ref.UPDATE, "k", "v2") == "v1"
    assert reference.apply(ref.READ, "k") == "v2"
    assert (reference.reads, reference.read_bytes, reference.records) == (2, 2, {"k": "v2"})
    ops = ref.stream(SEED, OPERATIONS, KEYS, RECORD_BYTES)
    assert ops == ref.stream(SEED, OPERATIONS, KEYS, RECORD_BYTES)
    assert ops != ref.stream(SEED + 1, OPERATIONS, KEYS, RECORD_BYTES)
    load, run = ops[:KEYS], ops[KEYS:]
    assert [key for _, key, _ in load] == [f"user{k}" for k in range(KEYS)]
    assert all(kind == ref.UPDATE and len(value) == RECORD_BYTES for kind, _, value in load)
    share = sum(kind == ref.READ for kind, _, _ in run) / len(run)
    assert 0.92 < share < 0.98
    assert all(len(value) == RECORD_BYTES for kind, _, value in run if kind == ref.UPDATE)
    with open(ref.__file__) as fh:
        source = fh.read()
    assert "import fantoch" not in source and "from fantoch" not in source
