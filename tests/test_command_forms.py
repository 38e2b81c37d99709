"""A command's two forms (core/command.py, PR 50): the dicts its constructor
is given and the tuple of plain values its frame carried.  A command restored
from its frame keeps that tuple as its ops and builds the dicts for the caller
that asks; a command the constructor made gives the tuple at its first use.
Whichever form a command was born with, every answer is the same: equality,
hash, ``__reduce__``, the accessors, ``_buckets`` and its row of the key
column, ``execute`` and the store's one pass, and the frame on the wire, which
is byte for byte what the parent's tree (PR 49's) framed.
"""

import gc
import pickle

import numpy as np
import pytest

from fantoch_tpu.core import Command, KVOp, Rifl
from fantoch_tpu.core.command import _DICTS_SLOT, _WIRE_SLOT, FLAT, _restore_command
from fantoch_tpu.core.ids import Dot
from fantoch_tpu.core.kvs import KVStore
from fantoch_tpu.parallel.mesh_step import KEY_PAD
from fantoch_tpu.run import rw
from fantoch_tpu.run.device_drivers import _buckets, _key_column
from fantoch_tpu.run.prelude import Submit

RIFL = Rifl(2**40 + 7, 2**33)
VALUE = "v" * 100  # the cells' payload

SHAPES = {
    "one_key_put": {0: {"999999": (KVOp.put(VALUE),)}},
    "one_key_get": {0: {"k": (KVOp.get(),)}},
    "one_key_delete": {3: {"k": (KVOp.delete(),)}},
    "non_ascii_key_and_value": {0: {"ключ-鍵-🔑": (KVOp.put("é鍵🔑" * 9),)}},
    "two_keys_two_shards": {2: {"905": (KVOp.get(),)}, 1: {"17": (KVOp.get(),)}},
    "two_keys_one_shard": {0: {"b": (KVOp.put("1"),), "a": (KVOp.put("2"),)}},
    "three_keys_two_shards": {1: {"x": (KVOp.put(VALUE),), "w": (KVOp.delete(),)}, 0: {"y": (KVOp.put(VALUE),)}},
    "two_ops_one_key": {0: {"k": (KVOp.put("1"), KVOp.delete())}},
}
CASES = sorted(SHAPES)

# ``rw.frame(Submit(Command(RIFL, SHAPES[name]))).hex()`` on the parent's tree
# (commit 6a8f9ef, PR 49), before a command kept its frame's tuple
PARENTS_FRAMES = {
    "one_key_put": (
        "00000093018005958700000000000000288a060700000000018a0500000000024b008c06393939393939"
        "944b018c64" + "76" * 100 +
        "9474942e"
    ),
    "one_key_get": (
        "00000028018005951c00000000000000288a060700000000018a0500000000024b008c016b944b004e74942e"
    ),
    "one_key_delete": (
        "00000028018005951c00000000000000288a060700000000018a0500000000024b038c016b944b024e74942e"
    ),
    "non_ascii_key_and_value": (
        "0000008b018005957f00000000000000288a060700000000018a0500000000024b008c11d0bad0bbd18e"
        "d1872de98db52df09f9491944b018c51c3a9e98db5f09f9491c3a9e98db5f09f9491c3a9e98db5f09f94"
        "91c3a9e98db5f09f9491c3a9e98db5f09f9491c3a9e98db5f09f9491c3a9e98db5f09f9491c3a9e98db5"
        "f09f9491c3a9e98db5f09f94919474942e"
    ),
    "two_keys_two_shards": (
        "00000049018005953d000000000000008a060700000000018a0500000000024b028c03393035944b004e"
        "869485948694859486944b018c023137944b004e86948594869485948694869487942e"
    ),
    "two_keys_one_shard": (
        "00000046018005953a000000000000008a060700000000018a0500000000024b008c0162944b018c0131"
        "948694859486948c0161944b018c01329486948594869486948694859487942e"
    ),
    "three_keys_two_shards": (
        "000000ba01800595ae000000000000008a060700000000018a0500000000024b018c0178944b018c64" + "76" * 100 +
        "948694859486948c0177944b024e869485948694869486944b008c0179944b0168018694859486948594"
        "8694869487942e"
    ),
    "two_ops_one_key": (
        "0000003b018005952f000000000000008a060700000000018a0500000000024b008c016b944b018c0131"
        "9486944b024e86948694869485948694859487942e"
    ),
}


def _built(name, rifl=RIFL):
    """The command as the constructor makes it: born with the dicts."""
    return Command(rifl, {shard: dict(ops) for shard, ops in SHAPES[name].items()})


def _restored(name, rifl=RIFL):
    """The command as the server's way in gives it: born with its frame's tuple."""
    payload = rw.serialize(Submit(_built(name, rifl)))
    return rw.deserialize(payload).cmd


def _has(cmd, slot):
    try:
        slot.__get__(cmd)
    except AttributeError:
        return False
    return True


def has_dicts(cmd):
    """Has anything asked ``cmd`` for its dict form yet (read through the
    slot, which builds nothing)?"""
    return _has(cmd, _DICTS_SLOT)


def _shards_touched(name):
    return list(SHAPES[name])


@pytest.mark.parametrize("name", CASES)
def test_each_is_born_with_one_form_and_builds_the_other_for_the_caller_that_asks(name):
    built, restored = _built(name), _restored(name)
    assert (_has(built, _DICTS_SLOT), _has(built, _WIRE_SLOT)) == (True, False)
    assert (_has(restored, _DICTS_SLOT), _has(restored, _WIRE_SLOT)) == (False, True)
    assert (built._off_wire, restored._off_wire) == (False, True)
    # a command off the wire holds plain values alone: no dict, no KVOp
    held = gc.get_referents(restored)
    assert not any(isinstance(x, (dict, KVOp)) for x in held)
    assert any(x is restored._wire for x in held) and type(restored._wire) is tuple
    # the tuple is the one the frame unpickled to, flat where it has one key and one op
    assert restored._wire == pickle.loads(rw.serialize(Submit(built))[1:])
    assert (len(restored._wire) == FLAT) == (built.single_key() is not None and name != "two_ops_one_key")
    # what neither was born with comes at the first read, and is kept
    assert built._wire == restored._wire and built._wire is built._wire
    assert restored._shard_to_ops == built._shard_to_ops == SHAPES[name]
    assert restored._shard_to_ops is restored._shard_to_ops
    assert [list(ops) for ops in restored._shard_to_ops.values()] == [
        list(ops) for ops in SHAPES[name].values()]  # the keys' order too
    assert list(restored._shard_to_ops) == list(SHAPES[name])  # and the shards'
    assert _has(built, _WIRE_SLOT) and _has(restored, _DICTS_SLOT)


@pytest.mark.parametrize("name", CASES)
def test_the_two_are_equal_hash_alike_and_reduce_alike(name):
    built, restored = _built(name), _restored(name)
    assert built == restored and restored == built and hash(built) == hash(restored)
    assert {built: 1}[restored] == 1
    assert restored.__reduce__() == built.__reduce__()
    # a restored command's own tuple goes back, the very object
    again = _restored(name)
    assert again.__reduce__()[1] is again._wire and not _has(again, _DICTS_SLOT)
    assert repr(built) == repr(restored)
    other = _restored(name, Rifl(RIFL.source, RIFL.sequence + 1))
    assert other != built and other != restored and other.__reduce__()[1][2:] == built.__reduce__()[1][2:]
    # through a pickle of its own (a peer's message, the WAL) and back
    for cmd in (built, restored):
        back = pickle.loads(pickle.dumps(cmd))
        assert back == cmd and back._off_wire and back.__reduce__() == cmd.__reduce__()


@pytest.mark.parametrize("name", CASES)
def test_every_accessor_answers_alike(name):
    built, restored = _built(name), _restored(name)
    for cmd in (built, restored):
        assert type(cmd.rifl) is Rifl and cmd.rifl == RIFL
    shards = _shards_touched(name) + [7]  # and a shard the command does not touch
    assert list(restored.shards()) == list(built.shards()) == _shards_touched(name)
    for shard in shards:
        assert list(restored.keys(shard)) == list(built.keys(shard))
        assert list(restored.iter_ops(shard)) == list(built.iter_ops(shard))
        assert restored.key_count(shard) == built.key_count(shard)
        assert restored.replicated_by(shard) == built.replicated_by(shard)
    assert list(restored.all_keys()) == list(built.all_keys())
    assert restored.single_key() == built.single_key()
    assert (restored.read_only, restored.total_key_count, restored.shard_count, restored.multi_shard()) == (
        built.read_only, built.total_key_count, built.shard_count, built.multi_shard())
    assert restored.read_only == all(op.is_read for ops in SHAPES[name].values() for k in ops.values() for op in k)
    assert restored.total_key_count == sum(len(ops) for ops in SHAPES[name].values())


@pytest.mark.parametrize("name", CASES)
def test_conflicts_is_key_intersection_whichever_forms_meet(name):
    shard, key = next(iter(_built(name).all_keys()))
    same_key = Command.from_single(Rifl(9, 1), shard, key, KVOp.put("z"))
    other_shard = Command.from_single(Rifl(9, 2), shard + 10, key, KVOp.put("z"))
    other_key = Command.from_single(Rifl(9, 3), shard, key + "'", KVOp.put("z"))
    for cmd in (_built(name), _restored(name)):
        for other, meets in ((same_key, True), (other_shard, False), (other_key, False)):
            for peer in (other, pickle.loads(pickle.dumps(other))):
                assert cmd.conflicts(peer) is meets and peer.conflicts(cmd) is meets


@pytest.mark.parametrize("shard_count", [1, 4])
@pytest.mark.parametrize("name", CASES)
def test_buckets_and_the_row_of_the_key_column_are_the_same(name, shard_count):
    """``_buckets`` is the plain definition (it reads the accessors);
    ``_key_column`` reads the wire form in place: one row each, and the
    restored command's row is read with no dict built."""
    built, restored = _built(name), _restored(name)
    key_buckets, width = 4096, 3
    rows = {}
    for shard_id in ([0, 1, 3] if shard_count == 1 else [0]):
        want = _buckets(built, shard_id, key_buckets, shard_count)
        assert _buckets(_restored(name), shard_id, key_buckets, shard_count) == want
        if not want:
            continue  # the session boundary admits no command without a bucket
        column = np.full((2, width), KEY_PAD, dtype=np.int32)
        _key_column([(Dot(1, 1), built), (Dot(1, 2), restored)], column, shard_id, key_buckets, shard_count)
        for row in column.tolist():
            assert [b for b in row if b != KEY_PAD] == want and row == want + [KEY_PAD] * (width - len(want))
        rows[shard_id] = want
    assert rows and not _has(restored, _DICTS_SLOT)


def _seeded_store():
    store = KVStore()
    store._store.update({"k": "old", "999999": "was", "905": "nine", "a": "A", "x": "X", "w": "W"})
    return store


@pytest.mark.parametrize("name", CASES)
def test_execute_gives_the_same_results_and_the_same_store(name):
    built, restored = _built(name), _restored(name)
    for shard in _shards_touched(name) + [7]:
        one, other = _seeded_store(), _seeded_store()
        got, want = restored.execute(shard, one), built.execute(shard, other)
        assert got == want and [type(r) for r in got] == [type(r) for r in want]
        assert one._store == other._store
        assert len(want) == built.key_count(shard)


@pytest.mark.parametrize("shard_id", [None, 0], ids=["every_shard", "shard0"])
@pytest.mark.parametrize("name", CASES)
def test_the_stores_one_pass_gives_the_same_results_and_the_same_store(name, shard_id):
    """``execute_commands`` over the round ``[command, a write of its first
    key, the command again]`` in each form, against ``Command.execute`` a shard a
    command: results, store, and the two tallies (a command off the wire
    counts as read off the wire; one with a key of several ops as neither)."""
    def round_of(make):
        shard, key = next(iter(make(name).all_keys()))
        return [make(name), Command.from_single(Rifl(5, 1), shard, key, KVOp.put("between")),
                make(name, Rifl(RIFL.source, RIFL.sequence + 1))]

    plain, want = _seeded_store(), []
    for cmd in round_of(_built):
        for shard in (cmd.shards() if shard_id is None else [shard_id]):
            want += cmd.execute(shard, plain)
    assert want or shard_id is not None
    spelled = name != "two_ops_one_key"
    for make, off_wire in ((_built, 0), (_restored, 2)):
        store, cmds = _seeded_store(), round_of(make)
        got = store.execute_commands(cmds, shard_id)
        assert got == want and [type(r) for r in got] == [type(r) for r in want]
        assert all(type(r.rifl) is Rifl and type(r.op_results) is tuple for r in got)
        assert store._store == plain._store
        assert store.applied_in_pass == (3 if spelled else 1)
        assert store.applied_off_wire == (off_wire if spelled else 0)
        if make is _restored and spelled:
            # read off the frame's tuple: the pass asked no command for its dicts
            assert not any(_has(cmd, _DICTS_SLOT) for cmd in (cmds[0], cmds[2]))


@pytest.mark.parametrize("name", CASES)
def test_the_frame_is_byte_for_byte_the_parents(name):
    want = bytes.fromhex(PARENTS_FRAMES[name])
    built, restored = _built(name), _restored(name)
    assert rw.frame(Submit(built)) == want
    assert rw.frame(Submit(restored)) == want
    assert rw.serialize(Submit(built)) == want[4:] and want[4] == rw.KIND_SUBMIT
    # and the command alone, as a peer's message or the WAL pickles it
    assert pickle.dumps(restored, pickle.HIGHEST_PROTOCOL) == pickle.dumps(built, pickle.HIGHEST_PROTOCOL)
    # from_single and from_keys, where they can make the shape, frame alike
    if len(SHAPES[name]) == 1:
        ((shard, ops),) = SHAPES[name].items()
        assert rw.frame(Submit(Command.from_keys(RIFL, shard, ops))) == want
        if len(ops) == 1 and len(next(iter(ops.values()))) == 1:
            ((key, (op,)),) = ops.items()
            assert rw.frame(Submit(Command.from_single(RIFL, shard, key, op))) == want


def test_a_shard_or_a_key_named_twice_is_what_the_dicts_make_of_it():
    """No ``__reduce__`` gives such a frame; the parent's restorer built dicts,
    which keep a name's last entry at its first place: so does this one, and
    the command's tuple is then that command's own."""
    twice_key = (1, 2, ((0, (("a", ((1, "x"),)), ("b", ((1, "z"),)), ("a", ((1, "y"),)))),))
    cmd = _restore_command(*twice_key)
    assert cmd == Command(Rifl(1, 2), {0: {"a": (KVOp.put("y"),), "b": (KVOp.put("z"),)}})
    assert cmd._wire == (1, 2, ((0, (("a", ((1, "y"),)), ("b", ((1, "z"),)))),)) and cmd.total_key_count == 2
    twice_shard = (1, 2, ((0, (("a", ((1, "x"),)),)), (0, (("b", ((1, "y"),)),))))
    cmd = _restore_command(*twice_shard)
    assert cmd == Command.from_single(Rifl(1, 2), 0, "b", KVOp.put("y"))
    assert cmd._wire == (1, 2, 0, "b", 1, "y") and cmd.total_key_count == 1 and cmd.single_key() == (0, "b")


def test_a_command_with_neither_form_raises_and_does_not_loop():
    bare = Command.__new__(Command)
    bare._rifl = RIFL
    with pytest.raises(AttributeError):
        bare._wire
    with pytest.raises(AttributeError):
        bare._shard_to_ops
    with pytest.raises(AttributeError):
        bare._no_such_slot
