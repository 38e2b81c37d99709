"""The store's one pass over a round's commands (``KVStore.execute_commands``,
PR 48) held to the plain definition, ``Command.execute`` a shard a command on
a second store: the same results element for element and the same store
after, on every shape of command and kind of op; under a monitor or a digest
the record the plain route leaves; and the seams the benchmark's three broken
servers replace (``KVStore._put``, ``KVStore._do_execute``,
``_DriverCore._execute_entry``) still see every command of a driver's drain."""

import random

import numpy as np
import pytest

from fantoch_tpu.core.command import Command
from fantoch_tpu.core.ids import Dot, Rifl
from fantoch_tpu.core.kvs import KVOp, KVOpKind, KVStore
from fantoch_tpu.executor.base import ExecutorResult
from fantoch_tpu.run.device_drivers import DeviceDriver, NewtDeviceDriver, _DriverCore
from fantoch_tpu.utils import key_hash
from tests.benchmark_tests import broken_multi_server, broken_server, stale_read_server

SHARDS = 4
KEYSPACE = 12  # small: hits and misses, a key twice in a round


def _ops(rng, read, several):
    """A key's ops: one, or two to three (``several``), all reads or none."""
    count = rng.choice((2, 3)) if several else 1
    if read:
        return tuple(KVOp.get() for _ in range(count))
    return tuple(
        KVOp.delete() if rng.random() < 0.3 else KVOp.put(f"v{rng.randrange(1000)}")
        for _ in range(count)
    )


def _command(rng, number, shape):
    """A command of ``shape``, each key on the shard the generators' rule
    gives it unless the shape says one shard."""
    read = rng.random() < 0.5
    names = rng.sample([f"k{i}" for i in range(KEYSPACE)], 3)
    width, one_shard = {
        "one_key": (1, False),
        "two_keys_two_shards": (2, False),
        "two_keys_one_shard": (2, True),
        "three_keys_two_and_one": (3, False),
        "several_ops_a_key": (rng.choice((1, 2)), False),
        "mixed": (1 + number % 3, number % 2 == 0),
    }[shape]
    several = shape == "several_ops_a_key" or (shape == "mixed" and number % 5 == 0)
    by_shard = {}
    for at, key in enumerate(names[:width]):
        shard = key_hash(key) % SHARDS
        if one_shard:
            shard = number % SHARDS
        elif shape == "two_keys_two_shards":
            shard = (number + at) % SHARDS
        elif shape == "three_keys_two_and_one":
            shard = (number + (at == 2)) % SHARDS
        by_shard.setdefault(shard, {})[key] = _ops(rng, read, several)
    return Command(Rifl(1 + number % 5, 1 + number), by_shard)


def _round(shape, seed, count=64):
    rng = random.Random(seed)
    return [_command(rng, number, shape) for number in range(count)]


def _plain(cmds, shard_id, store):
    """The plain definition: ``Command.execute`` a shard a command."""
    results = []
    for cmd in cmds:
        for shard in cmd.shards() if shard_id is None else (shard_id,):
            results.extend(cmd.execute(shard, store))
    return results


def _assert_same(got, want):
    assert got == want
    for result in got:
        assert type(result) is ExecutorResult and type(result.rifl) is Rifl
        assert type(result.op_results) is tuple


SHAPES = (
    "one_key",
    "two_keys_two_shards",
    "two_keys_one_shard",
    "three_keys_two_and_one",
    "several_ops_a_key",
    "mixed",
)


@pytest.mark.parametrize("shard_id", (None, 0, 2), ids=("every_shard", "shard0", "shard2"))
@pytest.mark.parametrize("shape", SHAPES)
def test_the_pass_is_the_plain_definition_result_for_result(shape, shard_id):
    """Seeded rounds over a dozen keys, three rounds on one store (so that a
    ``Get`` hits and misses, a ``Put`` is the first and an overwrite, a
    ``Delete`` finds its key and does not): the same list, ``ExecutorResult``
    with a ``Rifl`` and not bare tuples, and the same store after."""
    passed, plain = KVStore(), KVStore()
    applied = 0
    for seed in (1, 2, 3):
        cmds = _round(shape, seed)
        _assert_same(passed.execute_commands(cmds, shard_id), _plain(cmds, shard_id, plain))
        assert passed._store == plain._store
        if shape not in ("several_ops_a_key", "mixed"):
            applied += len(cmds)
            assert passed.applied_in_pass == applied
    kinds = {KVOpKind.GET: 0, KVOpKind.PUT: 0, KVOpKind.DELETE: 0}
    for cmd in cmds:
        for ops in cmd._shard_to_ops.values():
            for key_ops in ops.values():
                kinds[key_ops[0].kind] += 1
    assert all(kinds.values()) and len(plain) > 0


def _single(number, key, op, shard=0):
    return Command.from_single(Rifl(1, number), shard, key, op)


@pytest.mark.parametrize("shard_id", (None, 0), ids=("every_shard", "shard0"))
def test_every_kind_of_op_hit_and_miss_and_a_key_twice_in_a_round(shard_id):
    """By hand: each kind on a key that is there and on one that is not, a
    read after a write and a write after a read of one key inside a round, a
    value that is not ASCII."""
    cmds = [
        _single(1, "a", KVOp.get()),  # miss
        _single(2, "a", KVOp.put("première")),  # first
        _single(3, "a", KVOp.get()),  # read after write
        _single(4, "a", KVOp.put("值")),  # write after read, overwrite
        _single(5, "a", KVOp.get()),
        _single(6, "b", KVOp.delete()),  # miss
        _single(7, "a", KVOp.delete()),  # hit
        _single(8, "a", KVOp.get()),  # miss again
        _single(9, "c", KVOp.put("")),  # an empty value is a value
        _single(10, "c", KVOp.put("x")),
    ]
    passed, plain = KVStore(), KVStore()
    got = passed.execute_commands(cmds, shard_id)
    _assert_same(got, _plain(cmds, shard_id, plain))
    assert [r.op_results for r in got] == [
        (None,), (None,), ("première",), ("première",), ("值",), (None,), ("值",), (None,),
        (None,), ("",),
    ]
    assert passed._store == plain._store == {"c": "x"}
    assert passed.applied_in_pass == len(cmds)


def test_a_command_without_the_shard_gives_nothing_and_an_empty_round_an_empty_list():
    store = KVStore()
    elsewhere = _single(1, "a", KVOp.put("v"), shard=3)
    assert store.execute_commands([elsewhere], 0) == [] and len(store) == 0
    assert store.execute_commands([], None) == [] and store.execute_commands([], 1) == []
    assert store.execute_commands([elsewhere], None) == [ExecutorResult(Rifl(1, 1), "a", (None,))]


def test_several_ops_a_key_are_applied_in_their_order():
    cmd = Command(Rifl(1, 1), {0: {"a": (KVOp.put("1"), KVOp.put("2"), KVOp.delete()),
                                   "b": (KVOp.put("3"),)}})
    store = KVStore()
    assert store.execute_commands([cmd]) == [
        ExecutorResult(Rifl(1, 1), "a", (None, "1", "2")),
        ExecutorResult(Rifl(1, 1), "b", (None,)),
    ]
    assert store._store == {"b": "3"}
    # a command with a key of several ops is not one the one-op spelling applied
    assert store.applied_in_pass == 0


def test_an_op_of_no_known_kind_raises_as_the_plain_route_does():
    class Odd:
        kind = "Odd"
        value = None
        is_read = False

    cmd = Command.from_single(Rifl(1, 1), 0, "a", KVOp.get())
    cmd._shard_to_ops = {0: {"a": (Odd(),)}}
    with pytest.raises(AssertionError, match="unknown op kind"):
        KVStore().execute_commands([cmd])


@pytest.mark.parametrize("record", ("monitor", "digest", "both"))
@pytest.mark.parametrize("shape", ("one_key", "two_keys_two_shards", "several_ops_a_key"))
def test_under_a_monitor_or_a_digest_the_record_is_the_plain_routes(shape, record):
    kw = {"monitor_execution_order": record != "digest", "execution_digests": record != "monitor"}
    passed, plain = KVStore(**kw), KVStore(**kw)
    assert not passed.plain
    for seed in (4, 5):
        cmds = _round(shape, seed)
        _assert_same(passed.execute_commands(cmds), _plain(cmds, None, plain))
    assert passed._store == plain._store and passed.applied_in_pass == 0
    if record != "digest":
        assert passed.monitor == plain.monitor and len(plain.monitor) > 0
    if record != "monitor":
        assert passed.digest.summary() == plain.digest.summary() and plain.digest.summary()
        for key in plain.digest.summary():
            assert passed.digest.entries(key) == plain.digest.entries(key)


# --- the seams: what the three broken servers replace is seen once a round ---


def _counting(calls, name, sound):
    def replaced(self, *args):
        calls.append(name)
        return sound(self, *args)

    return replaced


# seam -> (the class, the method's name, the function the benchmark's broken
# server puts there, how many calls of it a command of the rounds below makes)
SEAMS = {
    "put": (KVStore, "_put", broken_server._dropping_put),
    "do_execute": (KVStore, "_do_execute", stale_read_server._stale_execute),
    "execute_entry": (_DriverCore, "_execute_entry", broken_multi_server._tearing_entry),
}
SEAM_DRIVERS = {
    # the dependency drain (DeviceDriver._execute) and the ordered one
    # (_execute_ordered, Newt's)
    "epaxos": lambda: DeviceDriver(3, batch_size=8, key_buckets=64),
    "newt": lambda: NewtDeviceDriver(3, f=1, batch_size=8, key_buckets=64),
}


def _put_batch(first, count=8):
    return [
        (Dot(1, seq), _single(seq, f"key{seq % 3}", KVOp.put(f"v{seq}")))
        for seq in range(first, first + count)
    ]


@pytest.mark.parametrize("seam", SEAMS)
@pytest.mark.parametrize("protocol", SEAM_DRIVERS)
def test_a_replaced_seam_sees_every_command_of_a_drain(protocol, seam, monkeypatch):
    """Replaced on the class as the broken server replaces it (its own
    function, counted), a driver's drain goes through the replacement for
    every command and ``executed_in_pass`` stays where it was; restored, the
    pass applies every command again."""
    driver = SEAM_DRIVERS[protocol]()
    assert len(driver.step(_put_batch(1))) == 8
    assert driver.executed_in_pass == driver.executed == 8
    owner, name, broken = SEAMS[seam]
    calls = []
    with monkeypatch.context() as patch:
        patch.setattr(owner, name, _counting(calls, name, broken))
        assert not (driver.store.plain and seam != "execute_entry")
        results = driver.step(_put_batch(9)) + driver.step(_put_batch(17, count=5))
        assert len(results) == 13 and calls == [name] * 13
        assert driver.executed == 21 and driver.executed_in_pass == 8
    assert driver.store.plain
    assert len(driver.step(_put_batch(22))) == 8
    assert driver.executed == 29 and driver.executed_in_pass == 16
    fresh = SEAM_DRIVERS[protocol]()
    fresh.step(_put_batch(1, count=6))
    assert fresh.executed_in_pass == fresh.executed == 6


@pytest.mark.parametrize("how", ("subclass", "instance"))
@pytest.mark.parametrize("name", ("execute", "_do_execute", "_put"))
def test_a_store_with_a_method_of_its_own_is_not_plain(name, how):
    """A subclass that overrides one of the three, or a store that carries
    its own, is seen as a class with the method replaced is: every op goes
    through ``execute``."""
    calls = []
    sound = getattr(KVStore, name)
    if how == "subclass":
        store = type("Own", (KVStore,), {name: _counting(calls, name, sound)})()
    else:
        store = KVStore()
        own = _counting(calls, name, sound)
        setattr(store, name, lambda *args: own(store, *args))
    assert KVStore().plain and not store.plain
    cmds = [_single(seq, "a", KVOp.put(str(seq))) for seq in range(1, 6)]
    _assert_same(store.execute_commands(cmds), _plain(cmds, None, KVStore()))
    assert calls == [name] * 5 and store.applied_in_pass == 0


@pytest.mark.parametrize("record", ("monitor", "digest"))
@pytest.mark.parametrize("protocol", SEAM_DRIVERS)
def test_a_driver_whose_store_keeps_a_record_runs_the_per_command_loop(protocol, record):
    driver, plain = SEAM_DRIVERS[protocol](), SEAM_DRIVERS[protocol]()
    kw = {"monitor_execution_order": record == "monitor", "execution_digests": record == "digest"}
    driver.store = KVStore(**kw)
    got, want = driver.step(_put_batch(1)), plain.step(_put_batch(1))
    assert got == want and driver.store._store == plain.store._store
    assert driver.executed == plain.executed == plain.executed_in_pass == 8
    assert driver.executed_in_pass == 0
    if record == "monitor":
        assert {key: driver.store.monitor.get_order(key) for key in driver.store.monitor.keys()} == {
            f"key{k}": [Rifl(1, seq) for seq in range(1, 9) if seq % 3 == k] for k in range(3)
        }
    else:
        assert {key: count for key, (count, _) in driver.store.digest.summary().items()} == {
            "key0": 2, "key1": 3, "key2": 3,
        }


@pytest.mark.parametrize("looped", (False, True), ids=("pass", "per_command"))
def test_a_row_registered_by_no_one_drops_out_and_counts_for_nothing(looped, monkeypatch):
    """The column's pop in a round where a pop gives nothing (a padding row
    among the executed): the row is walked, not executed, and not counted
    among the fast rows though its flag is set."""
    if looped:
        monkeypatch.setattr(_DriverCore, "_execute_entry",
                            _counting([], "_execute_entry", _DriverCore._execute_entry))
    driver = SEAM_DRIVERS["epaxos"]()
    for dot, cmd in _put_batch(1, count=6):
        driver._cmds[dot.sequence] = (dot, cmd)
    fast = np.array([True, True, False, True, False, True, True])
    results = driver._execute_rows([3, 90, 1, 91, 2, 6, 4], fast)
    assert [r.rifl for r in results] == [Rifl(1, seq) for seq in (3, 1, 2, 6, 4)]
    assert (driver.drain_rows_walked, driver.executed, driver.fast_paths) == (7, 5, 3)
    assert driver.executed_in_pass == (0 if looped else 5) and list(driver._cmds) == [5]
    # every row registered: the flags are counted as a mask
    assert len(driver._execute_rows([5], np.array([True]))) == 1 and driver.fast_paths == 4
    assert driver._execute_rows([], np.zeros(0, bool)) == [] and driver.executed == 6
