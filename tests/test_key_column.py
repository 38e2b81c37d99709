"""A round's key column (``device_drivers._key_column``) held to the plain
definition of a command's buckets (``_buckets``), the three conditions on
the bucket hash, and ``utils.key_hash`` pinned where the shard rule reads
it."""

import math
import subprocess
import sys

import numpy as np
import pytest

from fantoch_tpu.core.command import Command
from fantoch_tpu.core.ids import Dot, Rifl
from fantoch_tpu.core.kvs import KVOp
from fantoch_tpu.parallel.mesh_step import KEY_PAD
from fantoch_tpu.run.device_drivers import (
    CaesarDeviceDriver,
    DeviceDriver,
    NewtDeviceDriver,
    _bucket,
    _buckets,
    _key_column,
)
from fantoch_tpu.utils import key_hash

BUCKETS = 4096  # small enough that random keys share a bucket now and then


def _command(number, keys, shard_count, one_shard=None):
    """A command of ``keys``, each on the shard the generators' rule gives it
    (``key_hash(key) % shard_count``) or all on ``one_shard``."""
    ops = (KVOp.get() if number % 3 else KVOp.put(f"v{number}"),)
    by_shard = {}
    for key in keys:
        shard = key_hash(key) % shard_count if one_shard is None else one_shard
        by_shard.setdefault(shard, {})[key] = ops
    return Command(Rifl(1 + number % 7, 1 + number), by_shard)


def _same_bucket_pair(shard_count):
    """Two keys of one shard that fall in one bucket, found by search under
    the server's hash."""
    seen = {}
    for number in range(100000):
        key = f"s{number}"
        shard = key_hash(key) % shard_count
        other = seen.setdefault(_bucket(shard, key, BUCKETS, shard_count), key)
        if other != key:
            return other, key
    raise AssertionError("no two keys in one bucket")


def _batch(shape, shard_count, count, seed):
    rng = np.random.default_rng(seed)
    names = [str(int(k)) for k in rng.integers(0, 50000, size=(count, 3)).ravel()]
    rows = [names[3 * i: 3 * i + 3] for i in range(count)]
    if shape == "one_key":
        cmds = [_command(i, row[:1], shard_count) for i, row in enumerate(rows)]
    elif shape == "two_keys":
        cmds = [_command(i, row[:2], shard_count) for i, row in enumerate(rows)]
    elif shape == "two_keys_one_shard":
        cmds = [_command(i, row[:2], shard_count, one_shard=i % shard_count)
                for i, row in enumerate(rows)]
    elif shape == "two_keys_one_bucket":
        pair = _same_bucket_pair(shard_count)
        cmds = [_command(i, pair if i % 2 else row[:2], shard_count) for i, row in enumerate(rows)]
    elif shape == "three_keys":
        pair = _same_bucket_pair(shard_count)
        cmds = [_command(i, (*pair, row[0]) if i % 4 == 1 else row, shard_count)
                for i, row in enumerate(rows)]
    else:  # every width in one round
        cmds = [_command(i, row[: 1 + i % 3], shard_count) for i, row in enumerate(rows)]
    return [(Dot(1, i + 1), cmd) for i, cmd in enumerate(cmds)]


def _hold_to_the_definition(column, batch, shard_count, batch_size):
    width = column.shape[1]
    assert column.dtype == np.int32 and column.shape == (batch_size, width)
    for row, (_dot, cmd) in zip(column.tolist(), batch):
        want = _buckets(cmd, 0, BUCKETS, shard_count)
        assert row == want + [KEY_PAD] * (width - len(want))
        real = row[: len(want)]
        assert real == sorted(set(real)) and 1 <= len(real) and min(real) >= 0
        assert max(real) < BUCKETS
        # a bucket's shard is a shard the command names, and every shard it
        # names has a bucket
        shards = {shard for shard, ops in cmd._shard_to_ops.items() if ops}
        if shard_count > 1:
            assert {b % shard_count for b in real} == shards
    assert (column[len(batch):] == KEY_PAD).all()


SHAPES = ("one_key", "two_keys", "two_keys_one_shard", "two_keys_one_bucket", "three_keys", "mixed")


@pytest.mark.parametrize("shard_count", (1, 4))
@pytest.mark.parametrize("shape", SHAPES)
def test_the_column_is_the_plain_definition_row_by_row(shape, shard_count):
    """Seeded random commands of one, two and three keys on one and on four
    shards, a full batch and one shorter than the device batch: every row is
    ``_buckets`` of its command and then pads, ascending, no bucket twice,
    each bucket on a shard the command names."""
    batch_size = 64
    for count, seed in ((batch_size, 11), (23, 12), (1, 13)):
        batch = _batch(shape, shard_count, count, seed)
        column = np.full((batch_size, 3), KEY_PAD, np.int32)
        _key_column(batch, column, 0, BUCKETS, shard_count)
        _hold_to_the_definition(column, batch, shard_count, batch_size)
        if shape == "two_keys_one_bucket":  # the pair's rows: one bucket, then pads
            assert (column[1:count:2, 1:] == KEY_PAD).all()


def test_an_empty_batch_leaves_the_column_as_it_was():
    column = np.full((8, 2), KEY_PAD, np.int32)
    _key_column([], column, 0, BUCKETS, 4)
    assert (column == KEY_PAD).all()


@pytest.mark.parametrize("shard_count", (1, 4))
@pytest.mark.parametrize("keys, width", ((2, 1), (3, 2), (0, 1), (0, 2)))
def test_a_command_the_column_cannot_carry_asserts(keys, width, shard_count):
    """More distinct buckets than the key width, or none: the session
    boundary admits neither, and the column checks once a round."""
    names, at = [], 0
    while len(names) < keys:  # keys in distinct buckets of shard 0
        name = f"w{at}"
        at += 1
        if all(_bucket(0, name, BUCKETS, shard_count) != _bucket(0, other, BUCKETS, shard_count)
               for other in names):
            names.append(name)
    wrong = Command(Rifl(9, 9), {0: {name: (KVOp.get(),) for name in names}})
    batch = _batch("one_key", shard_count, 5, 3) + [(Dot(2, 1), wrong)]
    column = np.full((8, width), KEY_PAD, np.int32)
    with pytest.raises(AssertionError, match="key bucket"):
        _key_column(batch, column, 0, BUCKETS, shard_count)


def test_a_one_shard_driver_reads_the_keys_of_its_own_shard():
    """On one shard the column takes the keys the command has on the
    driver's shard, whatever its number, as ``_buckets`` does."""
    cmd = Command(Rifl(1, 1), {2: {"a": (KVOp.get(),), "b": (KVOp.get(),)}})
    column = np.full((2, 2), KEY_PAD, np.int32)
    _key_column([(Dot(1, 1), cmd)], column, 2, BUCKETS, 1)
    assert column[0].tolist() == _buckets(cmd, 2, BUCKETS, 1) and column[0, 1] != KEY_PAD
    assert max(column[0]) < BUCKETS
    with pytest.raises(AssertionError, match="no key bucket"):
        _key_column([(Dot(1, 1), cmd)], column, 0, BUCKETS, 1)


DRIVERS = {
    "dep_commit_4shard_2key": lambda: DeviceDriver(
        3, shard_count=4, batch_size=16, key_buckets=BUCKETS, key_width=2, pending_capacity=16),
    "dep_commit_1key": lambda: DeviceDriver(
        3, batch_size=16, key_buckets=BUCKETS, pending_capacity=16),
    "newt_4shard_2key": lambda: NewtDeviceDriver(
        3, shard_count=4, batch_size=16, key_buckets=BUCKETS, key_width=2, pending_capacity=16),
    "caesar_2key": lambda: CaesarDeviceDriver(
        3, batch_size=16, key_buckets=BUCKETS, key_width=2, pending_capacity=16),
}


@pytest.mark.parametrize("name", DRIVERS)
def test_both_assembly_loops_stage_that_column(name):
    """``DeviceDriver._assemble`` and the dot-keyed drivers'
    ``_assemble_rows`` (a round from the staging ring, and Newt's chain of
    rounds assembled whole) stage the one function's column, a short batch's
    rest as pads."""
    driver = DRIVERS[name]()
    shards, width = driver.shard_count, driver.key_width
    shape = "one_key" if width == 1 else "two_keys_one_bucket"
    batches = [_batch(shape, shards, count, 20 + count) for count in (16, 9)]
    for batch in batches:
        staged = driver._assemble(batch)
        columns = staged[0] if isinstance(driver, DeviceDriver) else staged
        assert columns[0].shape[1] == width
        _hold_to_the_definition(columns[0], batch, shards, 16)
    if isinstance(driver, NewtDeviceDriver):
        keys, _srcs, _seqs = DRIVERS[name]()._assemble_chain(batches)
        for r, batch in enumerate(batches):
            _hold_to_the_definition(keys[r], batch, shards, 16)


# --- the hash: three conditions, and the shard rule's own hash where it was ---

_PROBE = ("0", "999999", "hot", "kéy", "")


def test_a_keys_bucket_is_the_same_in_a_fresh_process():
    """Not the built-in ``hash``: another interpreter, another hash seed,
    the same buckets."""
    here = [_bucket(s, k, n, c) for k in _PROBE for s, n, c in ((0, 1 << 20, 1), (3, 4 * 5 * (1 << 18), 4))]
    code = (
        "from fantoch_tpu.run.device_drivers import _bucket\n"
        f"print([_bucket(s, k, n, c) for k in {_PROBE!r} "
        "for s, n, c in ((0, 1 << 20, 1), (3, 4 * 5 * (1 << 18), 4))])"
    )
    for seed in ("0", "4242"):
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True, check=True,
            env={"PYTHONHASHSEED": seed, "PYTHONPATH": ":".join(sys.path), "JAX_PLATFORMS": "cpu"},
        )
        assert out.stdout.strip() == repr(here)


@pytest.mark.parametrize("shard_count", (1, 4))
@pytest.mark.parametrize("per_shard", (1 << 20, 5 * (1 << 18)), ids=("2pow20", "5x2pow18"))
def test_decimal_keys_fill_a_shards_buckets_as_a_random_function_would(per_shard, shard_count):
    """200,000 keys ``str(i)`` into a shard's buckets, a power of two and not
    one: the distinct buckets are within 1% of a random function's
    ``m * (1 - exp(-n / m))`` (bare ``crc32 % m`` gives 13% fewer than that
    of 1M keys into 2**20)."""
    keys = 200_000
    sid = shard_count - 1
    total = per_shard * shard_count
    filled = {_bucket(sid, str(i), total, shard_count) for i in range(keys)}
    assert all(b % shard_count == sid for b in filled) or shard_count == 1
    assert min(filled) >= 0 and max(filled) < total
    random = per_shard * (1 - math.exp(-keys / per_shard))
    assert abs(len(filled) - random) <= 0.01 * random


@pytest.mark.parametrize("key, fnv1a", (("a", 0xAF63DC4C8601EC8C), ("foobar", 0x85944171F73967E8)))
def test_the_shard_rules_hash_is_fnv_1a_as_published(key, fnv1a):
    """``utils.key_hash`` is the generators', the workload's and the
    executors' shard rule: 64-bit FNV-1a, held to two of its published
    vectors so that a key's shard cannot move by accident."""
    assert key_hash(key) == fnv1a
