"""`kv_multi`: the keys of a command are a function of the seed, distinct,
and each goes to the shard the program's own hash gives; a command over
several shards is acknowledged when its last shard has answered, and a
shard's second answer is a stray."""

import socket
import struct
import threading

import numpy as np
import pytest

from benchmark.check import MORE_FIELDS, check_history
from benchmark.generators import kv_loop, kv_multi
from fantoch_tpu.core.command import CommandResult
from fantoch_tpu.core.ids import Rifl
from fantoch_tpu.run.prelude import ClientHi, ClientHiAck, Overloaded, Submit, ToClient
from fantoch_tpu.run.rw import deserialize, serialize
from fantoch_tpu.utils import key_hash

BIG_SEED = 2**31 + 12345
ZIPF = {"kind": "zipf", "coefficient": 0.7, "keys_per_shard": 250}


def test_the_keys_of_a_command_are_seeded_distinct_and_drawn_over_every_shards_keys():
    rows = kv_multi.key_rows(BIG_SEED, ZIPF, 4, 2, 0, size=20000)
    assert rows.shape == (20000, 2) and rows.dtype == np.int32
    assert np.array_equal(rows, kv_multi.key_rows(BIG_SEED, ZIPF, 4, 2, 0, size=20000))
    assert not np.array_equal(rows, kv_multi.key_rows(BIG_SEED + 1, ZIPF, 4, 2, 0, size=20000))
    assert not np.array_equal(rows, kv_multi.key_rows(BIG_SEED, ZIPF, 4, 2, 1, size=20000))
    assert rows.min() == 1 and 900 < rows.max() <= 1000  # ranks over 4 x 250 keys
    assert np.all(rows[:, 0] != rows[:, 1])
    # a command's first key is the plain zipf draw: rank 1 has 1 / H(1000; 0.7) = 4.0%
    assert np.array_equal(rows[:, 0], kv_loop.key_stream(
        BIG_SEED, {**ZIPF, "keys_per_shard": 1000}, 0, size=20000))
    assert 0.03 < np.mean(rows[:, 0] == 1) < 0.05


@pytest.mark.parametrize("keys_per_shard,shards,per_command", [(1, 3, 3), (2, 2, 3), (2, 1, 2)])
def test_distinct_keys_are_found_in_a_key_space_hardly_larger_than_a_command(
        keys_per_shard, shards, per_command):
    key_gen = {"kind": "zipf", "coefficient": 1.0, "keys_per_shard": keys_per_shard}
    rows = kv_multi.key_rows(7, key_gen, shards, per_command, 0, size=500)
    assert all(len(set(row)) == per_command for row in rows.tolist())
    assert rows.min() >= 1 and rows.max() <= keys_per_shard * shards


def test_key_generators_it_does_not_draw_and_key_spaces_too_small_are_refused():
    with pytest.raises(ValueError, match="zipf"):
        kv_multi.key_rows(1, {"kind": "conflict_rate", "rate": 50}, 2, 2, 0, size=10)
    with pytest.raises(ValueError, match="distinct"):
        kv_multi.key_rows(1, {"kind": "zipf", "coefficient": 1.0, "keys_per_shard": 1}, 2, 3, 0, size=10)


class Served:
    """The far end of the generator's connection: takes ``ClientHi``, then
    hands the test what arrives and sends what the test gives it."""

    def __init__(self):
        self.listener = socket.create_server(("127.0.0.1", 0))
        self.port = self.listener.getsockname()[1]
        self.ready = threading.Event()
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        self.conn, _ = self.listener.accept()
        assert isinstance(self.recv(), ClientHi)
        self.send(ClientHiAck())
        self.ready.set()

    def _exactly(self, n):
        data = b""
        while len(data) < n:
            data += self.conn.recv(n - len(data))
        return data

    def recv(self):
        (length,) = struct.unpack(">I", self._exactly(4))
        return deserialize(self._exactly(length))

    def send(self, message):
        payload = serialize(message)
        self.conn.sendall(struct.pack(">I", len(payload)) + payload)

    def close(self):
        self.conn.close()
        self.listener.close()


@pytest.fixture
def served():
    server = Served()
    yield server
    server.close()


def answer(rifl, keys, values=None):
    result = CommandResult(rifl, len(keys))
    for key in keys:
        result.add_partial(str(key), ((values or {}).get(key),))
    return ToClient(result)


def test_a_command_goes_to_the_shards_of_its_keys_and_is_acknowledged_by_the_last(served):
    shards = 4
    engine = kv_multi.MultiEngine("127.0.0.1", served.port, BIG_SEED, 8, 24,
                                  np.array([1, 2], np.int32), shards, ZIPF, per_command=2)
    assert served.ready.wait(5)
    try:
        # two keys on different shards, by the program's own hash
        a = next(k for k in range(1, 100) if key_hash(str(k)) % shards == 0)
        b = next(k for k in range(1, 100) if key_hash(str(k)) % shards == 3)
        c = next(k for k in range(a + 1, 100) if key_hash(str(k)) % shards == 0)
        engine.submit(1, (a, b), False, 0.0, 0.0, kv_loop.MEASURED)
        engine.submit(2, (a, c), False, 0.0, 0.0, kv_loop.MEASURED)
        engine.pump(0.5)
        first, second = served.recv(), served.recv()
        assert isinstance(first, Submit) and first.cmd.rifl == Rifl(1, 1)
        assert {shard: sorted(first.cmd.keys(shard)) for shard in first.cmd.shards()} == \
            {0: [str(a)], 3: [str(b)]}
        assert {shard: sorted(second.cmd.keys(shard)) for shard in second.cmd.shards()} == \
            {0: sorted([str(a), str(c)])}
        value = kv_loop.value_of(engine.pads, 24, 2, 7)

        assert engine._on_message(answer(Rifl(1, 1), [b], {b: value}), 1.0) is None  # one shard of two
        assert (1, 1) in engine.outstanding and not engine.strays
        assert engine._on_message(answer(Rifl(1, 1), [b]), 1.5) is None  # that shard again: a stray
        assert engine.strays == [(1, 1, 1.5)]
        assert engine._on_message(answer(Rifl(1, 1), [a]), 2.0) == 1  # the last shard: acknowledged
        assert engine._on_message(answer(Rifl(1, 1), [a]), 2.5) is None  # acknowledged twice
        assert engine.strays == [(1, 1, 1.5), (1, 1, 2.5)]
        # one shard, both keys in its one answer
        assert engine._on_message(answer(Rifl(2, 1), [a, c], {a: value}), 3.0) == 2
        assert not engine.outstanding

        rec = engine.history()
        assert set(rec) == {name for name, _ in kv_loop.RECORD_FIELDS} | {"shards", "strays"} | \
            {name for name, _ in MORE_FIELDS}
        assert rec["shards"].tolist() == [2, 1] and rec["status"].tolist() == [kv_loop.OK] * 2
        assert rec["acked"].tolist() == [2.0, 3.0] and rec["key"].tolist() == [a, a]
        assert rec["ret_client"].tolist() == [kv_loop.NONE_VALUE, 2] and rec["ret_seq"][1] == 7
        assert rec["more_key"].tolist() == [b, c] and rec["more_answers"].tolist() == [1, 1]
        assert (rec["more_client"].tolist(), rec["more_seq"].tolist()) == ([1, 2], [1, 1])
        assert (rec["more_ret_client"].tolist(), rec["more_ret_seq"].tolist()) == \
            ([2, kv_loop.NONE_VALUE], [7, kv_loop.NONE_VALUE])
    finally:
        engine.close()


def test_refused_rejected_and_half_answered_commands_are_failed_not_acknowledged(served):
    engine = kv_multi.MultiEngine("127.0.0.1", served.port, 3, 8, 24,
                                  np.array([1, 2, 3, 4], np.int32), 2, ZIPF, per_command=2)
    assert served.ready.wait(5)
    try:
        a = next(k for k in range(1, 100) if key_hash(str(k)) % 2 == 0)
        b = next(k for k in range(1, 100) if key_hash(str(k)) % 2 == 1)
        for client in (1, 2, 3, 4):
            engine.submit(client, (a, b), False, 0.0, 0.0, kv_loop.MEASURED)
        assert engine._on_message(Overloaded(Rifl(1, 1), 5, depth=1, limit=1), 1.0) == 1
        assert engine._on_message(ToClient(CommandResult(Rifl(2, 1), 0)), 2.0) == 2  # rejected
        assert engine._on_message(answer(Rifl(3, 1), [a]), 3.0) is None  # the other shard never comes
        # a key the command did not name is no shard's answer to it
        assert engine._on_message(answer(Rifl(4, 1), [a + 2 if (a + 2) != b else a + 4]), 4.0) is None
        rec = engine.history()
        assert rec["status"].tolist() == [kv_loop.OVERLOADED, kv_loop.REJECTED,
                                          kv_loop.UNANSWERED, kv_loop.UNANSWERED]
        assert rec["more_answers"].tolist() == [0, 0, 0, 0] and len(rec["strays"]) == 1
        strays = rec.pop("strays")
        verdict = check_history(rec, strays)  # failed commands: their fate is open
        assert [w["check"] for w in verdict["witnesses"]] == ["ack_unmatched"]
    finally:
        engine.close()


def test_a_command_over_one_key_and_one_shard_is_what_kv_loop_sends(served):
    engine = kv_multi.MultiEngine("127.0.0.1", served.port, BIG_SEED, 4, 24,
                                  np.array([1], np.int32), 1, {**ZIPF, "keys_per_shard": 1000})
    assert served.ready.wait(5)
    try:
        keys, is_read = engine.planned(1)
        assert len(keys) == 1 and not is_read
        assert keys[0] == kv_loop.key_stream(BIG_SEED, {**ZIPF, "keys_per_shard": 1000}, 0,
                                             size=len(engine.keys))[0]
        engine.submit(1, keys, False, 0.0, 0.0, kv_loop.WARM)
        engine.pump(0.5)
        cmd = served.recv().cmd
        assert list(cmd.shards()) == [0] and list(cmd.keys(0)) == [str(keys[0])]
        rec = engine.history()
        assert len(rec["more_key"]) == 0 and rec["shards"].tolist() == [1]
    finally:
        engine.close()
