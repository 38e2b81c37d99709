"""`kv_ycsb` alone, against a sequential store behind a socket: the load is a
partition of the records, each written once with a whole value; an update
carries `payload_bytes` bytes; the seed fixes every client's stream."""

import socket
import struct
import threading
import time

import numpy as np
import pytest

from benchmark.generators import kv_loop, kv_ycsb
from fantoch_tpu.core.command import CommandResult
from fantoch_tpu.core.kvs import KVOpKind
from fantoch_tpu.run.prelude import ClientHi, ClientHiAck, Submit, ToClient
from fantoch_tpu.run.rw import deserialize, serialize

BIG_SEED = 2**31 + 54321
PAYLOAD, CLIENTS, RECORDS = 1000, 8, 203
ZIPF = {"kind": "zipf", "coefficient": 0.99, "keys_per_shard": RECORDS}


class Store:
    """A sequential key-value store behind the program's client wire
    protocol: one connection, every ``Submit`` answered in arrival order
    (a ``Put`` with the previous value, a ``Get`` with the current one)."""

    def __init__(self):
        self.listener = socket.create_server(("127.0.0.1", 0))
        self.port = self.listener.getsockname()[1]
        self.values: dict[str, str] = {}
        self.puts: list[tuple[str, str]] = []  # (key, value) in arrival order
        self.gets = 0
        threading.Thread(target=self._serve, daemon=True).start()

    def _serve(self):
        conn, _ = self.listener.accept()
        with conn:
            def exactly(n):
                data = b""
                while len(data) < n:
                    chunk = conn.recv(n - len(data))
                    if not chunk:
                        raise ConnectionError
                    data += chunk
                return data

            def send(message):
                payload = serialize(message)
                conn.sendall(struct.pack(">I", len(payload)) + payload)

            try:
                while True:
                    (length,) = struct.unpack(">I", exactly(4))
                    message = deserialize(exactly(length))
                    if isinstance(message, ClientHi):
                        send(ClientHiAck())
                        continue
                    assert isinstance(message, Submit)
                    (_, key), = message.cmd.all_keys()
                    (op,), = (ops for ops in message.cmd._shard_to_ops[0].values())
                    if op.kind is KVOpKind.PUT:
                        self.puts.append((key, op.value))
                        returned, self.values[key] = self.values.get(key), op.value
                    else:
                        self.gets += 1
                        returned = self.values.get(key)
                    result = CommandResult(message.cmd.rifl, 1)
                    result.add_partial(key, (returned,))
                    send(ToClient(result))
            except ConnectionError:
                pass

    def close(self):
        self.listener.close()


def stream(seed, proc=0, n_procs=1, per_client=40):
    """One process's load and then ``per_client`` commands a client."""
    store = Store()
    own = kv_loop.own_clients(CLIENTS, proc, n_procs)
    engine = kv_ycsb.Engine("127.0.0.1", store.port, seed, CLIENTS, PAYLOAD, own, ZIPF, 0.95, proc)
    try:
        keys = kv_ycsb.load_keys(RECORDS, proc, n_procs)
        far = time.monotonic() + 60
        loaded = kv_ycsb.run_load(engine, own, keys, far)
        after_load = engine.rec.n
        engine.run_closed(time.monotonic(), far, own, kv_loop.MEASURED, per_client=per_client)
        return store, engine.history(), loaded, after_load
    finally:
        engine.close()
        store.close()


@pytest.mark.parametrize("n_procs", [1, 2, 4, 7])
def test_the_load_is_a_partition_of_the_records_over_processes_and_clients(n_procs):
    shares = [kv_ycsb.load_keys(RECORDS, proc, n_procs) for proc in range(n_procs)]
    assert sorted(np.concatenate(shares).tolist()) == list(range(1, RECORDS + 1))
    assert max(map(len, shares)) - min(map(len, shares)) <= 1
    for proc, share in enumerate(shares):
        own = kv_loop.own_clients(CLIENTS * n_procs, proc, n_procs)
        per_client = kv_ycsb.client_keys(share, own)
        assert sorted(per_client) == own.tolist()
        assert sorted(key for keys in per_client.values() for key in keys) == sorted(share.tolist())


def test_the_load_writes_every_record_of_its_share_once_with_a_whole_value():
    store, history, loaded, after_load = stream(BIG_SEED, proc=1, n_procs=2)
    share = kv_ycsb.load_keys(RECORDS, 1, 2).tolist()
    assert loaded == len(share) == after_load
    load = store.puts[:after_load]  # nothing else is sent before the load is acknowledged
    assert sorted(int(key) for key, _ in load) == share
    assert all(len(value) == PAYLOAD and len(value.encode()) == PAYLOAD for _, value in load)
    rows = slice(0, after_load)
    assert np.all(history["phase"][rows] == kv_loop.WARM) and np.all(history["op"][rows] == kv_loop.PUT)
    assert np.all(history["status"][rows] == kv_loop.OK)
    assert np.all(history["ret_client"][rows] == kv_loop.NONE_VALUE)
    # a load that is cut short says how far it got
    short = Store()
    own = kv_loop.own_clients(CLIENTS, 0, 1)
    engine = kv_ycsb.Engine("127.0.0.1", short.port, 1, CLIENTS, PAYLOAD, own, ZIPF, 0.95, 0)
    try:
        assert kv_ycsb.run_load(engine, own, kv_ycsb.load_keys(RECORDS, 0, 1), time.monotonic()) == 0
    finally:
        engine.close()
        short.close()


def test_an_update_carries_the_whole_record_and_a_read_returns_one():
    store, history, _, after_load = stream(BIG_SEED)
    run = slice(after_load, None)
    updates = store.puts[after_load:]
    assert len(updates) == int(np.sum(history["op"][run] == kv_loop.PUT)) > 0
    assert all(len(value.encode()) == PAYLOAD for _, value in updates)
    reads = history["op"][run] == kv_loop.GET
    assert store.gets == int(reads.sum()) and 0.85 < reads.mean() < 1.0  # 95% of 320
    # every key drawn was loaded, so every read names the write whose record it returned
    assert np.all(history["ret_client"][run][reads] >= 1)
    assert 1 <= history["key"][run].min() and history["key"][run].max() <= RECORDS


def test_the_seed_fixes_every_clients_stream_load_included():
    def per_client(history):
        order = np.lexsort((history["seq"], history["client"]))
        return [history[name][order].tolist() for name in ("client", "seq", "key", "op", "phase")]

    first, again, other = (per_client(stream(seed)[1]) for seed in (BIG_SEED, BIG_SEED, BIG_SEED + 1))
    assert first == again
    assert first[:2] == other[:2] and first[2:4] != other[2:4]  # same clients, other keys and ops
