"""The benchmark's load generator for key-value traffic.

One process of it carries a share of a mix's logical clients over one
connection.  It speaks the program's client wire protocol by importing the
program's message classes and its frame serializer (that interface is the
program's) and owns everything else: schedule, keys, values, clocks,
records.  It never imports jax and never resubmits: an ``Overloaded``
reply, an empty (rejected) result, or no reply by the end of the drain is a
failed request.

Run as ``python -m benchmark.generators.kv_loop <plan.json>`` by
``benchmark.run``.  On stdout it prints ``READY`` once its warm-up is
acknowledged, then waits for ``GO <t0>`` on stdin (``t0`` on the host's
system-wide monotonic clock), runs the measured phase, drains, dumps its
records as ``.npz`` and prints ``DONE``.

Mix parameters understood (a data file under ``benchmark/traffic``):
``loop`` (open | closed), ``clients``, ``rate_per_s`` (open), ``key_gen``
(zipf: coefficient, keys_per_shard; conflict_rate: rate), ``read_share``,
``burst`` (period_s, duty, factor: a square wave on the Poisson rate that
keeps its mean), ``warmup_s``, ``drain_limit_s``.  ``keys_per_command`` must
be 1: a multi-key mix brings its own generator.
"""

from __future__ import annotations

import json
import select
import socket
import struct
import sys
import time

import numpy as np

from fantoch_tpu.core.command import Command
from fantoch_tpu.core.ids import Rifl
from fantoch_tpu.core.kvs import KVOp
from fantoch_tpu.run.prelude import (
    ClientHi,
    ClientHiAck,
    Overloaded,
    Submit,
    ToClient,
)
from fantoch_tpu.run.rw import deserialize, serialize

# frames are a u32 big-endian length prefix + the program's serializer
# (fantoch_tpu/run/rw.py)
_LEN = struct.Struct(">I")

OK, OVERLOADED, REJECTED, UNANSWERED = 0, 1, 2, 3
WARM, MEASURED, READBACK = 0, 1, 2
PUT, GET = 0, 1
NONE_VALUE, BAD_VALUE = -1, -2  # ret_client codes: no previous value / not one of ours

_LETTERS = np.frombuffer(
    b"abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ", dtype="S1"
)
_SEND_CHUNK = 256  # requests queued between two looks at the socket
_KEY_STREAM = 1 << 21

RECORD_FIELDS = (
    ("client", np.int32), ("seq", np.int32), ("key", np.int32),
    ("op", np.int8), ("phase", np.int8), ("status", np.int8),
    ("due", np.float64), ("sent", np.float64), ("acked", np.float64),
    ("ret_client", np.int32), ("ret_seq", np.int32),
)


def _rng(seed: int, stream: int, index: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream, index])


def client_pads(seed: int, clients: int, payload: int) -> list[str]:
    """Seeded letters, one string per logical client (index = id - 1)."""
    draws = _rng(seed, 7).integers(0, len(_LETTERS), size=(clients, payload))
    return [row.tobytes().decode() for row in _LETTERS[draws]]


def value_of(pads: list[str], payload: int, client: int, seq: int) -> str:
    """The unique value of write ``seq`` of ``client``: it names its write."""
    prefix = f"{client}:{seq}:"
    return prefix + pads[client - 1][: max(0, payload - len(prefix))]


def parse_value(pads: list[str], payload: int, value) -> tuple[int, int]:
    """(client, seq) of the write whose value this is; ``NONE_VALUE`` for no
    previous value, ``BAD_VALUE`` for anything no client of this run wrote."""
    if value is None:
        return NONE_VALUE, NONE_VALUE
    try:
        head, mid, _ = value.split(":", 2)
        client, seq = int(head), int(mid)
    except (AttributeError, ValueError):
        return BAD_VALUE, BAD_VALUE
    if not 1 <= client <= len(pads) or value != value_of(pads, payload, client, seq):
        return BAD_VALUE, BAD_VALUE
    return client, seq


def key_stream(seed: int, key_gen: dict, stream_index: int, size: int = _KEY_STREAM):
    """``size`` seeded key draws.  zipf: ranks 1..keys_per_shard by inverse
    CDF (the arithmetic of ``fantoch_tpu/client/key_gen.py``).
    conflict_rate: 0 stands for the shared hot key, -1 for the client's own."""
    rng = _rng(seed, 11, stream_index)
    if key_gen["kind"] == "zipf":
        ranks = np.arange(1, int(key_gen["keys_per_shard"]) + 1, dtype=np.float64)
        weights = ranks ** (-float(key_gen["coefficient"]))
        cdf = np.cumsum(weights / weights.sum())
        draws = np.searchsorted(cdf, rng.random(size)) + 1
        return np.minimum(draws, len(cdf)).astype(np.int32)
    if key_gen["kind"] == "conflict_rate":
        hot = rng.integers(0, 100, size) < int(key_gen["rate"])
        return np.where(hot, 0, -1).astype(np.int32)
    raise ValueError(f"unknown key generator {key_gen['kind']!r}")


def _warp(times: np.ndarray, burst: dict | None, horizon: float) -> np.ndarray:
    """Map unit-mean arrival times through a square-wave rate of mean 1."""
    if not burst:
        return times
    period, duty, factor = (float(burst[k]) for k in ("period_s", "duty", "factor"))
    low = (1.0 - duty * factor) / (1.0 - duty)
    if low < 0:
        raise ValueError("burst: duty * factor must be at most 1")
    edges, mass = [0.0], [0.0]
    t = 0.0
    while t < horizon + period:
        for span, rate in ((duty * period, factor), ((1 - duty) * period, low)):
            t += span
            edges.append(t)
            mass.append(mass[-1] + span * rate)
    return np.interp(times, mass, edges)


def open_schedule(seed: int, stream: int, mix: dict, own: np.ndarray, seconds: float):
    """Absolute Poisson arrivals (offsets from the phase start) of the own
    clients, merged in time order: a function of the seed and the client."""
    per_client = float(mix["rate_per_s"]) / int(mix["clients"])
    count = int(seconds * per_client * 1.5 + 32)
    times, clients = [], []
    for client in own:
        arrivals = np.cumsum(_rng(seed, stream, int(client)).exponential(1.0 / per_client, count))
        arrivals = _warp(arrivals, mix.get("burst"), seconds)
        arrivals = arrivals[arrivals < seconds]
        times.append(arrivals)
        clients.append(np.full(len(arrivals), client, np.int32))
    times, clients = np.concatenate(times), np.concatenate(clients)
    order = np.argsort(times, kind="stable")
    return times[order], clients[order]


class Records:
    """Per-request columns, grown by doubling."""

    def __init__(self, capacity: int = 1 << 16):
        self.n = 0
        self.cols = {name: np.zeros(capacity, dtype) for name, dtype in RECORD_FIELDS}

    def add(self, **values) -> int:
        row = self.n
        if row == len(self.cols["client"]):
            for name, col in self.cols.items():
                self.cols[name] = np.concatenate([col, np.zeros_like(col)])
        for name, value in values.items():
            self.cols[name][row] = value
        self.n += 1
        return row

    def arrays(self) -> dict:
        return {name: col[: self.n].copy() for name, col in self.cols.items()}


class Engine:
    """One connection, its outstanding requests and their records."""

    def __init__(self, host: str, port: int, seed: int, clients: int, payload: int,
                 own: np.ndarray, key_gen: dict | None = None,
                 read_share: float = 0.0, stream_index: int = 0):
        self.payload = payload
        self.pads = client_pads(seed, clients, payload)
        self.own = own
        self.local = {int(c): i for i, c in enumerate(own)}
        self.next_seq = {int(c): 1 for c in own}
        if key_gen is not None:
            self.keys = key_stream(seed, key_gen, stream_index)
            self.reads = _rng(seed, 19, stream_index).random(len(self.keys)) < read_share
        self.rec = Records()
        self.outstanding: dict[tuple[int, int], int] = {}
        self.strays: list[tuple[int, int, float]] = []
        self.out = bytearray()
        self.inbuf = bytearray()
        self.sock = socket.create_connection((host, port), timeout=30)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._queue(ClientHi([int(c) for c in own]))
        self.sock.sendall(bytes(self.out))
        self.out.clear()
        ack = self._recv_blocking()
        if not isinstance(ack, ClientHiAck):
            raise ConnectionError(f"expected ClientHiAck, got {ack!r}")
        self.sock.setblocking(False)

    def close(self) -> None:
        self.sock.close()

    def _queue(self, message) -> None:
        payload = serialize(message)
        self.out += _LEN.pack(len(payload)) + payload

    def _recv_blocking(self):
        def exactly(n: int) -> bytes:
            data = b""
            while len(data) < n:
                chunk = self.sock.recv(n - len(data))
                if not chunk:
                    raise ConnectionError("server closed the connection")
                data += chunk
            return data

        (length,) = _LEN.unpack(exactly(_LEN.size))
        return deserialize(exactly(length))

    # --- requests ---

    def planned(self, client: int) -> tuple[int, bool]:
        """(key, is_read) of the client's next request: a function of the
        seed, the client and its sequence number."""
        slot = ((self.next_seq[client] - 1) * len(self.own) + self.local[client]) % len(self.keys)
        key = int(self.keys[slot])
        if key <= 0:  # conflict-rate draws: the hot key or the client's own
            key = 0 if key == 0 else client
        return key, bool(self.reads[slot])

    def submit(self, client: int, key: int, is_read: bool, due: float,
               now: float, phase: int) -> None:
        seq = self.next_seq[client]
        self.next_seq[client] = seq + 1
        op = KVOp.get() if is_read else KVOp.put(value_of(self.pads, self.payload, client, seq))
        self._queue(Submit(Command.from_single(Rifl(client, seq), 0, str(key), op)))
        self.outstanding[(client, seq)] = self.rec.add(
            client=client, seq=seq, key=key, op=GET if is_read else PUT,
            phase=phase, status=UNANSWERED, due=due, sent=now, acked=np.nan,
            ret_client=NONE_VALUE, ret_seq=NONE_VALUE,
        )

    def _on_message(self, message, now: float) -> int | None:
        """Record one reply; returns the client that may send again."""
        if isinstance(message, ToClient):
            result = message.cmd_result
            rifl = result.rifl
            row = self.outstanding.pop((rifl.source, rifl.sequence), None)
            if row is None:  # acknowledged twice, or never sent
                self.strays.append((rifl.source, rifl.sequence, now))
                return None
            cols = self.rec.cols
            cols["acked"][row] = now
            if not result.results:
                cols["status"][row] = REJECTED
            else:
                (returned,) = next(iter(result.results.values()))
                cols["status"][row] = OK
                cols["ret_client"][row], cols["ret_seq"][row] = parse_value(
                    self.pads, self.payload, returned
                )
            return rifl.source
        if isinstance(message, Overloaded):
            row = self.outstanding.pop((message.rifl.source, message.rifl.sequence), None)
            if row is None:
                self.strays.append((message.rifl.source, message.rifl.sequence, now))
                return None
            self.rec.cols["status"][row] = OVERLOADED
            return message.rifl.source
        raise ConnectionError(f"unexpected message {message!r}")

    def pump(self, timeout: float) -> list[int]:
        """Write what is queued, read what has come; the clients answered."""
        readable, writable, _ = select.select(
            [self.sock], [self.sock] if self.out else [], [], max(0.0, timeout)
        )
        if writable:
            sent = self.sock.send(memoryview(self.out)[: 1 << 20])
            del self.out[:sent]
        answered: list[int] = []
        if readable:
            data = self.sock.recv(1 << 20)
            if not data:
                raise ConnectionError("server closed the connection")
            now = time.monotonic()  # after the bytes arrived: never early
            self.inbuf += data
            offset, size = 0, len(self.inbuf)
            while size - offset >= _LEN.size:
                (length,) = _LEN.unpack_from(self.inbuf, offset)
                if size - offset - _LEN.size < length:
                    break
                start = offset + _LEN.size
                client = self._on_message(deserialize(bytes(self.inbuf[start : start + length])), now)
                if client is not None:
                    answered.append(client)
                offset = start + length
            del self.inbuf[:offset]
        return answered

    # --- loops ---

    def run_open(self, t0: float, times: np.ndarray, clients: np.ndarray, phase: int) -> None:
        """Send each request when due, or as soon after as possible."""
        sent, total = 0, len(times)
        while sent < total:
            now = time.monotonic()  # before the bytes leave: never late
            due_upto = int(np.searchsorted(times, now - t0, side="right"))
            upto = min(due_upto, sent + _SEND_CHUNK)
            for index in range(sent, upto):
                client = int(clients[index])
                key, is_read = self.planned(client)
                self.submit(client, key, is_read, t0 + float(times[index]), now, phase)
            sent = upto
            if sent < due_upto or sent >= total:
                wait = 0.0  # behind schedule, or nothing left to wait for
            else:
                wait = t0 + float(times[sent]) - time.monotonic()
            self.pump(min(wait, 0.005))

    def run_closed(self, t0: float, t_end: float, clients, phase: int,
                   per_client: int | None = None) -> None:
        """Each client sends its next command when the previous one is
        acknowledged, until ``t_end`` (or ``per_client`` commands each)."""
        while time.monotonic() < t0:
            time.sleep(min(0.002, max(0.0, t0 - time.monotonic())))
        left = {int(c): per_client for c in clients} if per_client else None
        ready = [int(c) for c in clients]
        while True:
            now = time.monotonic()
            if now >= t_end:
                return
            for client in ready[:_SEND_CHUNK]:
                if left is not None:
                    if left[client] == 0:
                        continue
                    left[client] -= 1
                key, is_read = self.planned(client)
                self.submit(client, key, is_read, now, now, phase)
            del ready[:_SEND_CHUNK]
            if left is not None and not self.outstanding and not ready:
                return
            ready.extend(self.pump(0.0 if ready else 0.005))

    def drain(self, limit_s: float) -> bool:
        """Wait for every outstanding request, or the fixed limit."""
        deadline = time.monotonic() + limit_s
        while (self.outstanding or self.out) and time.monotonic() < deadline:
            self.pump(0.05)
        return not self.outstanding

    def history(self) -> dict:
        """The records, and the replies that matched no outstanding request."""
        strays = np.array(self.strays, dtype=np.float64).reshape(-1, 3)
        return {**self.rec.arrays(), "strays": strays}


def own_clients(clients: int, proc_index: int, n_procs: int) -> np.ndarray:
    return np.arange(1 + proc_index, clients + 1, n_procs, dtype=np.int32)


def read_back(host: str, port: int, seed: int, clients: int, payload: int,
              keys, limit_s: float) -> dict:
    """One ``Get`` per key from a client of its own, after the drain."""
    reader = clients + 1
    engine = Engine(host, port, seed, clients, payload, np.array([reader], np.int32))
    try:
        now = time.monotonic()
        for key in keys:
            engine.submit(reader, int(key), True, now, now, READBACK)
        engine.drain(limit_s)
        return engine.history()
    finally:
        engine.close()


def main(plan_path: str) -> None:
    with open(plan_path) as fh:
        plan = json.load(fh)
    mix = plan["mix"]
    if int(mix.get("keys_per_command", 1)) != 1:
        raise SystemExit("kv_loop sends one key per command; this mix needs its own generator")
    seed, proc, n_procs = plan["seed"], plan["proc_index"], plan["n_procs"]
    own = own_clients(int(mix["clients"]), proc, n_procs)
    engine = Engine(
        plan["host"], plan["port"], seed, int(mix["clients"]), plan["payload_bytes"],
        own, mix["key_gen"], float(mix.get("read_share", 0.0)), proc,
    )
    try:
        is_open = mix["loop"] == "open"
        drain_limit = float(mix["drain_limit_s"])
        warm_s, seconds = float(mix["warmup_s"]), float(plan["seconds"])
        # the first commands pay whatever the server still has to compile
        # or load; an open schedule would pile up behind that
        far = time.monotonic() + plan["compile_limit_s"]
        engine.run_closed(time.monotonic(), far, own[:16], WARM, per_client=2)
        if is_open:
            times, clients = open_schedule(seed, 17, mix, own, warm_s)
            measured = open_schedule(seed, 13, mix, own, seconds)
            engine.run_open(time.monotonic(), times, clients, WARM)
        else:
            now = time.monotonic()
            engine.run_closed(now, now + warm_s, own, WARM)
        warm_drained = engine.drain(drain_limit)
        print("READY", flush=True)
        word, t0 = sys.stdin.readline().split()
        if word != "GO":
            raise SystemExit(f"expected GO, got {word!r}")
        t0 = float(t0)
        if is_open:
            engine.run_open(t0, *measured, MEASURED)
        else:
            engine.run_closed(t0, t0 + seconds, own, MEASURED)
        stop = time.monotonic()
        drained = engine.drain(drain_limit)
        np.savez(plan["out"], **engine.history())
        print("DONE " + json.dumps({
            "proc": proc, "warm_drained": warm_drained, "drained": drained,
            "stopped_offering_s": stop - t0, "drain_s": time.monotonic() - stop,
        }), flush=True)
    finally:
        engine.close()


if __name__ == "__main__":
    main(sys.argv[1])
