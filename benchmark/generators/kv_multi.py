"""The load generator for commands over several keys and several shards.

``kv_loop``'s connection, schedule, values, clocks and loops, with what a
command over ``keys_per_command`` keys on a ``shard_count``-shard deployment
adds: the keys of one command are distinct zipf draws over all
``keys_per_shard x shard_count`` keys (upstream's generator draws a key
again where the command holds it already, ``fantoch/src/client/workload.rs``
``gen_unique_keys``), each key belongs to shard ``key_hash(key) %
shard_count`` (the program's own hash, imported like its message classes),
and one ``Command`` names every shard it touches.  The unified
``--device-step`` server answers every shard on the connection the command
came by, one ``CommandResult`` per touched shard; the command is
acknowledged when the last of them has arrived.  A second reply from one
shard is a stray.

The record of a command is ``kv_loop``'s (its first key, and what that key
returned) plus ``shards``, how many shards it touched; each further key has
a row of its own in the ``more_*`` columns (``benchmark/check.py``
``MORE_FIELDS``), tied to the command by its rifl.

Run as ``python -m benchmark.generators.kv_multi <plan.json>``; the protocol
on stdin and stdout is ``kv_loop``'s.  Mix parameters: ``kv_loop``'s, with
``keys_per_command`` >= 1, ``shard_count`` >= 1 and ``key_gen`` of kind
``zipf``.  ``read_share`` > 0 sends that share of commands as ``Get``s of
all their keys.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

from benchmark.check import MORE_FIELDS
from benchmark.generators.kv_loop import (
    BAD_VALUE,
    GET,
    MEASURED,
    NONE_VALUE,
    OK,
    OVERLOADED,
    PUT,
    READBACK,
    REJECTED,
    UNANSWERED,
    WARM,
    Engine,
    Records,
    _rng,
    key_stream,
    open_schedule,
    own_clients,
    parse_value,
    value_of,
)
from fantoch_tpu.core.command import Command
from fantoch_tpu.core.ids import Rifl
from fantoch_tpu.core.kvs import KVOp
from fantoch_tpu.run.prelude import Overloaded, Submit, ToClient
from fantoch_tpu.utils import key_hash

_KEY_ROWS = 1 << 20  # commands planned ahead per process
_SPARE_DRAWS = 6     # further draws a command may need to find distinct keys


def key_rows(seed: int, key_gen: dict, shard_count: int, per_command: int,
             stream_index: int, size: int = _KEY_ROWS) -> np.ndarray:
    """``(size, per_command)`` seeded keys, distinct within a row: zipf ranks
    over every shard's keys, a key the row holds already drawn again."""
    if key_gen["kind"] != "zipf":
        raise ValueError(f"kv_multi draws zipf keys, not {key_gen['kind']!r}")
    total = int(key_gen["keys_per_shard"]) * shard_count
    if total < per_command:
        raise ValueError(f"{per_command} distinct keys a command out of {total}")
    over_all = {**key_gen, "keys_per_shard": total}
    rows = np.zeros((size, per_command), np.int32)
    held = np.zeros(size, np.int64)
    for draw in range(per_command + _SPARE_DRAWS):
        if (held == per_command).all():
            break
        keys = key_stream(seed, over_all, stream_index * 64 + draw, size)
        fresh = (held < per_command) & ~(rows == keys[:, None]).any(axis=1)
        rows[fresh, held[fresh]] = keys[fresh]
        held += fresh
    for row in np.flatnonzero(held < per_command):  # out of draws: the next ranks not held
        key = int(rows[row, held[row] - 1])
        while held[row] < per_command:
            key = key % total + 1
            if key not in rows[row, : held[row]]:
                rows[row, held[row]] = key
                held[row] += 1
    return rows


class MoreRows(Records):
    """``kv_loop.Records`` for the ``more_*`` columns: one row per further key."""

    def __init__(self, capacity: int = 1 << 16):
        self.n = 0
        self.cols = {name: np.zeros(capacity, dtype) for name, dtype in MORE_FIELDS}

    def add(self, **values) -> int:
        if self.n == len(self.cols["more_key"]):
            self.cols = {name: np.concatenate([col, np.zeros_like(col)])
                         for name, col in self.cols.items()}
        for name, value in values.items():
            self.cols[name][self.n] = value
        self.n += 1
        return self.n - 1


class MultiEngine(Engine):
    """``kv_loop.Engine`` with commands over several keys and shards."""

    def __init__(self, host: str, port: int, seed: int, clients: int, payload: int,
                 own: np.ndarray, shard_count: int, key_gen: dict | None = None,
                 per_command: int = 1, read_share: float = 0.0, stream_index: int = 0):
        super().__init__(host, port, seed, clients, payload, own)
        self.shard_count = shard_count
        self.shard_of: dict[int, int] = {}
        if key_gen is not None:
            self.keys = key_rows(seed, key_gen, shard_count, per_command, stream_index)
            self.reads = _rng(seed, 19, stream_index).random(len(self.keys)) < read_share
        self.rec.cols["shards"] = np.zeros(len(self.rec.cols["client"]), np.int8)
        self.more = MoreRows()
        # (client, seq) -> (record, {key: its more_* row, None for the first}, shards to
        # come, keys answered)
        self.outstanding: dict[tuple[int, int], tuple] = {}

    def shard(self, key: int) -> int:
        shard = self.shard_of.get(key)
        if shard is None:
            shard = self.shard_of[key] = key_hash(str(key)) % self.shard_count
        return shard

    def planned(self, client: int) -> tuple[tuple[int, ...], bool]:
        """(keys, is_read) of the client's next command: a function of the
        seed, the client and its sequence number."""
        slot = ((self.next_seq[client] - 1) * len(self.own) + self.local[client]) % len(self.keys)
        return tuple(int(key) for key in self.keys[slot]), bool(self.reads[slot])

    def submit(self, client: int, keys: tuple[int, ...], is_read: bool, due: float,
               now: float, phase: int) -> None:
        seq = self.next_seq[client]
        self.next_seq[client] = seq + 1
        ops = (KVOp.get() if is_read else KVOp.put(value_of(self.pads, self.payload, client, seq)),)
        by_shard: dict[int, dict[str, tuple]] = {}
        for key in keys:
            by_shard.setdefault(self.shard(key), {})[str(key)] = ops
        self._queue(Submit(Command(Rifl(client, seq), by_shard)))
        record = self.rec.add(
            client=client, seq=seq, key=keys[0], op=GET if is_read else PUT,
            phase=phase, status=UNANSWERED, due=due, sent=now, acked=np.nan,
            ret_client=NONE_VALUE, ret_seq=NONE_VALUE, shards=len(by_shard),
        )
        rows: dict[str, int | None] = {str(keys[0]): None}
        for key in keys[1:]:
            rows[str(key)] = self.more.add(
                more_client=client, more_seq=seq, more_key=key,
                more_ret_client=NONE_VALUE, more_ret_seq=NONE_VALUE, more_answers=0)
        self.outstanding[(client, seq)] = (record, rows, set(by_shard), set())

    def _on_message(self, message, now: float) -> int | None:
        """Record one reply; returns the client that may send again, once
        the last shard of its command has answered."""
        if not isinstance(message, (ToClient, Overloaded)):
            raise ConnectionError(f"unexpected message {message!r}")
        refused = isinstance(message, Overloaded)
        rifl = message.rifl if refused else message.cmd_result.rifl
        state = self.outstanding.get((rifl.source, rifl.sequence))
        if state is None:  # acknowledged twice, or never sent
            self.strays.append((rifl.source, rifl.sequence, now))
            return None
        record, rows, to_come, answered = state
        cols, more = self.rec.cols, self.more.cols
        results = {} if refused else message.cmd_result.results
        if results:
            shards = {self.shard(int(key)) for key in results if key in rows}
            if len(shards) != 1 or not shards <= to_come or any(key not in rows for key in results):
                # a shard's second reply, or keys that are no one shard's of this command
                self.strays.append((rifl.source, rifl.sequence, now))
                return None
            answered.update(results)
            for key, (returned,) in results.items():
                value = parse_value(self.pads, self.payload, returned)
                if rows[key] is None:
                    cols["ret_client"][record], cols["ret_seq"][record] = value
                else:
                    more["more_ret_client"][rows[key]], more["more_ret_seq"][rows[key]] = value
                    more["more_answers"][rows[key]] += 1
            to_come -= shards
            if to_come:
                return None
            if str(cols["key"][record]) not in answered:  # every shard replied, none for this key
                cols["ret_client"][record] = cols["ret_seq"][record] = BAD_VALUE
        del self.outstanding[(rifl.source, rifl.sequence)]
        if refused:
            cols["status"][record] = OVERLOADED
        else:
            cols["acked"][record] = now
            cols["status"][record] = OK if results else REJECTED
        return rifl.source

    def history(self) -> dict:
        return {**super().history(), **self.more.arrays()}


def read_back_mix(host: str, port: int, seed: int, mix: dict, payload: int,
                  keys, limit_s: float) -> dict:
    """One ``Get`` per key, sent to the key's own shard, from a client of
    its own, after the drain."""
    clients = int(mix["clients"])
    reader = clients + 1
    engine = MultiEngine(host, port, seed, clients, payload, np.array([reader], np.int32),
                         int(mix["shard_count"]))
    try:
        now = time.monotonic()
        for key in keys:
            engine.submit(reader, (int(key),), True, now, now, READBACK)
        engine.drain(limit_s)
        return engine.history()
    finally:
        engine.close()


def main(plan_path: str) -> None:
    with open(plan_path) as fh:
        plan = json.load(fh)
    mix = plan["mix"]
    seed, proc, n_procs = plan["seed"], plan["proc_index"], plan["n_procs"]
    own = own_clients(int(mix["clients"]), proc, n_procs)
    engine = MultiEngine(
        plan["host"], plan["port"], seed, int(mix["clients"]), plan["payload_bytes"], own,
        int(mix["shard_count"]), mix["key_gen"], int(mix["keys_per_command"]),
        float(mix.get("read_share", 0.0)), proc,
    )
    try:
        is_open = mix["loop"] == "open"
        drain_limit = float(mix["drain_limit_s"])
        warm_s, seconds = float(mix["warmup_s"]), float(plan["seconds"])
        # as kv_loop: the first commands pay whatever the server still has to
        # compile or load, before any schedule starts
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
        history = engine.history()
        np.savez(plan["out"], **history)
        sent = history["phase"] == MEASURED
        print("DONE " + json.dumps({
            "proc": proc, "warm_drained": warm_drained, "drained": drained,
            "stopped_offering_s": stop - t0, "drain_s": time.monotonic() - stop,
            "multi_shard_share": float(np.mean(history["shards"][sent] > 1)) if sent.any() else None,
        }), flush=True)
    finally:
        engine.close()


if __name__ == "__main__":
    main(sys.argv[1])
