"""The control: histories that the check must call incorrect.

This system states no precision, so the control breaks, one at a time, a
guarantee its configurations state, by the benchmark's own means on the
answers a real run recorded (``history.npz`` in the run's output directory);
the program gets no switch for it.

    python3 benchmark/control.py benchmark_out/<cell>/trace0 [--seeds 1,2,3]

exits 0 iff the sound history is accepted and every mutation of it, for
every seed, is rejected.  The mutations:

* ``fork``: a write returns the value another write already returned
  (per-key linearizable writes: one order per key).
* ``duplicate_ack``: one request is acknowledged a second time (exactly
  once per rifl).
* ``inversion``: a write late in a key's chain is stamped as acknowledged
  before an earlier one was sent (real time).
* ``lost_write``: the read-back of a key returns the value before the last
  acknowledged write (a reply only after execution; nothing acknowledged is
  lost).
* ``foreign_value``: a write returns a value no client of the run wrote.

Where the history holds commands over several keys, three more break the
guarantee those add, one order of commands across keys and shards:

* ``cross_key_swap``: two commands that share two keys keep their order on
  one and swap it on the other.
* ``torn_command``: a command's write on one of its keys is gone from that
  key's chain (the next write there, or the read-back, returns what the
  command itself returned); its other key is intact.
* ``cross_key_inversion``: a command is stamped as acknowledged before one
  it follows through a path over two keys was sent.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark.check import History, check_history  # noqa: E402
from benchmark.generators.kv_loop import BAD_VALUE, GET, OK, PUT, READBACK  # noqa: E402


def _chains(rec: dict) -> dict[int, list[int]]:
    """key -> rows of its acknowledged writes, for keys with three or more."""
    rows = np.flatnonzero((rec["op"] == PUT) & (rec["status"] == OK))
    by_key: dict[int, list[int]] = {}
    for row in rows[np.argsort(rec["key"][rows], kind="stable")]:
        by_key.setdefault(int(rec["key"][row]), []).append(int(row))
    return {key: rs for key, rs in by_key.items() if len(rs) >= 3}


def mutations(records: dict, strays, seed: int):
    """Yield ``(name, records, strays)``: each breaks one guarantee."""
    rng = np.random.default_rng([int(seed), 29])
    chains = _chains(records)
    if not chains:
        raise ValueError("no key with three acknowledged writes to mutate")
    keys = sorted(chains)
    strays = np.asarray(strays, dtype=np.float64).reshape(-1, 3)

    def fresh() -> dict:
        return {name: col.copy() for name, col in records.items()}

    rows = chains[keys[int(rng.integers(len(keys)))]]
    a, b = (int(r) for r in rng.choice(rows, size=2, replace=False))
    rec = fresh()
    rec["ret_client"][b], rec["ret_seq"][b] = rec["ret_client"][a], rec["ret_seq"][a]
    yield "fork", rec, strays

    row = rows[int(rng.integers(len(rows)))]
    again = [[records["client"][row], records["seq"][row], records["acked"][row] + 0.001]]
    yield "duplicate_ack", fresh(), np.concatenate([strays, np.array(again, dtype=np.float64)])

    # in chain order: the row whose value another returned comes first
    position = {(int(records["client"][r]), int(records["seq"][r])): r for r in rows}
    later = next(r for r in rows
                 if (int(records["ret_client"][r]), int(records["ret_seq"][r])) in position)
    earlier = position[(int(records["ret_client"][later]), int(records["ret_seq"][later]))]
    rec = fresh()
    rec["acked"][later] = rec["sent"][earlier] - 0.002
    rec["sent"][later] = rec["due"][later] = rec["acked"][later] - 0.001
    yield "inversion", rec, strays

    reads = np.flatnonzero((records["phase"] == READBACK) & (records["op"] == GET)
                           & (records["status"] == OK) & (records["ret_client"] > 0))
    for read in (int(r) for r in rng.permutation(reads)):
        writers = {(int(records["client"][r]), int(records["seq"][r])): r
                   for r in chains.get(int(records["key"][read]), [])}
        tail = writers.get((int(records["ret_client"][read]), int(records["ret_seq"][read])))
        if tail is not None and records["ret_client"][tail] > 0:
            rec = fresh()  # the read now returns what the last write overwrote
            rec["ret_client"][read], rec["ret_seq"][read] = rec["ret_client"][tail], rec["ret_seq"][tail]
            yield "lost_write", rec, strays
            break

    rec = fresh()
    rec["ret_client"][rows[0]] = rec["ret_seq"][rows[0]] = BAD_VALUE
    yield "foreign_value", rec, strays

    if len(records.get("more_key", ())):
        for name, rec in _Order(records).mutations(rng, fresh):
            yield name, rec, strays


class _Order:
    """Every key's chain of acknowledged writes, first and further keys
    alike, over the entries of ``check.History``: entry ``e < n`` is record
    ``e`` with its first key, ``e >= n`` is row ``e - n`` of the ``more_*``
    columns (the history is a sound one: every such row has its command)."""

    def __init__(self, rec: dict):
        hist = History(rec)
        cols = hist.cols
        self.rec, self.n, self.record, self.key = rec, hist.n, hist.row, cols["key"]
        self.ident = [(c << 32) | s for c, s in zip(cols["client"], cols["seq"])]
        self.ret = [(c << 32) | s if c >= 0 else -1 for c, s in zip(cols["ret_client"], cols["ret_seq"])]
        self.returned_by: dict[tuple[int, int], list[int]] = {}  # (key, value's id) -> entries
        for entry in range(hist.entries):
            if cols["status"][entry] == OK:
                self.returned_by.setdefault((self.key[entry], self.ret[entry]), []).append(entry)
        writes = [e for e in range(hist.entries) if cols["op"][e] == PUT and cols["status"][e] == OK]
        self.on = {(self.ident[e], self.key[e]): e for e in writes}  # who wrote what where
        # acknowledged writes of several keys: record -> its entries, the first key's first
        self.entries_of = {record: [record] + more for record, more in hist.more_of.items()
                           if cols["op"][record] == PUT and cols["status"][record] == OK}

    def set_returned(self, rec: dict, entry: int, value: int) -> None:
        """``entry`` now returned the value of the write with id ``value``."""
        client, seq = (-1, -1) if value < 0 else (value >> 32, value & 0xFFFFFFFF)
        if entry < self.n:
            rec["ret_client"][entry], rec["ret_seq"][entry] = client, seq
        else:
            rec["more_ret_client"][entry - self.n], rec["more_ret_seq"][entry - self.n] = client, seq

    def after(self, entry: int) -> list[int]:
        """The entries that returned ``entry``'s value: the next write, reads."""
        return self.returned_by.get((self.key[entry], self.ident[entry]), [])

    def follows(self, first: int, second: int) -> bool:
        """Whether ``second`` comes after ``first`` in their key's chain."""
        at = first
        while at is not None and at != second:
            at = next((e for e in self.after(at) if self.rec["op"][self.record[e]] == PUT), None)
        return at == second

    def mutations(self, rng, fresh):
        op, sent = self.rec["op"], self.rec["sent"]

        def pick(found: list, what: str):
            if not found:
                raise ValueError(f"nothing to mutate: no {what}")
            return found[int(rng.integers(len(found)))]

        # two commands over the same two keys: on one of them the later moves
        # to just before the earlier, ... p -> a -> x.. -> b -> n ... becomes p -> b -> a -> x.. -> n
        by_pair: dict[tuple[int, int], list[int]] = {}
        for record, entries in self.entries_of.items():
            for pair in itertools.combinations(sorted(self.key[e] for e in entries), 2):
                by_pair.setdefault(pair, []).append(record)
        pair, records = pick([item for item in by_pair.items() if len(item[1]) >= 2],
                             "two acknowledged commands that share two keys")
        a, b = (self.on[(self.ident[int(r)], pair[1])] for r in rng.choice(records, 2, replace=False))
        if not self.follows(a, b):
            a, b = b, a
        new = fresh()
        for entry in self.after(b):
            self.set_returned(new, entry, self.ret[b])
        self.set_returned(new, b, self.ret[a])
        self.set_returned(new, a, self.ident[b])
        yield "cross_key_swap", new

        # a command's write on a further key is gone: what follows it there
        # returns what the command itself returned
        entry = pick([e for entries in self.entries_of.values() for e in entries[1:] if self.after(e)],
                     "further key of a command that anything follows")
        new = fresh()
        for later in self.after(entry):
            self.set_returned(new, later, self.ret[entry])
        yield "torn_command", new

        # x -> c on one key, c -> d on another: d acknowledged before x was sent
        paths = []
        for record, entries in self.entries_of.items():
            x = self.on.get((self.ret[entries[1]], self.key[entries[1]]))
            d = next((e for e in self.after(record) if op[self.record[e]] == PUT), None)
            if x is not None and d is not None and self.record[x] != self.record[d]:
                paths.append((bool(sent[record] < sent[self.record[x]]), self.record[x], self.record[d]))
        # best where c was sent before x: then no key's own chain shows it, only the path
        _, early, late = pick([p for p in paths if p[0]] or paths,
                              "command that follows one on a key and precedes one on another")
        new = fresh()
        new["acked"][late] = new["sent"][early] - 0.002
        new["sent"][late] = new["due"][late] = new["acked"][late] - 0.001
        yield "cross_key_inversion", new


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("run_dir")
    parser.add_argument("--seeds", default="1,2,3")
    args = parser.parse_args(argv)
    history = dict(np.load(os.path.join(args.run_dir, "history.npz")))
    strays = history.pop("strays")
    sound = check_history(history, strays)
    print("CONTROL sound history:", "accepted" if sound["correct"] else "REJECTED",
          json.dumps(sound["stats"]))
    ok = sound["correct"]
    for seed in (int(s) for s in args.seeds.split(",")):
        for name, rec, extra in mutations(history, strays, seed):
            verdict = check_history(rec, extra)
            caught = not verdict["correct"]
            ok &= caught
            first = verdict["witnesses"][0]["check"] if verdict["witnesses"] else None
            print(f"CONTROL seed {seed} {name}: violations {verdict['stats']['violations']}"
                  f" (the check passes only at 0) -> {'rejected' if caught else 'ACCEPTED'} [{first}]")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
