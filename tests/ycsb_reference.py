"""The plain reference of what a YCSB key-value deployment means: one
``dict``, one operation at a time, in the order given.  Nothing here is the
program's: no import from ``fantoch_tpu``, no batch, no round, no hashing of
keys.

The store's interface is the program's, stated plainly: a *read* returns the
record its key holds now (nothing where nobody wrote one); an *update* (and
the load's insert, which is the same operation on a key without a record)
replaces the whole record and returns the one it replaced.  Linearizability
of the served path is then: there is one order of all operations (the order
the rounds executed them in) whose replay through this class returns, on
every operation, what the client was sent."""

from __future__ import annotations

import random

READ, UPDATE = "read", "update"


class Reference:
    def __init__(self):
        self.records: dict[str, str] = {}
        self.reads = 0
        self.read_bytes = 0  # UTF-8 bytes of the records that reads returned

    def apply(self, kind: str, key: str, value: str | None = None) -> str | None:
        if kind == READ:
            record = self.records.get(key)
            self.reads += 1
            self.read_bytes += 0 if record is None else len(record.encode())
            return record
        assert kind == UPDATE and value is not None
        previous = self.records.get(key)
        self.records[key] = value
        return previous


def record(seed: int, number: int, record_bytes: int) -> str:
    """A whole record: seeded letters, led by the number of its write."""
    letters = random.Random(f"{seed}:{number}").choices("abcdefghijklmnopqrstuvwxyz", k=record_bytes)
    head = f"{number}:"
    return head + "".join(letters[len(head):])


def stream(seed: int, operations: int, keys: int, record_bytes: int, read_share: float = 0.95,
           hot: int = 3) -> list[tuple[str, str, str | None]]:
    """YCSB in small: a load (every key written once), then ``operations``
    reads and updates, ``read_share`` of them reads, half of them on the
    ``hot`` first keys.  ``(kind, key, value)`` in submission order."""
    rng = random.Random(seed)
    out = [(UPDATE, f"user{key}", record(seed, key, record_bytes)) for key in range(keys)]
    for number in range(keys, keys + operations):
        key = rng.randrange(hot) if rng.random() < 0.5 else rng.randrange(keys)
        if rng.random() < read_share:
            out.append((READ, f"user{key}", None))
        else:
            out.append((UPDATE, f"user{key}", record(seed, number, record_bytes)))
    return out
