"""What decides ``correct``: the answers clients received, and nothing else.

The plain reference is the sequential key-value store itself, rebuilt from
the answers.  The program's store returns the previous value on a ``Put``
and every value written here names its write, so for each key the
acknowledged writes and the values they returned spell out the order in
which the store applied them.  That order is replayed through a ``dict``
and held to real time.  Nothing here looks at how many requests failed or
how late anything ran: a failed write's fate is open (it may or may not be
in the chain), and that never makes a run incorrect.

Checks (names as they appear in a witness):

1. ``ack_unmatched``: every acknowledgement matches one request sent, and
   none is acknowledged twice.
2. ``fork`` / ``unknown_value`` / ``cycle`` / ``replay``: one chain per key,
   ``initial -> v1 -> v2 ...``, each write returning its predecessor's value.
3. ``real_time``: a write acknowledged before another on its key was sent
   precedes it in the chain.
4. ``stale_read`` / ``future_read``: a ``Get`` (the read-back after the drain
   above all) returns a value no older than the last write acknowledged
   before the ``Get`` was sent: an acknowledged write is not lost.

Stamps come from one monotonic clock; ``sent`` is taken before the bytes
leave and ``acked`` after they arrive, so "acked before sent" is never
claimed of two operations that overlapped.
"""

from __future__ import annotations

import math
from typing import Any

import numpy as np

from benchmark.generators.kv_loop import BAD_VALUE, GET, NONE_VALUE, OK, PUT

_INITIAL = -1  # the id of "no value yet"
MAX_WITNESSES = 8


def _ident(client: int, seq: int) -> int:
    return (int(client) << 32) | int(seq)


class History:
    """Merged records as plain lists, with a witness writer."""

    def __init__(self, records: dict):
        self.cols = {name: np.asarray(col).tolist() for name, col in records.items()
                     if name != "strays"}
        self.n = len(self.cols["client"])

    def describe(self, row: int | None) -> Any:
        if row is None:
            return "initial (no value)"
        c = self.cols
        returned: Any = None
        if c["status"][row] == OK:
            returned = {NONE_VALUE: "none", BAD_VALUE: "not a value of this run"}.get(
                c["ret_client"][row], f"{c['ret_client'][row]}:{c['ret_seq'][row]}"
            )
        acked = c["acked"][row]
        return {
            "write" if c["op"][row] == PUT else "get": f"{c['client'][row]}:{c['seq'][row]}",
            "phase": c["phase"][row], "status": c["status"][row],
            "sent": c["sent"][row], "acked": None if math.isnan(acked) else acked,
            "returned": returned,
        }


def check_history(records: dict, strays=()) -> dict:
    """``{"correct", "witnesses", "stats"}`` for one run's merged records."""
    hist = History(records)
    cols = hist.cols
    witnesses: list[dict] = []

    def violation(check: str, key, rows, note: str = "") -> None:
        if len(witnesses) < MAX_WITNESSES:
            witnesses.append({"check": check, "key": key, "note": note,
                              "ops": [hist.describe(r) for r in rows]})
        stats["violations"] += 1

    stats = {"violations": 0, "requests": hist.n, "keys": 0, "acked_writes": 0,
             "open_writes": 0, "gets_checked": 0, "keys_with_open_writes": 0,
             "longest_chain": 0}

    # 1. exactly once, only what was sent
    for client, seq, when in np.asarray(strays, dtype=np.float64).reshape(-1, 3).tolist():
        violation("ack_unmatched", None, [],
                  f"reply for {int(client)}:{int(seq)} at {when}: never sent, or acknowledged twice")
    idents = [_ident(c, s) for c, s in zip(cols["client"], cols["seq"])]
    row_of = dict(zip(idents, range(hist.n)))
    if len(row_of) != hist.n:
        violation("ack_unmatched", None, [], "two requests share one rifl")

    order = np.argsort(np.asarray(records["key"]), kind="stable").tolist()
    keys = cols["key"]
    start = 0
    while start < hist.n:
        end = start
        while end < hist.n and keys[order[end]] == keys[order[start]]:
            end += 1
        _check_key(hist, idents, row_of, order[start:end], violation, stats)
        stats["keys"] += 1
        start = end
    return {"correct": stats["violations"] == 0, "witnesses": witnesses, "stats": stats}


def _check_key(hist: History, idents, row_of, rows, violation, stats) -> None:
    cols = hist.cols
    key = cols["key"][rows[0]]
    status, op, sent, acked = cols["status"], cols["op"], cols["sent"], cols["acked"]

    def returned(row: int) -> int | None:
        """The id whose value ``row`` returned; None where it cannot be one."""
        if cols["ret_client"][row] == NONE_VALUE:
            return _INITIAL
        if cols["ret_client"][row] == BAD_VALUE:
            violation("unknown_value", key, [row], "returned a value no client wrote")
            return None
        prev = _ident(cols["ret_client"][row], cols["ret_seq"][row])
        prev_row = row_of.get(prev)
        if prev_row is None or cols["key"][prev_row] != key or op[prev_row] != PUT:
            violation("unknown_value", key, [row], "returned a value nobody wrote to this key")
            return None
        return prev

    writes = [r for r in rows if op[r] == PUT]
    acked_writes = [r for r in writes if status[r] == OK]
    stats["acked_writes"] += len(acked_writes)
    stats["open_writes"] += len(writes) - len(acked_writes)

    # 2. one chain: who returned whose value
    successor: dict[int, int] = {}
    for row in acked_writes:
        prev = returned(row)
        if prev is None:
            continue
        if prev in successor:
            violation("fork", key, [successor[prev], row, row_of.get(prev)],
                      "two writes returned the same value")
            continue
        successor[prev] = row

    # segments: runs of consecutive writes.  The first starts at the initial
    # value; every other starts at a failed write that was applied after all
    # (its own predecessor is unknown).
    acked_ids = {idents[r] for r in acked_writes}
    heads = [_INITIAL] + [p for p in successor if p != _INITIAL and p not in acked_ids]
    segments: list[list[int | None]] = []  # rows; None stands for the initial value
    where: dict[int, tuple[int, int]] = {}
    reached = 0
    for head in heads:
        segment: list[int | None] = [None if head == _INITIAL else row_of[head]]
        store = {key: head}  # the plain reference: a dict, replayed
        where[head] = (len(segments), 0)
        row = successor.get(head)
        while row is not None:
            if store[key] != returned(row):
                violation("replay", key, [row], "replay gives this write another value")
            store[key] = idents[row]
            where[idents[row]] = (len(segments), len(segment))
            segment.append(row)
            reached += 1
            row = successor.get(idents[row])
        segments.append(segment)
    if reached != len(successor):
        lost = [r for r in acked_writes if idents[r] not in where]
        violation("cycle", key, lost[:2], "writes that return each other's values")
        return
    stats["longest_chain"] = max(stats["longest_chain"], max(map(len, segments)) - 1)
    if len(segments) > 1:
        stats["keys_with_open_writes"] += 1

    def ack_time(row: int | None) -> float:
        if row is None:
            return -math.inf  # the initial value was there before anything
        return acked[row] if status[row] == OK else math.inf

    def send_time(row: int | None) -> float:
        return -math.inf if row is None else sent[row]

    # 3. real time inside each segment: nothing later in the chain was
    # acknowledged before something earlier was sent
    earliest_ack_after: list[list[tuple[float, int | None]]] = []
    for segment in segments:
        suffix: list[tuple[float, int | None]] = [(math.inf, None)] * (len(segment) + 1)
        for pos in range(len(segment) - 1, -1, -1):
            here = (ack_time(segment[pos]), segment[pos])
            suffix[pos] = min(here, suffix[pos + 1], key=lambda pair: pair[0])
            if suffix[pos + 1][0] < send_time(segment[pos]):
                violation("real_time", key, [suffix[pos + 1][1], segment[pos]],
                          "acknowledged before the other was sent, yet after it in the chain")
        earliest_ack_after.append(suffix)

    # order between segments, as far as real time fixes it
    before: set[tuple[int, int]] = {(0, s) for s in range(1, len(segments))}
    if len(segments) > 1:
        first_ack = [min((ack_time(r) for r in seg if r is not None), default=math.inf)
                     for seg in segments]
        last_send = [max(send_time(r) for r in seg) for seg in segments]
        for x in range(len(segments)):
            for y in range(len(segments)):
                if x != y and first_ack[x] < last_send[y]:
                    before.add((x, y))

    # 4. reads: no older than the last write acknowledged before the read
    for row in rows:
        if op[row] != GET or status[row] != OK:
            continue
        stats["gets_checked"] += 1
        value = returned(row)
        if value is None:
            continue
        if value not in where:  # a failed write nobody overwrote: applied after all
            where[value] = (len(segments), 0)
            segments.append([row_of[value]])
            earliest_ack_after.append([(math.inf, None), (math.inf, None)])
            before.add((0, len(segments) - 1))
        seg, pos = where[value]
        if send_time(segments[seg][pos]) > acked[row]:
            violation("future_read", key, [row, segments[seg][pos]],
                      "read a value whose write was sent after the read was acknowledged")
        newer_ack, newer = earliest_ack_after[seg][pos + 1]
        if newer_ack < sent[row]:
            violation("stale_read", key, [row, newer, segments[seg][pos]],
                      "a write acknowledged before the read was sent is newer than what it read")
        for other, segment in enumerate(segments):
            if other == seg:
                continue
            if earliest_ack_after[other][0][0] < sent[row]:
                before.add((other, seg))
            if acked[row] < max(send_time(r) for r in segment):
                before.add((seg, other))

    if len(segments) > 1 and not _orderable(len(segments), before):
        violation("real_time", key, [seg[-1] for seg in segments][:4],
                  "no order of this key's runs of writes agrees with real time "
                  "(a lost or reordered acknowledged write)")


def _orderable(count: int, before: set[tuple[int, int]]) -> bool:
    """Whether the "must come before" pairs leave any total order."""
    waiting = {node: {a for a, b in before if b == node} for node in range(count)}
    placed: set[int] = set()
    while len(placed) < count:
        free = [n for n in waiting if n not in placed and waiting[n] <= placed]
        if not free:
            return False
        placed.update(free)
    return True
