"""What decides ``correct``: the answers clients received, and nothing else.

The plain reference is the sequential key-value store itself, rebuilt from
the answers.  The program's store returns the previous value on a ``Put``
and every value written here names its write, so for each key the
acknowledged writes and the values they returned spell out the order in
which the store applied them.  That order is replayed through a ``dict``
and held to real time.  Nothing here looks at how many requests failed or
how late anything ran: a failed write's fate is open (it may or may not be
in the chain), and that never makes a run incorrect.

A record is one command.  A command over several keys has, beside its
record, one row for each further key in the ``more_*`` columns, tied to it
by its rifl (``more_client``, ``more_seq``): the key, the value that key
returned and how many answers came for it.  A history without such rows is
checked exactly as it was before there were any.

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

Where commands write several keys, the chains of all keys are one graph
over commands (an edge for every "returned the value of"), and the store
applied every command at one point:

5. ``partial_answer``: an acknowledged command has exactly one answer for
   every key it names.
6. ``cross_key_cycle``: that graph has no cycle: two commands that share two
   keys stand in the same order on both.
7. ``replay`` over all keys: the commands, applied in one topological order
   of the graph to one ``dict``, return on every key what the run returned.
8. ``real_time`` over the graph: no command was acknowledged before one it
   follows, through whatever keys, was sent.

Stamps come from one monotonic clock; ``sent`` is taken before the bytes
leave and ``acked`` after they arrive, so "acked before sent" is never
claimed of two operations that overlapped.
"""

from __future__ import annotations

import math
import time
from typing import Any

import numpy as np

from benchmark.generators.kv_loop import BAD_VALUE, GET, NONE_VALUE, OK, PUT

_INITIAL = -1  # the id of "no value yet"
MAX_WITNESSES = 8
MORE = "more_"  # prefix of the columns that hold the further keys of commands
MORE_FIELDS = (
    ("more_client", np.int32), ("more_seq", np.int32), ("more_key", np.int32),
    ("more_ret_client", np.int32), ("more_ret_seq", np.int32), ("more_answers", np.int8),
)


def _ident(client: int, seq: int) -> int:
    return (int(client) << 32) | int(seq)


def command_rows(records: dict) -> np.ndarray:
    """For each ``more_*`` row the row of its command (-1: no such rifl)."""
    if len(records.get("more_key", ())) == 0:
        return np.zeros(0, np.int64)
    idents = (records["client"].astype(np.int64) << 32) | records["seq"].astype(np.int64)
    wanted = (records["more_client"].astype(np.int64) << 32) | records["more_seq"].astype(np.int64)
    order = np.argsort(idents, kind="stable")
    at = np.minimum(np.searchsorted(idents[order], wanted), len(idents) - 1)
    return np.where(idents[order][at] == wanted, order[at], -1)


class History:
    """Merged records as plain lists, with a witness writer.

    An *entry* is one (command, key): entries ``0 .. n-1`` are the records
    themselves, with their first key, and the entries from ``n`` on are the
    ``more_*`` rows.  ``cols`` holds every column per entry."""

    def __init__(self, records: dict):
        main = {name: np.asarray(col) for name, col in records.items()
                if name != "strays" and not name.startswith(MORE)}
        self.n = len(main["client"])
        more = {name: np.asarray(records[name]) for name, _ in MORE_FIELDS
                if name in records}
        self.row = list(range(self.n))  # entry -> its command's record
        self.answers: list[int] = []    # per more_* row
        self.orphans: list[int] = []    # more_* rows of no command
        if len(more.get("more_key", ())):
            rows = command_rows({**main, **more})
            self.orphans = np.flatnonzero(rows < 0).tolist()
            keep = rows >= 0
            rows = rows[keep]
            self.row += rows.tolist()
            self.answers = more["more_answers"][keep].tolist()
            own = {"key": "more_key", "ret_client": "more_ret_client", "ret_seq": "more_ret_seq"}
            main = {name: np.concatenate([col, more[own[name]][keep] if name in own else col[rows]])
                    for name, col in main.items()}
        self.cols = {name: col.tolist() for name, col in main.items()}
        self.entries = len(self.row)
        self.more_of: dict[int, list[int]] = {}  # record -> its further entries
        for entry in range(self.n, self.entries):
            self.more_of.setdefault(self.row[entry], []).append(entry)

    def describe(self, entry: int | None) -> Any:
        if entry is None:
            return "initial (no value)"
        c = self.cols

        def returned(e: int) -> Any:
            if c["status"][e] != OK:
                return None
            return {NONE_VALUE: "none", BAD_VALUE: "not a value of this run"}.get(
                c["ret_client"][e], f"{c['ret_client'][e]}:{c['ret_seq'][e]}")

        acked = c["acked"][entry]
        out = {
            "write" if c["op"][entry] == PUT else "get": f"{c['client'][entry]}:{c['seq'][entry]}",
            "phase": c["phase"][entry], "status": c["status"][entry],
            "sent": c["sent"][entry], "acked": None if math.isnan(acked) else acked,
            "returned": returned(entry),
        }
        first = self.row[entry]
        if first in self.more_of:  # every key of the command, and what each returned
            out["keys"] = {str(c["key"][e]): returned(e) for e in [first] + self.more_of[first]}
        return out

    def entry_on(self, record: int, key: int) -> int | None:
        """The entry of ``record``'s command on ``key``."""
        if self.cols["key"][record] == key:
            return record
        for entry in self.more_of.get(record, ()):
            if self.cols["key"][entry] == key:
                return entry
        return None


def check_history(records: dict, strays=()) -> dict:
    """``{"correct", "witnesses", "stats"}`` for one run's merged records."""
    began = time.perf_counter()
    hist = History(records)
    cols = hist.cols
    witnesses: list[dict] = []

    def violation(check: str, key, rows, note: str = "", **more) -> None:
        if len(witnesses) < MAX_WITNESSES:
            witnesses.append({"check": check, "key": key, "note": note,
                              "ops": [hist.describe(r) for r in rows], **more})
        stats["violations"] += 1

    stats = {"violations": 0, "requests": hist.n, "keys": 0, "acked_writes": 0,
             "open_writes": 0, "gets_checked": 0, "keys_with_open_writes": 0,
             "longest_chain": 0}

    # 1. exactly once, only what was sent
    for client, seq, when in np.asarray(strays, dtype=np.float64).reshape(-1, 3).tolist():
        violation("ack_unmatched", None, [],
                  f"reply for {int(client)}:{int(seq)} at {when}: never sent, or acknowledged twice")
    idents = [_ident(c, s) for c, s in zip(cols["client"], cols["seq"])]
    row_of = dict(zip(idents[: hist.n], range(hist.n)))
    if len(row_of) != hist.n:
        violation("ack_unmatched", None, [], "two requests share one rifl")

    graph = _Graph() if hist.more_of else None
    order = np.argsort(np.asarray(cols["key"]), kind="stable").tolist()
    keys = cols["key"]
    start = 0
    while start < hist.entries:
        end = start
        while end < hist.entries and keys[order[end]] == keys[order[start]]:
            end += 1
        found = stats["violations"]
        _check_key(hist, idents, row_of, order[start:end], violation, stats, graph)
        if graph is not None and stats["violations"] != found:
            graph.loose.add(keys[order[start]])  # its chain is broken: named once, here
        stats["keys"] += 1
        start = end
    stats["multi_key_commands"] = len(hist.more_of)
    stats["cross_key_edges"] = 0
    if graph is not None:
        _check_commands(hist, idents, graph, violation, stats)
    stats["check_seconds"] = time.perf_counter() - began
    return {"correct": stats["violations"] == 0, "witnesses": witnesses, "stats": stats}


class _Graph:
    """What the keys' chains say about the order of commands."""

    def __init__(self):
        self.edges: list[tuple[int, int, int]] = []  # (earlier record, later record, key)
        self.applied: set[int] = set()  # entries of failed writes that were applied after all
        self.loose: set[int] = set()    # keys with no one known chain: not replayed across keys
        self.flagged: set[int] = set()  # records a key's own real-time check has named


def _check_key(hist: History, idents, row_of, rows, violation, stats, graph) -> None:
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
        if prev_row is None or op[prev_row] != PUT or (
                cols["key"][prev_row] != key and hist.entry_on(prev_row, key) is None):
            violation("unknown_value", key, [row], "returned a value nobody wrote to this key")
            return None
        return prev

    def entry_of(ident: int) -> int:
        return hist.entry_on(row_of[ident], key)

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
            violation("fork", key, [successor[prev], row,
                                    None if prev == _INITIAL else entry_of(prev)],
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
        segment: list[int | None] = [None if head == _INITIAL else entry_of(head)]
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
    if graph is not None:
        # the initial run comes before every other: its writes follow each
        # other from the first value on, so a failed write came after them
        tail = segments[0][-1]
        for segment in segments:
            if tail is not None and segment[0] is not None:
                graph.edges.append((hist.row[tail], hist.row[segment[0]], key))
            for earlier, later in zip(segment, segment[1:]):
                if earlier is not None:
                    graph.edges.append((hist.row[earlier], hist.row[later], key))
        graph.applied.update(segment[0] for segment in segments[1:])
        if len(segments) > 2:
            graph.loose.add(key)

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
                if graph is not None:
                    graph.flagged.add(hist.row[segment[pos]])
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
            segments.append([entry_of(value)])
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


def _check_commands(hist: History, idents, graph: _Graph, violation, stats) -> None:
    """Checks 5 to 8: the commands of all keys in one order."""
    cols = hist.cols
    status, op, sent, acked, keys = cols["status"], cols["op"], cols["sent"], cols["acked"], cols["key"]

    # 5. an acknowledged command was answered once on every key
    for entry in hist.orphans:
        violation("partial_answer", None, [], f"further key row {entry} belongs to no command sent")
    for entry in range(hist.n, hist.entries):
        count = hist.answers[entry - hist.n]
        if status[entry] == OK and count != 1:
            violation("partial_answer", keys[entry], [entry],
                      f"an acknowledged command with {count} answers for this key")

    # 6. one order: Kahn's algorithm over the edges of all chains
    later: dict[int, list[tuple[int, int]]] = {}
    waits = [0] * hist.n
    for earlier, after, key in graph.edges:
        later.setdefault(earlier, []).append((after, key))
        waits[after] += 1
        stats["cross_key_edges"] += earlier in hist.more_of or after in hist.more_of
    writes = [r for r in range(hist.n) if op[r] == PUT]
    free = [r for r in writes if waits[r] == 0]
    order: list[int] = []
    while free:
        record = free.pop()
        order.append(record)
        for after, _ in later.get(record, ()):
            waits[after] -= 1
            if waits[after] == 0:
                free.append(after)
    if len(order) < len(writes):
        cycle = _a_cycle(graph.edges, {r for r in writes if waits[r] > 0})
        violation("cross_key_cycle", None, [record for record, _ in cycle],
                  f"commands that precede each other through these keys; "
                  f"{len(writes) - len(order)} commands have no place in any one order",
                  keys=[key for _, key in cycle])
        return

    # 7. the plain reference: one dict over all keys, the commands applied in that order
    store: dict[int, int] = {}
    for record in order:
        for entry in [record] + hist.more_of.get(record, []):
            if status[entry] == OK:
                if cols["ret_client"][entry] == NONE_VALUE:
                    value = _INITIAL
                elif cols["ret_client"][entry] == BAD_VALUE:
                    value = None  # named under unknown_value already
                else:
                    value = _ident(cols["ret_client"][entry], cols["ret_seq"][entry])
                have = store.get(keys[entry], _INITIAL)
                if value is not None and value != have and keys[entry] not in graph.loose:
                    violation("replay", keys[entry], [entry],
                              "applied in the one order of all commands, the store returns another "
                              "value here")
            elif entry not in graph.applied:
                continue  # a failed write nobody saw: not applied, as far as the answers say
            store[keys[entry]] = idents[entry]

    # 8. real time through the graph: the earliest acknowledgement among all
    # that follow a command, carried from the last command backwards
    earliest: dict[int, tuple[float, int, int, int]] = {}  # record -> (ack, of, next hop, its key)
    for record in reversed(order):
        best = None
        for after, key in later.get(record, ()):
            direct = acked[after] if status[after] == OK else math.inf
            for found in ((direct, after, after, key), earliest.get(after)):
                if found is not None and (best is None or found[0] < best[0]):
                    best = (found[0], found[1], after, key)
        if best is None or best[0] == math.inf:
            continue
        earliest[record] = best
        if best[0] < sent[record] and record not in graph.flagged:
            path, at = [], record
            while at != best[1]:
                _, _, at, key = earliest[at]
                path.append(key)
            violation("real_time", None, [best[1], record],
                      "acknowledged before the other was sent, yet after it in the one order of "
                      "commands, through these keys", keys=path)


def _a_cycle(edges, left: set[int]) -> list[tuple[int, int]]:
    """One cycle among the records Kahn's algorithm could not place, as
    ``(record, key of the edge that leads to the next)``."""
    before: dict[int, tuple[int, int]] = {}
    for earlier, after, key in edges:
        if earlier in left and after in left:
            before[after] = (earlier, key)  # every record left has one
    at, seen = next(iter(before)), set()
    while at not in seen:
        seen.add(at)
        at = before[at][0]
    walk = [at]
    while before[walk[-1]][0] != at:
        walk.append(before[walk[-1]][0])
    walk.reverse()  # from "comes before" steps to the order of the edges
    return [(record, before[walk[(i + 1) % len(walk)]][1]) for i, record in enumerate(walk)]
