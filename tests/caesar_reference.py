"""The plain reference of the Caesar round as the served path runs it: its
equations one command at a time over ``dict``s.  Nothing here is the
program's: no import from ``fantoch_tpu``, ``jax`` or ``numpy``, no batch
tensor, no sort network, no hashing of keys.

The protocol is Caesar (Arun, Peluso, Palmieri, Losa, Ravindran, DSN'17) as
upstream implements it (``fantoch_ps/src/protocol/caesar.rs:216-451``,
executor ``fantoch_ps/src/executor/pred/mod.rs:132-186``, quorums
``fantoch/src/config.rs:283``) in the dense, round-based form of
``parallel/mesh_step.py`` ``caesar_protocol_step``:

* **Clocks.**  Each of ``n`` replicas keeps ``clock[key]``, the highest
  timestamp it knows on the key.
* **A round** is given its commands in arrival order, what the last round
  carried first.  Its commands are concurrent: a replica hears of the
  round's commits when the round ends.
* **Proposal.**  For each uncommitted command, in that order, every replica
  proposes ``max(clock[k] for its keys) + 1`` and occupies that value on
  those keys at once, so the next command on a key is numbered above it.
* **Fast path.**  The command is fast iff the first ``3n//4 + 1`` replicas
  proposed the same value, which is then its clock.
* **Retry.**  Otherwise its clock is the maximum of the live replicas'
  proposals, and it commits iff at least ``n//2 + 1`` replicas are live.
  Uncommitted, it keeps that clock as its place in the order until it is
  proposed again, next round.
* **Learning.**  At the round's end every live replica keeps what it
  occupied and joins every committed clock on the command's keys.  A
  replica that is not live numbers the round's commands as a live one does,
  forgets them at the round's end and learns nothing.
* **Execution.**  Per key, committed commands execute in (clock, source,
  sequence) order up to the first uncommitted command on that key (a
  predecessor of unknown fate blocks); a command over several keys executes
  only where it may on all of them, and held back on one it holds back what
  follows it on the others.  A ``dict`` is the store: a write returns the
  value it replaced.
* **Carry.**  What did not execute is carried into the next round, committed
  commands first, each class in arrival order, up to the pending capacity;
  what is beyond it is handed back (uncommitted, under its own dot) for the
  caller to submit again.

Two departures of the device round, noted and not repaired here:

1. **A replica that is not live still reports its stale proposal to the fast
   quorum** (``mesh_step.py`` masks the quorum by replica row, ``in_fq``, not
   by liveness; what is learnt is masked by ``live``).  In ``caesar.rs`` a
   crashed replica reports nothing, and a fast quorum that holds one cannot
   answer at all.  This file follows the round.  No flag and no cell of the
   benchmark reaches it (every served replica is live); the fault hook's PR
   (ROADMAP R6) decides it.
2. **At key width 2 the device numbers a bucket's run per key slot and then
   takes the command's maximum**, so a command's second key is not occupied
   at the command's own clock before the next command on that key is
   numbered, and clocks there may differ from the one-at-a-time rule above.
   Width 2 is held to the contract only (unique (clock, dot), one order for
   two commands on every key they share:
   ``tests/test_caesar_reference.py``); width 1, the benchmark cell's, is
   held to this file exactly.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Tuple

Dot = Tuple[int, int]  # (source, sequence)


class Command(NamedTuple):
    """A write of ``value`` to every key of ``keys``."""

    src: int
    seq: int
    keys: Tuple[str, ...]
    value: str

    @property
    def dot(self) -> Dot:
        return (self.src, self.seq)


class Verdict(NamedTuple):
    """What a round made of one command of its working set."""

    clock: Optional[int]  # the committed timestamp; None while uncommitted
    committed: bool
    fast: bool  # took the fast path in this round
    executed: bool
    returned: Optional[Tuple[Optional[str], ...]]  # per key, what the write replaced


class Round(NamedTuple):
    verdicts: Dict[Dot, Verdict]  # every command of the working set
    order: List[Dot]  # the executed ones, in execution order
    resubmit: List[Command]  # beyond the pending capacity: the caller's again
    slow_paths: int
    watermark: int  # the highest clock executed in this round, 0 if none


def quorum_sizes(n: int) -> Tuple[int, int]:
    """(fast, write) of config.rs:283."""
    return 3 * n // 4 + 1, n // 2 + 1


class Reference:
    def __init__(self, n: int, pending_capacity: int, live: Optional[int] = None):
        self.n, self.capacity = n, pending_capacity
        self.live = n if live is None else live  # replicas 0 .. live-1; may be set between rounds
        self.fast_quorum, self.write_quorum = quorum_sizes(n)
        self.clock: List[Dict[str, int]] = [{} for _ in range(n)]
        self.carried: List[Tuple[Command, Optional[int]]] = []  # (command, committed clock)
        self.store: Dict[str, str] = {}
        self.executed: Dict[str, List[Dot]] = {}  # key -> the dots applied to it, in order

    def round(self, batch: List[Command]) -> Round:
        assert 1 <= self.live <= self.n
        work = self.carried + [(cmd, None) for cmd in batch]
        live = range(self.live)

        # --- proposal, fast path, retry: one command at a time
        occupied = [dict(clock) for clock in self.clock]  # each replica's view inside the round
        clock: Dict[Dot, int] = {}  # committed timestamp, or an uncommitted command's place
        committed: Dict[Dot, bool] = {}
        fast: Dict[Dot, bool] = {}
        slow_paths = 0
        for cmd, known in work:
            fast[cmd.dot] = False
            if known is not None:
                clock[cmd.dot], committed[cmd.dot] = known, True
                continue
            proposals = []
            for view in occupied:
                proposal = max(view.get(key, 0) for key in cmd.keys) + 1
                for key in cmd.keys:
                    view[key] = proposal
                proposals.append(proposal)
            if len(set(proposals[: self.fast_quorum])) == 1:
                clock[cmd.dot], committed[cmd.dot], fast[cmd.dot] = proposals[0], True, True
                continue
            slow_paths += 1
            clock[cmd.dot] = max(proposals[replica] for replica in live)
            committed[cmd.dot] = self.live >= self.write_quorum

        # --- learning, at the round's end
        for replica in live:
            self.clock[replica] = occupied[replica]
            for cmd, _ in work:
                if committed[cmd.dot]:
                    for key in cmd.keys:
                        self.clock[replica][key] = max(self.clock[replica].get(key, 0), clock[cmd.dot])

        # --- execution: (clock, dot) order, behind no blocked command on any key
        in_order = sorted((clock[cmd.dot], cmd.src, cmd.seq) for cmd, _ in work)
        rank = {(src, seq): at for at, (_, src, seq) in enumerate(in_order)}
        blocked = {cmd.dot for cmd, _ in work if not committed[cmd.dot]}
        while True:
            hold: Dict[str, int] = {}
            for cmd, _ in work:
                if cmd.dot in blocked:
                    for key in cmd.keys:
                        hold[key] = min(hold.get(key, len(work)), rank[cmd.dot])
            held = {cmd.dot for cmd, _ in work
                    if any(rank[cmd.dot] >= hold.get(key, len(work)) for key in cmd.keys)}
            if held | blocked == blocked:
                break
            blocked |= held
        by_dot = {cmd.dot: cmd for cmd, _ in work}
        order = [(src, seq) for _, src, seq in in_order if (src, seq) not in blocked]
        returned: Dict[Dot, Tuple[Optional[str], ...]] = {}
        for dot in order:
            cmd = by_dot[dot]
            returned[dot] = tuple(self.store.get(key) for key in cmd.keys)
            for key in cmd.keys:
                self.store[key] = cmd.value
                self.executed.setdefault(key, []).append(dot)

        # --- carry: committed first, each class in arrival order
        left = [(cmd, clock[cmd.dot] if committed[cmd.dot] else None)
                for cmd, _ in work if cmd.dot in blocked]
        left.sort(key=lambda entry: entry[1] is None)  # stable
        self.carried, beyond = left[: self.capacity], left[self.capacity:]
        assert all(known is None for _, known in beyond), "a committed command cannot be proposed again"

        verdicts = {
            cmd.dot: Verdict(clock[cmd.dot] if committed[cmd.dot] else None, committed[cmd.dot],
                             fast[cmd.dot], cmd.dot in returned, returned.get(cmd.dot))
            for cmd, _ in work}
        return Round(verdicts, order, [cmd for cmd, _ in beyond], slow_paths,
                     max((clock[dot] for dot in order), default=0))
