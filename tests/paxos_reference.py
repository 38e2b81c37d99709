"""The plain reference of the leader round as the served path runs it: the
slot log one command at a time over lists and a ``dict``.  Nothing here is
the program's: no import from ``fantoch_tpu``, ``jax`` or ``numpy``, no batch
tensor, no prefix sum, no sort.

The protocol is FPaxos as upstream runs it (``fantoch_ps/src/bin/fpaxos.rs``,
``fantoch_ps/src/protocol/fpaxos.rs`` over ``common/synod/multi.rs``
MultiSynod, accept quorum ``f + 1`` by ``fantoch/src/config.rs:258``,
execution by ``fantoch_ps/src/executor/slot.rs:96`` in contiguous slot order;
from memory) in the dense, round-based form of ``parallel/mesh_step.py``
``paxos_protocol_step``:

* **The leader** (replica 0) keeps ``next_slot``, the next slot of the log it
  hands out, and ``frontier``: every slot below it has executed.
* **A round** is given what the last round carried, lowest slot first, and
  then its batch, row for row (a row may be empty).  A carried command keeps
  its slot; a new one takes ``next_slot`` in batch order.
* **Accept.**  Replica ``r`` of ``n`` is live iff ``r < live``, and every live
  replica acknowledges every slot proposed, so ``acks`` is the number of live
  replicas.  A slot is chosen iff ``acks >= f + 1``.
* **Execution.**  The chosen slots that continue ``frontier`` without a gap
  execute, in slot order, each against the ``dict`` and returning what the
  program's store returns: a write the value it replaced, a read the value it
  found (``None`` where there was none).
* **Carry.**  What did not execute is carried into the next round, lowest
  slot first, up to the pending capacity.  What is beyond it is dropped,
  reported, and handed back under its own dot for the caller to submit
  again; its slots are handed back too (``next_slot -= dropped``), so the log
  stays dense.

Three departures of the device round from upstream's MultiSynod, followed
here and not repaired:

1. **No ballots and no phase 1**: the leader is fixed at ballot 0 and never
   changes (``mesh_step.py:1261-1263``, "ballot-0 leader; crashed replicas
   stay silent").  Upstream's ``multi.rs`` spawns a commander per slot under
   the leader's ballot and an acceptor rejects a stale one; the served path
   has no election (README "Known limits").
2. **One ack count for all of a round's slots**
   (``mesh_step.py:1268-1270``: one scalar ``psum`` of live acceptors,
   ``committed = valid & (slot >= 0) & (acks >= quorum)``).  Upstream counts
   ``MAccepted`` per slot, so two slots of one batch may be chosen in
   different rounds; here a round chooses all of its slots or none, and a
   gap in the log can only come from a carry.
3. **No durable acceptor state for a rolled-back slot**
   (``mesh_step.py:1286-1305``: the carry keeps the lowest ``pend_cap``
   slots and ``new_next = next_slot + new - dropped``).  Upstream's acceptor
   remembers what it accepted in a slot; here a dropped slot was accepted by
   nobody that remembers, so handing it to another command is safe.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

Dot = Tuple[int, int]  # (source, sequence)
Returned = Tuple[Optional[str]]  # what one operation on one key returned


class Command(NamedTuple):
    """A write of ``value`` to ``key``, or a read of it (``value`` None)."""

    src: int
    seq: int
    key: str
    value: Optional[str]

    @property
    def dot(self) -> Dot:
        return (self.src, self.seq)


class Row(NamedTuple):
    """The fate of one working row's command in one round."""

    dot: Dot
    slot: int
    chosen: bool
    executed: bool
    returned: Optional[Returned]  # None unless executed


class Round(NamedTuple):
    """What one round did, in the device's layout of the working set."""

    carried_rows: List[Row]  # what the last round carried, lowest slot first
    batch_rows: List[Optional[Row]]  # the batch row for row; None = an empty row
    order: List[Dot]  # the executed commands, in slot order
    pending: int  # commands carried into the next round
    dropped: int  # commands beyond the pending capacity
    resubmit: List[Command]  # those, lowest slot first, for the caller
    frontier: int
    next_slot: int


class Reference:
    def __init__(self, n: int, f: int, pend_cap: int, live: Optional[int] = None):
        self.n, self.f, self.pend_cap = n, f, pend_cap
        self.live = n if live is None else live
        self.next_slot = 0
        self.frontier = 0
        self.carried: List[Tuple[int, Command]] = []  # (slot, command), lowest slot first
        self.store: Dict[str, str] = {}
        self.log: List[Dot] = []  # log[slot] is the command executed in that slot

    def _apply(self, cmd: Command) -> Returned:
        before = self.store.get(cmd.key)
        if cmd.value is not None:
            self.store[cmd.key] = cmd.value
        return (before,)

    def round(self, batch: Sequence[Optional[Command]]) -> Round:
        # the leader hands out slots: a carried command keeps its own
        working: List[Optional[Tuple[int, Command]]] = list(self.carried)
        for cmd in batch:
            if cmd is None:
                working.append(None)
            else:
                working.append((self.next_slot, cmd))
                self.next_slot += 1

        # one accept round: every live replica acknowledges every slot
        acks = sum(1 for replica in range(self.n) if replica < self.live)
        chosen = {slot for slot, _ in filter(None, working) if acks >= self.f + 1}

        # contiguous execution in slot order
        by_slot = sorted(filter(None, working), key=lambda entry: entry[0])
        returned: Dict[Dot, Returned] = {}
        order: List[Dot] = []
        for slot, cmd in by_slot:
            if slot not in chosen or slot != self.frontier:
                break
            returned[cmd.dot] = self._apply(cmd)
            order.append(cmd.dot)
            self.log.append(cmd.dot)
            self.frontier += 1

        # the carry: lowest slots first; the rest goes back, slots and all
        left = [entry for entry in by_slot if entry[1].dot not in returned]
        self.carried, beyond = left[: self.pend_cap], left[self.pend_cap:]
        self.next_slot -= len(beyond)

        rows = [
            None if entry is None else Row(
                entry[1].dot, entry[0], entry[0] in chosen, entry[1].dot in returned,
                returned.get(entry[1].dot),
            )
            for entry in working
        ]
        split = len(working) - len(batch)
        return Round(
            rows[:split], rows[split:], order, len(self.carried), len(beyond),
            [cmd for _, cmd in beyond], self.frontier, self.next_slot,
        )
