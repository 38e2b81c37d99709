"""The plain reference of the Caesar round with a coordinator at every site
(``--protocol caesar``, one shard, one key a command, clients registered at
more than one site: ``parallel/mesh_step.py`` ``caesar_protocol_step(sites=n)``):
its rules one command at a time over ``dict``s and ``list``s.  Nothing here is
the program's round: no import from ``fantoch_tpu.parallel`` or
``fantoch_tpu.ops``, no ``jax``, no ``numpy``, no batch tensor, no sort
network, no scan (``bisect`` keeps a replica's index of a key in timestamp
order, as upstream's ``BTreeMap`` does).

The protocol is Caesar (Arun, Peluso, Palmieri, Losa, Ravindran, DSN'17) as
upstream implements it (``fantoch_ps/src/protocol/caesar.rs:216-451``, the
quorums of ``fantoch/src/config.rs:283``), read through this repository's port
of those files (``fantoch_tpu/protocol/caesar.py``, ``protocol/common/
pred_clocks.py``), in the dense, round-based form of the device round.  ``n``
replicas, one a site; one key (bucket) a command; ``fast, write = 3n//4 + 1,
n//2 + 1``.  Each rule beside its handler:

* **Coordinator, ring, order of timestamps** (``submit``, ``caesar.py:352-357``;
  ``Clock``, ``pred_clocks.py:21-37``).  A command ``x`` is submitted at site
  ``s = (dot.source - site_base) % n``; its fast quorum is the ring ``Q(x) =
  {(s + j) % n : j < fast}``.  A timestamp is an integer, and two commands that
  tie are ordered by dot (source, then sequence): upstream's ``Clock`` is
  ``(seq, process)`` and the coordinator's process is the dot's source.  Below,
  "higher" and "lower" are in that order, ``(T0, dot)``.
* **A replica's view of a round** (``submit:355-357``: ``MPropose`` goes to all
  ``n``).  Of the commands that propose this round, replica ``r`` has those of
  its own site first, in working order (carried, then new in arrival order),
  then every other, in working order.  Every ``MPropose`` of a round is handled
  before any ``MProposeAck``, ``MRetry`` or ``MCommit`` of it.
* **Proposal** (``submit``; ``_handle_mpropose:481-507``).  The coordinator
  reaching its own ``x`` on key ``k`` proposes ``T0(x) = clock_s[k] + 1`` and
  sets ``clock_s[k]`` to it (``clock_next``).  Every other replica reaching
  ``x`` joins ``clock_r[k] = max(clock_r[k], T0(x))`` (``clock_join``) and
  indexes ``x`` at ``T0(x)`` (``_update_clock``).  At that moment ``r`` has
  ``P_r(x)``, the commands on ``k`` it met earlier that are lower (its
  predecessors), and ``B_r(x)``, those that are higher, the blockers
  (``predecessors(..., higher=)``, ``pred_clocks.py:76-97``).  A live replica
  has indexed every carried, committed command of ``k``; one above ``T0(x)``
  is a blocker whose dependencies are final and do not hold ``x``.
* **The wait condition** (``_handle_mpropose:523-555``, ``_safe_to_ignore``,
  ``_try_to_unblock``).  If ``B_r(x)`` is empty ``r`` says ok at once.
  Otherwise it waits for each blocker's ``(clock, deps)`` and ignores a blocker
  ``y`` iff ``x`` is in ``deps(y)``; all ignored, it says ok, reporting
  ``P_r(x)``; one that cannot be ignored, it rejects.  ``deps(y)`` is what
  ``y``'s coordinator aggregated from its ring (``QuorumClocks.add``): the
  union over ``q`` in ``Q(y)`` of ``P_q(y)`` where ``q`` said ok, and of
  everything ``q`` has met on ``k`` where ``q`` rejected
  (``_reject_command:927-934`` takes the predecessors under a fresh, higher
  clock, after the whole view).  This file keeps no set: ``x`` is in
  ``P_q(y)`` iff ``q`` met ``x`` before ``y`` and ``x`` is lower, which the
  place of each command in ``q``'s walk says.  A command's fate needs only the
  fates of higher commands, and the highest of a key waits for nothing, so a
  key's proposing commands are resolved from the highest down.
* **Fast path and retry** (``_handle_mproposeack:606-619``, ``_handle_mretry``,
  ``_handle_mretryack``).  ``x`` is fast iff every member of ``Q(x)`` said ok;
  its clock is ``T0(x)``.  Otherwise each rejecting member ``r`` counter-proposes
  ``clock_r[k] + 1`` and keeps it (``clock_next``), in the order the commands
  are resolved, the retry clock ``T1(x)`` is the highest report of the ring (an
  ok reports ``T0(x)``), and ``x`` commits at ``T1(x)`` iff at least ``write``
  replicas are live.  Uncommitted, it keeps ``T1(x)`` as its place in the order
  until it is proposed again.
* **Learning, execution, carry**: as ``tests/caesar_reference.py`` has them.
  At the round's end a live replica keeps what it occupied (the timestamps it
  met and its own counter-proposals) and joins every committed clock; per key,
  committed commands execute in (clock, dot) order up to the first uncommitted
  one; what did not execute is carried, committed first, each class in working
  order, up to the pending capacity; the rest is dropped (the driver requeues
  it).

Departures from the handlers, noted and followed by the device round:

1. **Bucket aliasing.**  The driver hashes a key to a bucket and the round
   orders buckets.  The keys given to this file are the buckets.
2. **A per-bucket ceiling in place of the replica-wide sequence**
   (``SequentialKeyClocks._seq``), as the one-coordinator round has it: a
   timestamp is one above what the replica knows *on the key*.  On one hot key
   the two coincide (``tests/test_caesar_sites_port.py`` holds the port to
   this file there).
3. **The quorum is the ring**, where upstream counts the first ``fast`` acks
   to arrive, which under waiting "may not be the closest one"
   (``caesar.py:355-356``), and **the whole ring is awaited** where
   ``QuorumClocks.all`` may finish early on a majority with a rejection.
4. **A round is dense and no delay is injected**: the views are the only
   source of disagreement; the accept of a retry (``MRetry`` / ``MRetryAck``) is
   the same round, with no competing proposal for the dot.
5. **A replica's counter-proposals are made from the highest command down, and
   only by members of the command's ring; the committed clocks are joined at
   the round's end.**  The port rejects as soon as the first blocker that
   cannot be ignored is decided (``_try_to_unblock``: "reject ASAP", over a
   ``set``, so in hash order), replicas outside the ring answer too (and spend
   a ``clock_next``), and every ``MRetry`` and ``MCommit`` is joined as it
   arrives, so later counter-proposals lie above it.  None of that moves a
   verdict (a verdict needs the views and the ``T0``s alone), nor the clock of
   a fast command; it moves the clocks of the retried commands among
   themselves, all of which lie above every ``T0`` of the key either way.
6. **A retried command's tie is still broken by its dot**, where upstream's
   retry clock carries the rejecter's process.
7. **A replica that is not live still answers, from the clocks it had when it
   stopped learning** (``caesar_reference.py`` departure 1: the ring is masked
   by replica row, not by liveness); it has forgotten the carried commands,
   keeps nothing and learns nothing.  No flag and no cell reaches it.
8. **A carried, uncommitted command (only under the write quorum) is proposed
   anew as its site's submission of the next round** (``tempo_sites_reference
   .py`` departure 9): what its ring reported is withdrawn with the round, but
   for the timestamps the live replicas occupied.
9. **Arrival order inside a round is the sites' commands in turn.**  This file
   takes a round's commands in the order it is given them; the driver gives the
   round its batch by ``run/device_drivers.py`` ``_sites_in_turn``.

What the round tallies, beside ``CaesarStepOutput``'s fields, over the commands
committed this round: ``wait_rows``, those of whose ring at least one member
met a blocker; ``wait_acks``, such members, summed; ``reject_acks``, the
members that rejected; ``retry_clock_lift``, the sum of ``T1 - T0`` over the
retried.  And ``wait_passes``, the recursion's depth: one more than the longest
chain of proposing commands each of which has, at some member of its ring, the
next as a blocker it could not ignore if that one went fast (so its answer
hangs on that one's verdict); 0 where nothing proposes.
"""

from __future__ import annotations

from bisect import bisect_right, insort
from typing import Dict, List, NamedTuple, Optional, Tuple

PAD = -1  # no key: the row holds no command
NO_CLOCK = -1
TALLIES = ("wait_rows", "wait_acks", "reject_acks", "retry_clock_lift")


def quorum_sizes(n: int) -> Tuple[int, int]:
    """(fast, write) of ``config.rs:283``."""
    return 3 * n // 4 + 1, n // 2 + 1


def ring(site: int, n: int, fast: int) -> List[int]:
    """The fast quorum of the coordinator at ``site``; the coordinator first."""
    return [(site + j) % n for j in range(fast)]


class Carried(NamedTuple):
    key: int
    src: int
    seq: int
    clock: int  # NO_CLOCK until committed


class RoundResult(NamedTuple):
    """Over the working rows: the pending buffer's slots, then the batch."""

    clock: List[int]
    committed: List[bool]
    fast_path: List[bool]
    executed: List[bool]
    order: List[int]  # the executed rows, in execution order
    slow_paths: int
    pending: int
    dropped: int
    watermark: int  # the highest clock executed this round, 0 if none
    tallies: Dict[str, int]
    wait_passes: int
    # row -> what it proposed, T0 (the rows that proposed this round)
    proposed: Dict[int, int]
    # row -> member -> "ok" (no blocker), "waited" (blockers, all ignored) or
    # "rejected"
    answers: Dict[int, Dict[int, str]]


class CaesarSitesReference:
    def __init__(self, n: int, pending_capacity: int, site_base: int = 1,
                 live_replicas: Optional[int] = None):
        self.n, self.capacity, self.site_base = n, pending_capacity, site_base
        self.fast, self.write = quorum_sizes(n)
        self.live = [r < (n if live_replicas is None else live_replicas) for r in range(n)]
        self.clock: List[Dict[int, int]] = [{} for _ in range(n)]  # replica -> key -> clock
        self.pending: List[Carried] = []

    def site(self, cmd: Carried) -> int:
        return (cmd.src - self.site_base) % self.n

    def round(self, keys, srcs, seqs) -> RoundResult:
        n = self.n
        batch = [Carried(int(k), int(src), int(seq), NO_CLOCK)
                 for k, src, seq in zip(keys, srcs, seqs)]
        work: List[Optional[Carried]] = (
            self.pending + [None] * (self.capacity - len(self.pending)) + batch)
        work = [cmd if cmd is not None and cmd.key != PAD else None for cmd in work]
        rows = range(len(work))
        proposing = [w for w in rows if work[w] is not None and work[w].clock == NO_CLOCK]
        carried = [w for w in rows if work[w] is not None and work[w].clock != NO_CLOCK]

        # --- proposal: every replica walks its view, one command at a time
        now: List[Dict[int, int]] = [dict(clock) for clock in self.clock]
        proposed: Dict[int, int] = {}

        def stamp(w: int) -> Tuple[int, int, int]:  # a command's place among timestamps
            cmd = work[w]
            return (proposed[w] if cmd.clock == NO_CLOCK else cmd.clock, cmd.src, cmd.seq)

        # replica -> key -> what it has indexed there, in timestamp order
        index: List[Dict[int, List[Tuple[Tuple[int, int, int], int]]]] = [{} for _ in range(n)]
        met_at: List[Dict[int, int]] = [{} for _ in range(n)]  # replica -> row -> place in its walk
        blockers: List[Dict[int, List[int]]] = [{} for _ in range(n)]  # replica -> row -> B_r(x)
        for r in range(n):
            if self.live[r]:  # a live replica has every carried, committed command
                for w in carried:
                    insort(index[r].setdefault(work[w].key, []), (stamp(w), w))

        def meet(r: int, w: int) -> None:
            known = index[r].setdefault(work[w].key, [])
            higher = bisect_right(known, (stamp(w), w))
            blockers[r][w] = [y for _, y in known[higher:]]
            known.insert(higher, (stamp(w), w))
            met_at[r][w] = len(met_at[r])

        for r in range(n):  # the view's first part: its own site's commands
            for w in proposing:
                if self.site(work[w]) == r:
                    key = work[w].key
                    proposed[w] = now[r][key] = now[r].get(key, 0) + 1
                    meet(r, w)
        for r in range(n):  # ... then every other command
            for w in proposing:
                if self.site(work[w]) != r:
                    key = work[w].key
                    now[r][key] = max(now[r].get(key, 0), proposed[w])
                    meet(r, w)

        # --- the wait condition, the fast path and the retry: a key's
        # proposing commands from the highest down
        answers: Dict[int, Dict[int, str]] = {}
        fast_path = [False] * len(work)
        retry: Dict[int, int] = {}  # row -> T1
        depth: Dict[int, int] = {}

        def reported_if_ok(x: int, y: int) -> bool:
            """Whether ``x`` is in the union of ``P_q(y)`` over ``y``'s ring."""
            if stamp(x) > stamp(y):
                return False
            return any(met_at[q][x] < met_at[q][y]
                       for q in ring(self.site(work[y]), n, self.fast))

        def in_deps(x: int, y: int) -> bool:
            """Whether ``x`` is in what ``y``'s coordinator aggregated."""
            for q, said in answers[y].items():
                if said == "rejected":
                    if x in met_at[q]:  # everything it has met on the key
                        return True
                elif met_at[q][x] < met_at[q][y] and stamp(x) < stamp(y):
                    return True
            return False

        for x in sorted(proposing, key=stamp, reverse=True):
            key = work[x].key
            answers[x], depth[x] = {}, 0
            for r in ring(self.site(work[x]), n, self.fast):
                said = "ok" if not blockers[r][x] else "waited"
                for y in blockers[r][x]:
                    if work[y].clock != NO_CLOCK:  # committed: its deps are final
                        said = "rejected"
                        continue
                    if not reported_if_ok(x, y):  # the answer hangs on y's verdict
                        depth[x] = max(depth[x], depth[y] + 1)
                    if not in_deps(x, y):
                        said = "rejected"
                answers[x][r] = said
            fast_path[x] = all(said != "rejected" for said in answers[x].values())
            if not fast_path[x]:
                reports = [proposed[x]]
                for r, said in answers[x].items():
                    if said == "rejected":
                        now[r][key] += 1
                        reports.append(now[r][key])
                retry[x] = max(reports)

        # --- commit
        clock = [NO_CLOCK if cmd is None else cmd.clock for cmd in work]
        committed = [c != NO_CLOCK for c in clock]
        place = list(clock)  # an uncommitted command's place in the order is its T1
        accepted = sum(self.live) >= self.write
        tallies = dict.fromkeys(TALLIES, 0)
        for x in proposing:
            place[x] = proposed[x] if fast_path[x] else retry[x]
            if fast_path[x] or accepted:
                committed[x], clock[x] = True, place[x]
                waited = sum(said != "ok" for said in answers[x].values())
                tallies["wait_rows"] += waited > 0
                tallies["wait_acks"] += waited
                tallies["reject_acks"] += sum(
                    said == "rejected" for said in answers[x].values())
                tallies["retry_clock_lift"] += place[x] - proposed[x]

        # --- learning, at the round's end
        for r in range(n):
            if self.live[r]:
                self.clock[r] = now[r]
                for w in rows:
                    if work[w] is not None and committed[w]:
                        key = work[w].key
                        self.clock[r][key] = max(self.clock[r].get(key, 0), clock[w])

        # --- execution: per key in (clock, dot) order, up to the first uncommitted
        def clock_dot(w: int):
            return (place[w], work[w].src, work[w].seq)

        by_key: Dict[int, List[int]] = {}
        for w in rows:
            if work[w] is not None:
                by_key.setdefault(work[w].key, []).append(w)
        executed = [False] * len(work)
        for on_key in by_key.values():
            for w in sorted(on_key, key=clock_dot):
                if not committed[w]:
                    break
                executed[w] = True
        order = sorted((w for w in rows if executed[w]), key=clock_dot)

        # --- what is carried: committed commands first, each class in order
        left = [w for w in rows if work[w] is not None and not executed[w]]
        left.sort(key=lambda w: (not committed[w], w))
        self.pending = [work[w]._replace(clock=clock[w]) for w in left[: self.capacity]]

        return RoundResult(
            clock=clock, committed=committed, fast_path=fast_path, executed=executed,
            order=order, slow_paths=len(retry), pending=min(len(left), self.capacity),
            dropped=max(len(left) - self.capacity, 0),
            watermark=max((clock[w] for w in order), default=0),
            tallies=tallies, wait_passes=max((d + 1 for d in depth.values()), default=0),
            proposed=proposed, answers=answers)
