"""The plain reference of the Tempo (Newt) round with a coordinator at every
site (``--protocol newt``, one shard, one key a command, clients registered at
more than one site: ``parallel/mesh_step.py`` ``newt_protocol_step(sites=n)``):
its rules one command at a time over ``dict``s and ``list``s.  Nothing here is
the program's round: no import from ``fantoch_tpu.parallel`` or
``fantoch_tpu.ops``, no ``jax``, no ``numpy``, no batch tensor, no sort
network, no scan.

The protocol is Tempo (Enes, Baquero, Gotsman, Sutra, EuroSys'21) as upstream
implements it (``fantoch_ps/src/protocol/newt.rs``; the clocks
``common/table/clocks/keys/sequential.rs``; ``QuorumClocks``; the quorums of
``fantoch/src/config.rs``; the executor ``fantoch_ps/src/executor/table/mod.rs``),
read through this repository's port of those files (``fantoch_tpu/protocol/
newt.py``, ``protocol/common/table_clocks.py``, ``core/config.py``), in the
dense, round-based form of the device round:

* **Sites and replicas.**  ``n`` replicas, one a site, one shard, one key
  (bucket) a command.  ``fast``, ``write``, ``threshold`` are
  ``newt_quorum_sizes(n, f)``: ``n // 2 + f``, ``f + 1``, ``n // 2 + 1``.  A
  command is submitted at a site; the replica there coordinates it, and its dot
  is ``(coordinator's process, the coordinator's next sequence)``: the site is
  ``(dot.source - site_base) % n``.  The coordinator's fast quorum is the ring
  ``(s + j) % n``, ``j < fast`` (a ring stands for "the closest").
* **A replica's view of a round.**  Of the commands that propose this round,
  replica ``r`` has those of its own site first, in working order (what was
  carried, in carried order, then the round's new commands in arrival order),
  then every other command, in working order.  (All commands of one round are
  concurrent: a replica has its own clients' commands before any ``MCollect``
  reaches it.)
* **Proposals.**  The coordinator ``s``, reaching its command ``x`` on key
  ``k`` in its view, proposes ``c(x) = clock_s[k] + 1`` and sets ``clock_s[k] =
  c(x)`` (``newt.py:497``, ``proposal(cmd, 0)``).  A member ``r != s`` of ``x``'s
  quorum, reaching ``x`` in its own view, proposes ``p_r(x) = max(c(x),
  clock_r[k] + 1)`` and sets ``clock_r[k] = p_r(x)`` (``:539``, ``proposal(cmd,
  remote_clock)``).  A replica outside the quorum keeps the payload, proposes
  nothing, and its clock does not move before the commit (``:522-533``).
* **The timestamp** ``t(x)`` is the highest proposal of the quorum.  **Fast
  path** iff at least ``f`` members reported exactly ``t(x)`` (``:617-642``,
  ``quorum_clocks.add``: ``max_count >= f``).  Otherwise the accept round at
  ballot 0 proposes ``t(x)`` and ``x`` commits at ``t(x)`` iff at least
  ``write`` replicas are live.
* **Votes.**  Every live replica votes ``k`` up to every committed ``t(x)``,
  and its clock for ``k`` never lags its votes.
* **Stability.**  ``k`` is stable up to the ``threshold``-th highest vote.  A
  command executes when its clock is stable and no committed, held-back
  command precedes it in (clock, dot) order on its key; executed commands come
  out in (clock, dot) order.
* **Carry.**  What did not execute is carried to the next round, committed
  commands first, each class in working order, up to the pending capacity; the
  rest is dropped (the driver requeues it).

Departures from ``newt.rs``, noted and followed by the device round:

1. **Bucket aliasing.**  The driver hashes a key to a bucket and the round
   orders buckets, so two keys of one bucket conflict.  The keys given to this
   file are the buckets.
2. **A round is dense.**  Every command of a round is proposed, committed (or
   not) and voted on inside the round; votes are a watermark a key and replica,
   not ranges (``RangeEventSet`` frontiers where votes are consumed
   contiguously).
3. **Every replica has every command of the round in its view**, not only the
   members of the command's fast quorum (upstream sends ``MCollect`` to all).
   Only members propose, so this changes nothing a member has proposed
   *before* a command: a replica outside the quorum moves no clock.
4. **A replica that is not live still proposes, from the clocks it had when it
   stopped learning** (the round masks the quorum by replica row, not by
   liveness); it accepts nothing on the slow path, votes nothing and learns
   nothing.  No flag and no cell of the benchmark reaches it.
5. **No delay is injected**: the view above is the only source of
   disagreement.
6. **The accept round is the same round**: a command that missed the fast path
   commits in the round that proposed it (ballot 0, skip-prepare), with no
   competing proposal for its dot.
7. **Arrival order inside a round is the sites' commands in turn.**  This file
   takes a round's commands in the order it is given them; the driver gives the
   round its batch by ``run/device_drivers.py`` ``_sites_in_turn``.
8. **A proposal lasts as long as its round.**  A replica's clock for a key is,
   between rounds, the highest of what it had and its votes (a live replica's
   proposals for committed commands are at most their timestamps, so nothing is
   lost with them); a proposal for a command that did not commit (only under
   the write quorum) is withdrawn with the round.
9. **A carried, uncommitted command (only under the write quorum) is proposed
   anew as its site's submission of the next round**: it stands in every view
   where a new command of its site stands, before the round's new commands of
   that site (working order), and not before every site's new commands.  With
   "what was carried first, for everyone" the coordinator of a carried command
   would meet other sites' carried commands before its own proposal and the
   replicas' scans would be coupled; a committed carried command proposes
   nothing, so its place in a view is no one's business.
10. **No detached bump on an ack** (``newt.rs:506-521``, an optimisation: a
   member raises its clock to the highest clock seen so far): the commit's
   votes raise every live replica's clock to ``t(x)`` at the round's end.
11. **Two commands of a round may tie on a clock**; the dot breaks the tie
   (``mod.rs:18``, the votes table's sort id).

What the round tallies, beside ``NewtStepOutput``'s fields:
``site_clock_spread``, the sum over the commands committed this round of
``t(x)`` less the lowest proposal of ``x``'s quorum; ``clock_ties``, the
commands committed this round that share (key, clock) with another one
committed this round; ``arrival_reordered``, the commands executed this round
that came out before a command of their key, executed this round too, that
stood earlier in the working set.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Tuple

PAD = -1  # no key: the row holds no command
NO_CLOCK = -1
INT_MAX = 2**31 - 1


def quorum_sizes(n: int, f: int) -> Tuple[int, int, int]:
    """(fast quorum, write quorum, stability threshold), ``config.rs``."""
    return n // 2 + f, f + 1, n // 2 + 1


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
    watermark: int
    tallies: Dict[str, int]
    # row -> member -> what it proposed (the rows that proposed this round)
    proposals: Dict[int, Dict[int, int]]


class TempoSitesReference:
    def __init__(self, n: int, f: int, pending_capacity: int, site_base: int = 1,
                 live_replicas: Optional[int] = None):
        self.n, self.f, self.capacity, self.site_base = n, f, pending_capacity, site_base
        self.fast, self.write, self.threshold = quorum_sizes(n, f)
        self.live = [r < (n if live_replicas is None else live_replicas) for r in range(n)]
        self.clock: List[Dict[int, int]] = [{} for _ in range(n)]  # replica -> key -> clock
        self.votes: List[Dict[int, int]] = [{} for _ in range(n)]
        self.pending: List[Carried] = []

    def site(self, cmd: Carried) -> int:
        return (cmd.src - self.site_base) % self.n

    def stable_clock(self, key: int) -> int:
        votes = sorted(self.votes[r].get(key, 0) for r in range(self.n))
        return votes[self.n - self.threshold]

    def round(self, keys, srcs, seqs) -> RoundResult:
        n = self.n
        batch = [Carried(int(k), int(src), int(seq), NO_CLOCK)
                 for k, src, seq in zip(keys, srcs, seqs)]
        work: List[Optional[Carried]] = (
            self.pending + [None] * (self.capacity - len(self.pending)) + batch)
        work = [cmd if cmd is not None and cmd.key != PAD else None for cmd in work]
        rows = range(len(work))
        proposing = [w for w in rows if work[w] is not None and work[w].clock == NO_CLOCK]

        # --- proposals: every replica walks its view, one command at a time.
        # A site's own commands stand first in its view, so every c(x) is made
        # before any member needs it
        now: List[Dict[int, int]] = [dict() for _ in range(n)]  # the clocks as the walk moves them

        def bump(r: int, key: int, at_least: int) -> int:
            clock = max(at_least, now[r].get(key, self.clock[r].get(key, 0)) + 1)
            now[r][key] = clock
            return clock

        proposals: Dict[int, Dict[int, int]] = {w: {} for w in proposing}
        for r in range(n):
            for w in proposing:  # the view's first part: its own site's
                if self.site(work[w]) == r:
                    proposals[w][r] = bump(r, work[w].key, 0)
        for r in range(n):
            for w in proposing:  # ... then every other command
                s = self.site(work[w])
                if s != r and r in ring(s, n, self.fast):
                    proposals[w][r] = bump(r, work[w].key, proposals[w][s])

        # --- commit: the highest proposal, and how many reported it
        clock = [NO_CLOCK if cmd is None else cmd.clock for cmd in work]
        committed = [c != NO_CLOCK for c in clock]
        fast_path = [False] * len(work)
        slow_paths = spread = 0
        accepted = sum(self.live) >= self.write
        new = []  # committed this round
        for w in proposing:
            said = list(proposals[w].values())
            assert len(said) == self.fast
            highest = max(said)
            fast_path[w] = said.count(highest) >= self.f
            slow_paths += not fast_path[w]
            if fast_path[w] or accepted:
                committed[w], clock[w] = True, highest
                spread += highest - min(said)
                new.append(w)
        at = {}
        for w in new:
            at.setdefault((work[w].key, clock[w]), []).append(w)
        ties = sum(len(tied) for tied in at.values() if len(tied) > 1)

        # --- votes: every live replica chases every committed clock
        for w in rows:
            if work[w] is not None and committed[w]:
                for r in range(n):
                    if self.live[r]:
                        key = work[w].key
                        self.votes[r][key] = max(self.votes[r].get(key, 0), clock[w])
                        self.clock[r][key] = max(self.clock[r].get(key, 0), self.votes[r][key])

        # --- stability and the hold-back of a key behind a blocked command
        stable = [work[w] is not None and committed[w]
                  and clock[w] <= self.stable_clock(work[w].key) for w in rows]

        def clock_dot(w: int):
            cmd = work[w]
            if cmd is None:
                return (INT_MAX, 0, 0, w)
            return (clock[w] if committed[w] else INT_MAX, cmd.src, cmd.seq, w)

        rank = {w: place for place, w in enumerate(sorted(rows, key=clock_dot))}
        hold: Dict[int, int] = {}
        for w in rows:
            if work[w] is not None and committed[w] and not stable[w]:
                hold[work[w].key] = min(hold.get(work[w].key, len(work)), rank[w])
        executed = [stable[w] and rank[w] < hold.get(work[w].key, len(work)) for w in rows]
        order = sorted((w for w in rows if executed[w]), key=clock_dot)
        reordered, latest = 0, {}  # key -> the latest place in the order, so far
        for x in rows:  # in working order
            if executed[x]:
                reordered += latest.get(work[x].key, -1) > rank[x]
                latest[work[x].key] = max(latest.get(work[x].key, -1), rank[x])

        # --- what is carried: committed commands first, each class in order
        left = [w for w in rows if work[w] is not None and not executed[w]]
        left.sort(key=lambda w: (not committed[w], w))
        self.pending = [work[w]._replace(clock=clock[w]) for w in left[: self.capacity]]

        seen = {cmd.key for cmd in work if cmd is not None}
        return RoundResult(
            clock=clock, committed=committed, fast_path=fast_path, executed=executed,
            order=order, slow_paths=slow_paths, pending=min(len(left), self.capacity),
            dropped=max(len(left) - self.capacity, 0),
            watermark=min((self.stable_clock(k) for k in seen), default=INT_MAX),
            tallies={"site_clock_spread": spread, "clock_ties": ties,
                     "arrival_reordered": reordered},
            proposals=proposals)
