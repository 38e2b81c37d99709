"""A plain reference of the Newt (Tempo) round under partial replication.

One round of ``fantoch_tpu.parallel.mesh_step.newt_protocol_step`` written
the slow way: Python loops over commands, replicas and keys, numpy only for
the two clock tables, no mesh, no jit, no sort network.  It follows the
mechanism as the paper and upstream state it (Enes et al., EuroSys'21;
``fantoch_ps/src/protocol/newt.rs`` ``mcollect_actions``,
``protocol/partial.rs`` ``mcommit_actions``) in the dense, round-based form
the device runs:

* ``shard_count`` shards of ``n`` replicas each; key bucket ``b`` belongs to
  shard ``b % shard_count``; replica rows are shard-major.
* **Proposal.**  Every member of a key's shard hands the key's uncommitted
  commands consecutive clocks above its own clock for that key, in the
  round's order of arrival (carried commands first).  A member's proposal
  for a command is one clock a shard: the highest it gave any of the
  command's keys there.
* **Fast quorum.**  A shard's clock for a command is the highest proposal
  among the shard's first ``fast_quorum`` members; the shard is fast when at
  least ``f`` of them proposed exactly that.  The command takes the fast
  path when every shard it touches is fast.
* **The command's clock is the highest of its shards' clocks** (MShardCommit).
* **Slow path.**  Otherwise it commits at the same clock once every shard
  it touches has ``f + 1`` live members (Synod at ballot 0).
* **Votes.**  Every live member of a key's shard votes the key up to the
  committed clock, and its own clock for the key never lags its votes.
* **Stability.**  A key is stable up to the ``stability_threshold``-th
  highest vote among its shard's members.  A command executes when its
  clock is stable on every key it touches and no command that comes before
  it in (clock, dot) order on one of its keys is committed and held back.
* **Pending.**  What did not execute is carried to the next round,
  committed commands first, up to the pending capacity; the rest is dropped
  (the driver requeues it).

Differences from upstream, shared with the device round and noted there: a
round is dense (every replica sees every command of the round), votes are a
watermark a key and not ranges, and two commands of one round may tie on a
clock, the dot breaking the tie.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

PAD = -1  # an unused key slot; a row of pads is no command
NO_CLOCK = -1
INT_MAX = 2**31 - 1


def quorum_sizes(n: int, f: int, tiny_quorums: bool = False) -> Tuple[int, int, int]:
    """(fast quorum, write quorum, stability threshold) of newt.rs:90-100."""
    if tiny_quorums:
        return 2 * f, f + 1, n - f
    return n // 2 + f, f + 1, n // 2 + 1


class Carried(NamedTuple):
    keys: Tuple[int, ...]
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


class NewtReference:
    def __init__(self, n: int, f: int, shard_count: int, key_buckets: int,
                 pending_capacity: int, key_width: int,
                 live_replicas: Optional[int] = None, tiny_quorums: bool = False):
        self.n, self.f, self.shard_count = n, f, shard_count
        self.capacity, self.key_width = pending_capacity, key_width
        self.fast_quorum, self.write_quorum, self.threshold = quorum_sizes(n, f, tiny_quorums)
        rows = n * shard_count
        self.live = [row < (rows if live_replicas is None else live_replicas)
                     for row in range(rows)]
        self.key_clock = np.zeros((rows, key_buckets), np.int64)
        self.votes = np.zeros((rows, key_buckets), np.int64)
        self.pending: List[Carried] = []

    def members(self, shard: int) -> range:
        return range(shard * self.n, (shard + 1) * self.n)

    def stable_clock(self, bucket: int) -> int:
        votes = sorted(self.votes[row, bucket] for row in self.members(bucket % self.shard_count))
        return int(votes[self.n - self.threshold])

    def round(self, keys, srcs, seqs) -> RoundResult:
        batch = [Carried(tuple(int(k) for k in row), int(src), int(seq), NO_CLOCK)
                 for row, src, seq in zip(np.reshape(keys, (len(srcs), -1)), srcs, seqs)]
        work: List[Optional[Carried]] = (
            self.pending + [None] * (self.capacity - len(self.pending)) + batch)
        work = [cmd if cmd is not None and any(k != PAD for k in cmd.keys) else None
                for cmd in work]
        rows = range(len(work))

        def buckets(cmd: Carried) -> List[int]:
            return [k for k in cmd.keys if k != PAD]

        # --- proposals: a member's next clock for a key, handed out in order
        next_clock: Dict[Tuple[int, int], int] = {}
        proposal: Dict[int, Dict[int, Dict[int, int]]] = {}  # row -> shard -> member -> clock
        for w in rows:
            cmd = work[w]
            if cmd is None or cmd.clock != NO_CLOCK:
                continue
            proposal[w] = {}
            for bucket in buckets(cmd):
                shard = bucket % self.shard_count
                of_shard = proposal[w].setdefault(shard, {})
                for member in self.members(shard):
                    at = (member, bucket)
                    clock = next_clock.get(at, int(self.key_clock[member, bucket]) + 1)
                    next_clock[at] = clock + 1
                    of_shard[member] = max(of_shard.get(member, clock), clock)

        # --- commit: fast quorum max a shard, the command at the max of its shards
        clock = [NO_CLOCK if cmd is None else cmd.clock for cmd in work]
        committed = [c != NO_CLOCK for c in clock]
        fast_path = [False] * len(work)
        slow_paths = 0
        for w, shards in proposal.items():
            shard_clock, fast = {}, True
            for shard, by_member in shards.items():
                quorum = [by_member[m] for m in self.members(shard)[: self.fast_quorum]]
                shard_clock[shard] = max(quorum)
                fast = fast and quorum.count(max(quorum)) >= self.f
            slow_ok = all(sum(self.live[m] for m in self.members(shard)) >= self.write_quorum
                          for shard in shards)
            fast_path[w] = fast
            slow_paths += not fast
            if fast or slow_ok:
                committed[w], clock[w] = True, max(shard_clock.values())

        # --- votes: the live members of a key's shard chase the committed clock
        for w in rows:
            if work[w] is None or not committed[w]:
                continue
            for bucket in buckets(work[w]):
                for member in self.members(bucket % self.shard_count):
                    if self.live[member]:
                        self.votes[member, bucket] = max(self.votes[member, bucket], clock[w])
        for member, live in enumerate(self.live):
            if live:
                np.maximum(self.key_clock[member], self.votes[member], out=self.key_clock[member])

        # --- stability and the hold-back of a key behind a blocked command
        stable = [work[w] is not None and committed[w]
                  and all(clock[w] <= self.stable_clock(b) for b in buckets(work[w]))
                  for w in rows]

        def clock_dot(w: int):
            cmd = work[w]
            if cmd is None:
                return (INT_MAX, 0, 0, w)
            return (clock[w] if committed[w] else INT_MAX, cmd.src, cmd.seq, w)

        rank = {w: at for at, w in enumerate(sorted(rows, key=clock_dot))}
        hold: Dict[int, int] = {}
        for w in rows:
            if work[w] is not None and committed[w] and not stable[w]:
                for bucket in buckets(work[w]):
                    hold[bucket] = min(hold.get(bucket, len(work)), rank[w])
        executed = [stable[w] and all(rank[w] < hold.get(b, len(work)) for b in buckets(work[w]))
                    for w in rows]
        order = sorted((w for w in rows if executed[w]), key=clock_dot)

        # --- what is carried: committed commands first, each class in order
        left = [w for w in rows if work[w] is not None and not executed[w]]
        left.sort(key=lambda w: (not committed[w], w))
        self.pending = [work[w]._replace(clock=clock[w]) for w in left[: self.capacity]]

        seen = {b for cmd in work if cmd is not None for b in buckets(cmd)}
        return RoundResult(
            clock=clock, committed=committed, fast_path=fast_path, executed=executed,
            order=order, slow_paths=slow_paths, pending=min(len(left), self.capacity),
            dropped=max(len(left) - self.capacity, 0),
            watermark=min((self.stable_clock(b) for b in seen), default=INT_MAX))
