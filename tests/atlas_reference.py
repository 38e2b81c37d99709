"""The plain reference of the dependency round as the served path runs it
(``--protocol atlas``, Janus* with ``--shard-count``; ``--protocol epaxos``):
its rules one command at a time over ``dict``s and ``set``s.  Nothing here is
the program's: no import from ``fantoch_tpu``, ``jax`` or ``numpy``, no batch
tensor, no sort, no scan.

The protocols are EPaxos (Moraru et al., SOSP'13) and Atlas (Enes et al.,
EuroSys'20) as upstream implements them (``fantoch_ps/src/protocol/epaxos.rs``,
``atlas.rs``, partial replication ``partial.rs``, quorums
``fantoch/src/config.rs``), with the conflict index of
``fantoch_ps/src/protocol/common/graph/deps/keys/locked.rs`` and the executor of
``fantoch_ps/src/executor/graph``, in the dense, round-based form of
``parallel/mesh_step.py`` ``protocol_step``:

* **Shards and replicas.**  ``shards`` shards of ``n`` replicas each; key
  ``k`` belongs to shard ``k % shards``.  Replicas are numbered shard by shard
  (member ``m`` of shard ``s`` is row ``s * n + m``) and the rows below
  ``live`` are live.
* **The index** of a replica keeps, per key of its shard, the latest write and
  the latest read it knows (``LatestRWDep``).  A read depends on the latest
  write and becomes the latest read; a write depends on the latest write and
  on the latest read *since it* (a read before that write is ordered by the
  write already: the repo's host index clears the read there,
  ``protocol/common/graph_deps.py``; upstream keeps it), and becomes the latest
  write.  "Later" is arrival order.
* **A round** is given its commands in arrival order, what the last round
  carried first, and every replica is sent all of them in that order: each
  adds them, one after the other, on top of what it has learnt, and reports
  each command's dependencies on the keys of its own shard.
* **Quorums**, per shard: EPaxos ``f = n // 2``, fast ``f + (f + 1) // 2``,
  write ``f + 1``; Atlas fast ``n // 2 + f``, write ``f + 1``.  The fast quorum
  is the shard's first members.
* **Fast path**, per shard: EPaxos iff every member of the fast quorum
  reported the same set; Atlas iff every dependency of the union was reported
  by at least ``f`` of them (always so at ``f`` = 1).  A command is fast iff
  every shard it touches is.
* **Slow path.**  Otherwise the union is proposed to the shard's replicas and
  accepted iff at least the write quorum of them is live.  The committed
  dependencies are the union over the command's shards either way.
* **Execution**: Tarjan over the committed commands of the working set, the
  strongly connected components in dependency order, each in dot order; a
  component runs once everything it depends on has, so an uncommitted command
  holds back whatever is downstream of it.
* **Learning.**  At the round's end every live replica learns what was
  executed, on the keys of its shard.
* **Carry.**  What did not execute is carried into the next round in arrival
  order, up to the pending capacity; what is beyond it is handed back for the
  caller to submit again.

Departures of the device round, noted and followed here:

1. **Bucket aliasing.**  The driver hashes a key to a bucket and the round
   orders buckets, so two keys of one bucket conflict.  The keys given to
   this file are the buckets.
2. **The indexes hold executed commands only.**  A replica learns a command
   when it executes (above), not when it is sent it, and the working set is
   added anew every round on top of that.  With every replica live a round
   executes all it is given, so nothing is added twice.
3. **A replica that is not live still reports, from what it knew when it
   stopped learning**, to the fast quorum (``mesh_step.py`` masks the quorum by
   replica row, not by liveness); it accepts nothing on the slow path.  In
   upstream a crashed replica reports nothing.  No flag and no cell of the
   benchmark reaches it (every served replica is live).
4. **A command that missed the fast path on one shard runs the accept round on
   every shard it touches.**  In ``partial.rs`` each shard commits on its own.
5. **Per key and class the device keeps the latest dependency of the union**
   (one slot for the write, one for the read): the earlier ones were executed
   before it, or it would not be in an index.  ``Verdict.deps`` is the union;
   ``Verdict.slots`` is what the device commits, and what its tallies count.
"""

from __future__ import annotations

from typing import Dict, FrozenSet, List, NamedTuple, Optional, Set, Tuple

Dot = Tuple[int, int]  # (source, sequence)


class Command(NamedTuple):
    """A read or a write of every key of ``keys`` (distinct)."""

    src: int
    seq: int
    keys: Tuple[int, ...]
    read: bool

    @property
    def dot(self) -> Dot:
        return (self.src, self.seq)


class Verdict(NamedTuple):
    """What a round made of one command of its working set."""

    committed: bool
    fast: bool
    executed: bool
    deps: FrozenSet[Dot]  # the union of what the fast quorums reported
    # per key (latest write, latest read since it) of that union: departure 5
    slots: Dict[int, Tuple[Optional[Dot], Optional[Dot]]]
    # per key, whether the quorum knew a command before this one on the key,
    # and whether that one and this one are both reads
    links: Dict[int, Tuple[bool, bool]]


class Round(NamedTuple):
    verdicts: Dict[Dot, Verdict]  # every command of the working set
    order: List[Dot]  # the executed ones, in execution order
    resubmit: List[Command]  # beyond the pending capacity: the caller's again
    slow_paths: int

    def tally(self, commands: Dict[Dot, Command], shards: int) -> Dict[str, int]:
        """The device round's tallies over what this round executed."""
        done = [(commands[dot], self.verdicts[dot]) for dot in self.order]
        return {
            "deps_committed": sum(
                sum(dep is not None for pair in v.slots.values() for dep in pair) for _, v in done
            ),
            "key_links": sum(linked for _, v in done for linked, _ in v.links.values()),
            "read_links_commuted": sum(both for _, v in done for _, both in v.links.values()),
            "read_rows": sum(c.read for c, _ in done),
            "cross_shard_executed": sum(len({k % shards for k in c.keys}) > 1 for c, _ in done),
        }


def quorum_sizes(rule: str, n: int, f: int) -> Tuple[int, int]:
    """(fast, write) of ``config.rs``."""
    if rule == "atlas":
        return n // 2 + f, f + 1
    assert rule == "epaxos", rule
    minority = n // 2
    return minority + (minority + 1) // 2, minority + 1


class _Latest:
    """One key's entry of an index: arrival numbers, None for none."""

    __slots__ = ("write", "read")

    def __init__(self, write: Optional[int] = None, read: Optional[int] = None):
        self.write, self.read = write, read


class _Round(dict):
    """A replica's index for the length of a round: what it has learnt,
    under what the round adds (a key is copied up when first touched)."""

    def __init__(self, learnt: Dict[int, _Latest]):
        super().__init__()
        self.learnt = learnt

    def __missing__(self, key: int) -> _Latest:
        below = self.learnt.get(key)
        entry = self[key] = _Latest() if below is None else _Latest(below.write, below.read)
        return entry


def _add(index: _Round, key: int, arrival: int, read: bool):
    """``KeyDeps::add_cmd`` on one key: (the write, the read) depended on."""
    entry = index[key]
    depends = (entry.write, None)
    if read:
        entry.read = arrival
    else:
        if entry.read is not None and (entry.write is None or entry.read > entry.write):
            depends = (entry.write, entry.read)
        entry.write = arrival
    return depends


class Reference:
    def __init__(self, rule: str, n: int, f: int = 1, shards: int = 1, pending: int = 1 << 30):
        self.rule, self.n, self.f, self.shards, self.pending = rule, n, f, shards, pending
        self.fast_quorum, self.write_quorum = quorum_sizes(rule, n, f)
        # index[shard][member][key]
        self.index: List[List[Dict[int, _Latest]]] = [
            [{} for _ in range(n)] for _ in range(shards)
        ]
        self.arrival: Dict[Dot, int] = {}  # a command's place in arrival order
        self.dot_at: Dict[int, Dot] = {}
        self.carried: List[Command] = []
        self.executed: Set[Dot] = set()

    def round(self, commands: List[Command], live: Optional[int] = None) -> Round:
        n, shards = self.n, self.shards
        live = n * shards if live is None else live
        for cmd in commands:
            assert cmd.dot not in self.arrival and len(set(cmd.keys)) == len(cmd.keys)
            self.arrival[cmd.dot] = len(self.arrival)
            self.dot_at[self.arrival[cmd.dot]] = cmd.dot
        working = self.carried + list(commands)

        # every replica adds the working set, in order, on top of what it learnt
        reports: Dict[Tuple[int, int], Dict[Dot, Dict[int, Tuple]]] = {}
        for shard in range(shards):
            for member in range(n):
                mine = _Round(self.index[shard][member])
                told = reports[shard, member] = {}
                for cmd in working:
                    told[cmd.dot] = {
                        # also what it knew of the key: for the links
                        key: ((mine[key].write, mine[key].read),
                              _add(mine, key, self.arrival[cmd.dot], cmd.read))
                        for key in cmd.keys if key % shards == shard
                    }

        verdicts: Dict[Dot, Verdict] = {}
        slow_paths = 0
        for cmd in working:
            touched = sorted({key % shards for key in cmd.keys})
            fast, accepted, union = True, True, set()
            slots: Dict[int, Tuple] = {}
            links: Dict[int, Tuple[bool, bool]] = {}
            for shard in touched:
                quorum = [reports[shard, member][cmd.dot] for member in range(self.fast_quorum)]
                said = [
                    frozenset(dep for pair in told.values() for dep in pair[1] if dep is not None)
                    for told in quorum
                ]
                shard_union = frozenset().union(*said)
                if self.rule == "epaxos":
                    fast &= all(one == said[0] for one in said)
                else:
                    fast &= all(sum(dep in one for one in said) >= self.f for dep in shard_union)
                union |= shard_union
                live_here = sum(shard * n + member < live for member in range(n))
                accepted &= live_here >= self.write_quorum
                for key in quorum[0]:
                    writes = [told[key][1][0] for told in quorum]
                    reads = [told[key][1][1] for told in quorum]
                    slots[key] = (_latest(writes), _latest(reads))
                    before_w = _latest([told[key][0][0] for told in quorum])
                    before_r = _latest([told[key][0][1] for told in quorum])
                    linked = before_w is not None or before_r is not None
                    links[key] = (linked, linked and cmd.read and before_r is not None
                                  and (before_w is None or before_r > before_w))
            slow_paths += not fast
            verdicts[cmd.dot] = Verdict(
                committed=fast or accepted, fast=fast, executed=False,
                deps=frozenset(self.dot_at[dep] for dep in union),
                slots={key: tuple(None if dep is None else self.dot_at[dep] for dep in pair)
                       for key, pair in slots.items()},
                links=links,
            )

        order = self._execute(working, verdicts)
        for dot in order:
            verdicts[dot] = verdicts[dot]._replace(executed=True)
        self.executed.update(order)
        by_dot = {cmd.dot: cmd for cmd in working}
        for dot in sorted(order, key=self.arrival.__getitem__):
            cmd = by_dot[dot]
            for key in cmd.keys:
                shard = key % shards
                for member in range(n):
                    if shard * n + member < live:
                        entry = self.index[shard][member].setdefault(key, _Latest())
                        if cmd.read:
                            entry.read = self.arrival[dot]
                        else:
                            entry.write = self.arrival[dot]
        left = [cmd for cmd in working if cmd.dot not in self.executed]
        self.carried, resubmit = left[: self.pending], left[self.pending:]
        for cmd in resubmit:  # it comes back as a new arrival
            del self.dot_at[self.arrival.pop(cmd.dot)]
        return Round(verdicts, order, resubmit, slow_paths)

    def _execute(self, working: List[Command], verdicts: Dict[Dot, Verdict]) -> List[Dot]:
        """Tarjan over the committed commands of the working set; a component
        runs iff all it depends on, outside itself, has run."""
        graph = {
            cmd.dot: sorted(dep for dep in verdicts[cmd.dot].deps if dep not in self.executed)
            for cmd in working if verdicts[cmd.dot].committed
        }
        index: Dict[Dot, int] = {}
        low: Dict[Dot, int] = {}
        stack: List[Dot] = []
        on_stack: Set[Dot] = set()
        ran: Set[Dot] = set()
        order: List[Dot] = []

        def visit(root: Dot) -> None:
            calls = [(root, iter(graph[root]))]
            index[root] = low[root] = len(index)
            stack.append(root)
            on_stack.add(root)
            while calls:
                node, deps = calls[-1]
                for dep in deps:
                    if dep not in graph:
                        continue  # uncommitted: whoever depends on it waits
                    if dep not in index:
                        index[dep] = low[dep] = len(index)
                        stack.append(dep)
                        on_stack.add(dep)
                        calls.append((dep, iter(graph[dep])))
                        break
                    if dep in on_stack:
                        low[node] = min(low[node], index[dep])
                else:
                    calls.pop()
                    if calls:
                        low[calls[-1][0]] = min(low[calls[-1][0]], low[node])
                    if low[node] == index[node]:
                        component = []
                        while True:
                            member = stack.pop()
                            on_stack.discard(member)
                            component.append(member)
                            if member == node:
                                break
                        inside = set(component)
                        if all(dep in ran or dep in inside
                               for member in component for dep in graph[member]):
                            ran.update(component)
                            order.extend(sorted(component))

        for cmd in working:
            if cmd.dot in graph and cmd.dot not in index:
                visit(cmd.dot)
        return order


def _latest(arrivals) -> Optional[int]:
    return max((a for a in arrivals if a is not None), default=None)
