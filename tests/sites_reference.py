"""The plain reference of the dependency round with a coordinator at every
site (``--protocol epaxos`` or ``--protocol atlas`` at any ``-f``, any
``--shard-count`` and ``--device-key-width``, clients registered at more than
one site:
``parallel/mesh_step.py`` ``protocol_step(sites=n)``): its rules one command
at a time over ``dict``s, ``set``s and ``list``s.
Nothing here is the program's round: no import from ``fantoch_tpu.parallel``
or ``fantoch_tpu.ops``, no ``jax``, no ``numpy``, no batch tensor, no sort, no
scan.  (``tests/test_sites_reference.py`` holds this file's components to the
repo's host Tarjan, ``executor/graph/tarjan.py``, as a second opinion.)

The protocols are EPaxos (Moraru et al., SOSP'13) and Atlas (Enes et al.,
EuroSys'20; with shards, the Tempo paper's Janus*) as upstream implements them
(``fantoch_ps/src/protocol/epaxos.rs``, ``atlas.rs``, partial replication
``partial.rs``; the conflict index
``fantoch_ps/src/protocol/common/graph/deps/keys/locked.rs``; the executor
``fantoch_ps/src/executor/graph/tarjan.rs``), in the dense, round-based form
of ``protocol_step``:

* **Sites, shards and replicas.**  ``shards`` shards of ``n`` replicas each,
  one replica of every shard a site; key ``k`` belongs to shard ``k % shards``
  and the replica of shard ``k`` at site ``s`` is row ``k * n + s``.  A command
  (a read or a write of one or several distinct keys) is submitted at a site;
  that site's replica coordinates it in every shard it touches
  (``partial.rs:8``: the coordinator forwards the submit to its own site's
  process of every other shard), and its dot is ``(coordinator's process, the
  coordinator's next sequence)``.
* **A replica's view of a round.**  What the last round carried comes first,
  in the order it was carried (every replica saw it in an earlier round).
  Then the round's new commands: replica ``r`` has the commands of its own
  site first, in arrival order, then every other command in arrival order.
  (All commands of one round are concurrent: a replica has its own clients'
  commands before any ``MCollect`` reaches it.)
* **The index** of a replica keeps, per key of its shard, the latest write and
  the latest read since it (``LatestRWDep``).  A read depends on the latest write and
  becomes the latest read; a write depends on the latest write and on the
  latest read since it, and becomes the latest write.  "Latest" and "since"
  are by the replica's own view.
* **A report.**  A replica adds the working set in the order of its view on
  top of what it has learnt, each command on its keys of the replica's shard;
  what it finds for a command, over those keys, is its own word.  Its report to
  the command's coordinator in its shard is that joined with the coordinator's
  own (``KeyDeps::add_cmd(dot, cmd, past)``: the ``MCollect`` carries the
  coordinator's dependencies and a member adds them to what it reports).
* **Quorums**, per shard.  EPaxos: ``f = n // 2``, fast ``f + (f + 1) // 2``,
  write ``f + 1``; Atlas: fast ``n // 2 + f``, write ``f + 1`` (``config.rs``).
  The coordinator at site ``s`` collects, in shard ``k``, from the rows ``k * n
  + (s + j) % n``, ``j < fast``: a ring stands for "the closest".
* **Fast path**, per shard: EPaxos iff every member of the fast quorum
  reported the same set (``epaxos.rs:339-345``, ``check_union``); Atlas iff
  every dependency of the union was reported by at least ``f`` members
  (``atlas.rs``, ``check_threshold``; at ``f`` = 1 always).  A command is fast
  iff every shard it touches is.  Otherwise the union is proposed and accepted
  iff every touched shard has at least its write quorum live.  The committed
  dependencies are the union over the command's shards either way
  (``partial.rs``'s aggregation): up to ``fast`` commands a key and class.
* **Execution**: Tarjan over the committed commands of the working set
  (``tarjan.rs``): the strongly connected components in dependency order,
  each in dot order; a component runs once everything it depends on has, so
  an uncommitted command holds back whatever reaches it.
* **Learning.**  At the round's end every live replica learns what was
  executed on the keys of its shard: per key the latest write and the latest
  read, by arrival.
* **Carry.**  What did not execute is carried into the next round in working
  order (what was carried, then arrival order).

Departures from ``epaxos.rs``, ``atlas.rs`` and ``partial.rs``, noted and
followed by the device round:

1. **Bucket aliasing.**  The driver hashes a key to a bucket and the round
   orders buckets, so two keys of one bucket conflict.  The keys given to
   this file are the buckets.
2. **The indexes hold executed commands only.**  A replica learns a command
   when it executes, not when it is sent it, and the working set is added
   anew every round on top of that (so a carried command is proposed again,
   by its coordinator, with what the replicas know then).
3. **Every replica has every command of the round in its view**, not only the
   members of the command's fast quorum: upstream sends ``MCollect`` to all
   and a replica outside the quorum keeps the payload without adding it to
   its index until the commit.  Only members report, so this changes what a
   member has seen *before* a command, not who speaks for it.
4. **A replica that is not live still reports, from what it knew when it
   stopped learning** (the round masks the quorum by replica row, not by
   liveness); it accepts nothing on the slow path and learns nothing.  No
   flag and no cell of the benchmark reaches it.
5. **No delay is injected**: the view above is the only source of
   disagreement, and a round's commands are all concurrent however far apart
   they arrived.
6. **The accept round is the same round**: a command that missed the fast
   path commits in the round that proposed it (ballot 0, skip-prepare), with
   no competing proposal for its dot.
7. **Arrival order inside a round is the sites' commands in turn.**  This
   file takes a round's commands in the order it is given them.  The driver
   gives the round its batch with the first command of each site, then the
   second of each, and so on (``run/device_drivers.py`` ``_sites_in_turn``):
   what one socket read brings is hundreds of commands of one site in a row,
   where a replica's network would deliver five coordinators' ``MCollect``s
   interleaved.  A site's own commands keep their order.  The sites take
   their turns in the order they first appear in the batch, and under
   Atlas's threshold (departure 9) that order shows: a command's predecessor
   in turn is reported by every member but the one at the predecessor's own
   site, so fewer commands fall short where that site is the one replica
   outside the coordinator's ring.
8. **A command that missed the fast path in one shard runs the accept round
   in every shard it touches**, and commits when all of them accepted, in the
   round that proposed it.  In ``partial.rs`` each shard commits on its own
   and the coordinator aggregates the ``MShardCommit``s.
9. **Atlas's threshold is taken over the reports**, each joined with the
   coordinator's own, as ``atlas.rs`` joins them: a dependency the coordinator
   found is reported by every member, so only what a member that is not the
   coordinator found alone, or with fewer than ``f`` of the ring, sends a
   command to the accept round.  At ``f`` = 1 the fast path is unconditional
   either way, and the device round compares nothing there; at ``f`` >= 2 it
   serves this rule (``mesh_step._atlas_threshold``).
10. **Every replica of a shard has every command of the round that touches
   its shard in its view** (departure 3, per shard), and a shard's replicas
   see nothing of a command's keys on other shards.
"""

from __future__ import annotations

from typing import Dict, FrozenSet, List, NamedTuple, Optional, Set, Tuple

Dot = Tuple[int, int]  # (source, sequence)


class Command(NamedTuple):
    """A read or a write, submitted at ``site``, of one key (``key`` an
    ``int``) or of several distinct ones (a tuple of them)."""

    src: int
    seq: int
    key: int | Tuple[int, ...]
    read: bool
    site: int

    @property
    def dot(self) -> Dot:
        return (self.src, self.seq)

    @property
    def keys(self) -> Tuple[int, ...]:
        return self.key if isinstance(self.key, tuple) else (self.key,)


class Verdict(NamedTuple):
    """What a round made of one command of its working set."""

    # fast-quorum member (its replica row, ``shard * n + site``) -> what it reported
    reports: Dict[int, FrozenSet[Dot]]
    fast: bool
    committed: bool
    deps: FrozenSet[Dot]  # the union of the reports
    executed: bool
    # key -> the members' own words on that key, joined
    by_key: Dict[int, FrozenSet[Dot]] = {}
    # where Atlas's threshold is taken (``f`` >= 2): the dependencies of the
    # union that fewer than ``f`` members of some shard's ring reported, and
    # whether some shard's reports were not one set (EPaxos's test)
    short: FrozenSet[Dot] = frozenset()
    split: bool = False


class Round(NamedTuple):
    verdicts: Dict[Dot, Verdict]  # every command of the working set
    order: List[Dot]  # the executed ones, in execution order
    components: List[List[Dot]]  # the executed components, each in dot order
    slow_paths: int
    commands: Dict[Dot, Command] = {}  # the working set
    shards: int = 1

    def tally(self) -> Dict[str, int]:
        """The device round's tallies over what this round executed.  Of the
        components of several commands: ``scc_span_rows`` counts the commands
        of those whose members hold more than one key, ``scc_shard_rows`` of
        those whose members' keys lie on more than one shard.  The last three
        are Atlas's threshold's, 0 where none is taken (EPaxos's rule, ``f`` =
        1): the dependencies that fell short of ``f``, the commands whose
        quorum was split, and those of them that were fast all the same."""
        ran = [self.verdicts[dot] for dot in self.order]
        multi = [c for c in self.components if len(c) > 1]
        span = shard = 0
        for component in multi:
            on_keys = {key for dot in component for key in self.commands[dot].keys}
            span += len(component) * (len(on_keys) > 1)
            shard += len(component) * (len({key % self.shards for key in on_keys}) > 1)
        return {
            "deps_committed": sum(len(self.verdicts[dot].deps) for dot in self.order),
            "cross_shard_executed": sum(
                len({key % self.shards for key in self.commands[dot].keys}) > 1
                for dot in self.order
            ),
            "scc_rows": sum(map(len, multi)),
            "scc_count": len(multi),
            "scc_rows_max": max(map(len, multi), default=0),
            "scc_span_rows": span,
            "scc_shard_rows": shard,
            "threshold_short_deps": sum(len(verdict.short) for verdict in ran),
            "split_quorum_rows": sum(verdict.split for verdict in ran),
            "threshold_fast_split_rows": sum(verdict.split and verdict.fast for verdict in ran),
        }


def quorum_sizes(n: int, rule: str = "epaxos", f: int = 1) -> Tuple[int, int]:
    """(fast, write) of ``config.rs``: EPaxos's whatever ``f``, Atlas's."""
    if rule == "atlas":
        return n // 2 + f, f + 1
    assert rule == "epaxos", rule
    minority = n // 2
    return minority + (minority + 1) // 2, minority + 1


def fast_quorum(site: int, n: int, rule: str = "epaxos", f: int = 1) -> List[int]:
    """The members of a shard the coordinator at ``site`` collects from."""
    return [(site + k) % n for k in range(quorum_sizes(n, rule, f)[0])]


def view(replica: int, carried: List[Command], new: List[Command]) -> List[Command]:
    """The working set in the order the replica at site ``replica`` (of
    whichever shard) has seen it."""
    own = [cmd for cmd in new if cmd.site == replica]
    others = [cmd for cmd in new if cmd.site != replica]
    return list(carried) + own + others


class _Latest:
    """One key's entry of an index: arrival numbers, None for none."""

    __slots__ = ("write", "read")

    def __init__(self, write: Optional[int] = None, read: Optional[int] = None):
        self.write, self.read = write, read


def _add(index: Dict[int, _Latest], learnt: Dict[int, _Latest], key: int, read: bool, arrival: int):
    """``KeyDeps::add_cmd`` on one key: the arrivals depended on."""
    entry = index.get(key)
    if entry is None:
        below = learnt.get(key)
        entry = index[key] = _Latest()
        if below is not None:
            entry.write = below.write
            # what it learnt by arrival: a read is "since" a write if later
            if below.read is not None and (below.write is None or below.read > below.write):
                entry.read = below.read
    if read:
        depends = {entry.write}
        entry.read = arrival
    else:
        depends = {entry.write, entry.read}
        entry.write, entry.read = arrival, None
    return frozenset(dep for dep in depends if dep is not None)


class Reference:
    def __init__(self, n: int, shards: int = 1, rule: str = "epaxos", f: int = 1):
        self.n, self.shards, self.rule, self.f = n, shards, rule, f
        self.fast_quorum, self.write_quorum = quorum_sizes(n, rule, f)
        # learnt[row], row = shard * n + site
        self.learnt: List[Dict[int, _Latest]] = [{} for _ in range(n * shards)]
        self.arrival: Dict[Dot, int] = {}  # a command's place in arrival order
        self.dot_at: Dict[int, Dot] = {}
        self.carried: List[Command] = []
        self.executed: Set[Dot] = set()

    def round(self, commands: List[Command], live: Optional[int] = None) -> Round:
        """One round; the replica rows below ``live`` are live."""
        n, shards = self.n, self.shards
        live = n * shards if live is None else live
        for cmd in commands:
            assert cmd.dot not in self.arrival and 0 <= cmd.site < n
            assert len(set(cmd.keys)) == len(cmd.keys)
            self.arrival[cmd.dot] = len(self.arrival)
            self.dot_at[self.arrival[cmd.dot]] = cmd.dot
        working = self.carried + list(commands)

        # every replica adds the working set, in the order of its view, each
        # command on its keys of the replica's shard: own[row][dot][key]
        own: List[Dict[Dot, Dict[int, FrozenSet[int]]]] = []
        for row in range(n * shards):
            shard, index = row // n, {}
            own.append({
                cmd.dot: {
                    key: _add(index, self.learnt[row], key, cmd.read, self.arrival[cmd.dot])
                    for key in cmd.keys if key % shards == shard
                }
                for cmd in view(row % n, self.carried, list(commands))
            })

        def word(row: int, dot: Dot) -> FrozenSet[int]:
            return frozenset().union(*own[row][dot].values())

        def dots(arrivals) -> FrozenSet[Dot]:
            return frozenset(self.dot_at[at] for at in arrivals)

        verdicts: Dict[Dot, Verdict] = {}
        slow_paths = 0
        for cmd in working:
            fast = accepted = True
            split, short = False, set()
            reports: Dict[int, FrozenSet[int]] = {}
            by_key: Dict[int, Set[int]] = {key: set() for key in cmd.keys}
            for shard in sorted({key % shards for key in cmd.keys}):
                rows = [shard * n + m for m in fast_quorum(cmd.site, n, self.rule, self.f)]
                said = [word(row, cmd.dot) | word(rows[0], cmd.dot) for row in rows]
                if self.rule == "epaxos":
                    fast &= all(one == said[0] for one in said)
                else:
                    fell = {
                        dep for dep in frozenset().union(*said)
                        if sum(dep in one for one in said) < self.f
                    }  # at f = 1 none can
                    fast &= not fell
                    if self.f > 1:  # the device round compares, and counts, from there on
                        short |= fell
                        split |= any(one != said[0] for one in said)
                accepted &= sum(shard * n + m < live for m in range(n)) >= self.write_quorum
                reports.update(zip(rows, said))
                for row in rows:
                    for key, found in own[row][cmd.dot].items():
                        by_key[key] |= found
            slow_paths += not fast
            verdicts[cmd.dot] = Verdict(
                reports={row: dots(said) for row, said in reports.items()},
                fast=fast, committed=fast or accepted,
                deps=dots(frozenset().union(*reports.values())),
                executed=False,
                by_key={key: dots(found) for key, found in by_key.items()},
                short=dots(short), split=split,
            )

        components = self._execute(working, verdicts)
        order = [dot for component in components for dot in component]
        for dot in order:
            verdicts[dot] = verdicts[dot]._replace(executed=True)
        self.executed.update(order)
        by_dot = {cmd.dot: cmd for cmd in working}
        for dot in sorted(order, key=self.arrival.__getitem__):
            cmd = by_dot[dot]
            for key in cmd.keys:
                for row in range(key % shards * n, (key % shards + 1) * n):
                    if row < live:
                        # (reads commute: one carried behind a write of
                        # another of its keys may execute a round after a
                        # read that arrived later; the later arrival stays)
                        entry = self.learnt[row].setdefault(key, _Latest())
                        if cmd.read:
                            entry.read = max(entry.read or 0, self.arrival[dot])
                        else:
                            entry.write = max(entry.write or 0, self.arrival[dot])
        self.carried = [cmd for cmd in working if cmd.dot not in self.executed]
        return Round(verdicts, order, components, slow_paths, by_dot, shards)

    def _execute(self, working: List[Command], verdicts: Dict[Dot, Verdict]) -> List[List[Dot]]:
        """Tarjan over the committed commands of the working set; a component
        runs iff all it depends on, outside itself, has run."""
        graph = {
            cmd.dot: sorted(dep for dep in verdicts[cmd.dot].deps if dep not in self.executed)
            for cmd in working if verdicts[cmd.dot].committed
        }
        ran: Set[Dot] = set()
        out: List[List[Dot]] = []
        for component in components_of(graph):
            inside = set(component)
            if all(dep in ran or dep in inside for member in component for dep in graph[member]):
                ran.update(component)
                out.append(sorted(component))
        return out


def components_of(graph: Dict[Dot, List[Dot]]) -> List[List[Dot]]:
    """The strongly connected components of ``graph`` (vertex -> the vertices
    it depends on; a dependency that is no vertex is skipped: it is not
    committed), each after every component it depends on (Tarjan, without
    recursion)."""
    index: Dict[Dot, int] = {}
    low: Dict[Dot, int] = {}
    stack: List[Dot] = []
    on_stack: Set[Dot] = set()
    found: List[List[Dot]] = []

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
                    found.append(component)

    for vertex in graph:
        if vertex not in index:
            visit(vertex)
    return found
