"""The plain reference of the dependency round with a coordinator at every
site (``--protocol epaxos``, one shard, one key a command, clients registered
at more than one site: ``parallel/mesh_step.py`` ``protocol_step(sites=n)``):
its rules one command at a time over ``dict``s, ``set``s and ``list``s.
Nothing here is the program's round: no import from ``fantoch_tpu.parallel``
or ``fantoch_tpu.ops``, no ``jax``, no ``numpy``, no batch tensor, no sort, no
scan.  (``tests/test_sites_reference.py`` holds this file's components to the
repo's host Tarjan, ``executor/graph/tarjan.py``, as a second opinion.)

The protocol is EPaxos (Moraru et al., SOSP'13) as upstream implements it
(``fantoch_ps/src/protocol/epaxos.rs``; the conflict index
``fantoch_ps/src/protocol/common/graph/deps/keys/locked.rs``; the executor
``fantoch_ps/src/executor/graph/tarjan.rs``), in the dense, round-based form
of ``protocol_step``:

* **Sites.**  ``n`` replicas, one a site.  A command is submitted at a site;
  that site's replica is its coordinator, and its dot is ``(coordinator's
  process, the coordinator's next sequence)``.
* **A replica's view of a round.**  What the last round carried comes first,
  in the order it was carried (every replica saw it in an earlier round).
  Then the round's new commands: replica ``r`` has the commands of its own
  site first, in arrival order, then every other command in arrival order.
  (All commands of one round are concurrent: a replica has its own clients'
  commands before any ``MCollect`` reaches it.)
* **The index** of a replica keeps, per key, the latest write and the latest
  read since it (``LatestRWDep``).  A read depends on the latest write and
  becomes the latest read; a write depends on the latest write and on the
  latest read since it, and becomes the latest write.  "Latest" and "since"
  are by the replica's own view.
* **A report.**  A replica adds the working set in the order of its view on
  top of what it has learnt; what it finds for a command is its own word.  Its
  report to the command's coordinator is that joined with the coordinator's
  own (``KeyDeps::add_cmd(dot, cmd, past)``: the ``MCollect`` carries the
  coordinator's dependencies and a member adds them to what it reports).
* **Quorums.**  ``f = n // 2``, fast ``f + (f + 1) // 2``, write ``f + 1``
  (``config.rs``).  The coordinator at site ``s`` collects from ``s, s + 1,
  ..., s + fast - 1 (mod n)``: a ring stands for "the closest".
* **Fast path** iff every member of the fast quorum reported the same set
  (``epaxos.rs:339-345``, ``check_union``).  Otherwise the union is proposed
  and accepted iff at least the write quorum of the replicas is live.  The
  committed dependencies are the union either way: up to ``fast`` commands a
  key and class.
* **Execution**: Tarjan over the committed commands of the working set
  (``tarjan.rs``): the strongly connected components in dependency order,
  each in dot order; a component runs once everything it depends on has, so
  an uncommitted command holds back whatever reaches it.
* **Learning.**  At the round's end every live replica learns what was
  executed: per key the latest write and the latest read, by arrival.
* **Carry.**  What did not execute is carried into the next round in working
  order (what was carried, then arrival order).

Departures from ``epaxos.rs``, noted and followed by the device round:

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
   second of each, and so on (``run/device_runner.py`` ``_sites_in_turn``):
   what one socket read brings is hundreds of commands of one site in a row,
   where a replica's network would deliver five coordinators' ``MCollect``s
   interleaved.  A site's own commands keep their order.
"""

from __future__ import annotations

from typing import Dict, FrozenSet, List, NamedTuple, Optional, Set, Tuple

Dot = Tuple[int, int]  # (source, sequence)


class Command(NamedTuple):
    """A read or a write of one key, submitted at ``site``."""

    src: int
    seq: int
    key: int
    read: bool
    site: int

    @property
    def dot(self) -> Dot:
        return (self.src, self.seq)


class Verdict(NamedTuple):
    """What a round made of one command of its working set."""

    reports: Dict[int, FrozenSet[Dot]]  # fast-quorum member -> what it reported
    fast: bool
    committed: bool
    deps: FrozenSet[Dot]  # the union of the reports
    executed: bool


class Round(NamedTuple):
    verdicts: Dict[Dot, Verdict]  # every command of the working set
    order: List[Dot]  # the executed ones, in execution order
    components: List[List[Dot]]  # the executed components, each in dot order
    slow_paths: int

    def tally(self) -> Dict[str, int]:
        """The device round's tallies over what this round executed."""
        multi = [c for c in self.components if len(c) > 1]
        return {
            "deps_committed": sum(len(self.verdicts[dot].deps) for dot in self.order),
            "scc_rows": sum(map(len, multi)),
            "scc_count": len(multi),
            "scc_rows_max": max(map(len, multi), default=0),
        }


def quorum_sizes(n: int) -> Tuple[int, int]:
    """(fast, write) of EPaxos in ``config.rs``."""
    minority = n // 2
    return minority + (minority + 1) // 2, minority + 1


def fast_quorum(site: int, n: int) -> List[int]:
    """The members the coordinator at ``site`` collects from."""
    return [(site + k) % n for k in range(quorum_sizes(n)[0])]


def view(replica: int, carried: List[Command], new: List[Command]) -> List[Command]:
    """The working set in the order ``replica`` has seen it."""
    own = [cmd for cmd in new if cmd.site == replica]
    others = [cmd for cmd in new if cmd.site != replica]
    return list(carried) + own + others


class _Latest:
    """One key's entry of an index: arrival numbers, None for none."""

    __slots__ = ("write", "read")

    def __init__(self, write: Optional[int] = None, read: Optional[int] = None):
        self.write, self.read = write, read


def _add(index: Dict[int, _Latest], learnt: Dict[int, _Latest], cmd: Command, arrival: int):
    """``KeyDeps::add_cmd`` on the command's key: the arrivals depended on."""
    entry = index.get(cmd.key)
    if entry is None:
        below = learnt.get(cmd.key)
        entry = index[cmd.key] = _Latest()
        if below is not None:
            entry.write = below.write
            # what it learnt by arrival: a read is "since" a write if later
            if below.read is not None and (below.write is None or below.read > below.write):
                entry.read = below.read
    if cmd.read:
        depends = {entry.write}
        entry.read = arrival
    else:
        depends = {entry.write, entry.read}
        entry.write, entry.read = arrival, None
    return frozenset(dep for dep in depends if dep is not None)


class Reference:
    def __init__(self, n: int):
        self.n = n
        self.fast_quorum, self.write_quorum = quorum_sizes(n)
        self.learnt: List[Dict[int, _Latest]] = [{} for _ in range(n)]
        self.arrival: Dict[Dot, int] = {}  # a command's place in arrival order
        self.dot_at: Dict[int, Dot] = {}
        self.carried: List[Command] = []
        self.executed: Set[Dot] = set()

    def round(self, commands: List[Command], live: Optional[int] = None) -> Round:
        n = self.n
        live = n if live is None else live
        for cmd in commands:
            assert cmd.dot not in self.arrival and 0 <= cmd.site < n
            self.arrival[cmd.dot] = len(self.arrival)
            self.dot_at[self.arrival[cmd.dot]] = cmd.dot
        working = self.carried + list(commands)

        # every replica adds the working set, in the order of its view
        own: List[Dict[Dot, FrozenSet[int]]] = []
        for replica in range(n):
            index: Dict[int, _Latest] = {}
            own.append({
                cmd.dot: _add(index, self.learnt[replica], cmd, self.arrival[cmd.dot])
                for cmd in view(replica, self.carried, list(commands))
            })

        verdicts: Dict[Dot, Verdict] = {}
        slow_paths = 0
        for cmd in working:
            reports = {
                member: own[member][cmd.dot] | own[cmd.site][cmd.dot]
                for member in fast_quorum(cmd.site, n)
            }
            said = list(reports.values())
            fast = all(one == said[0] for one in said)
            slow_paths += not fast
            verdicts[cmd.dot] = Verdict(
                reports={m: frozenset(self.dot_at[d] for d in r) for m, r in reports.items()},
                fast=fast, committed=fast or live >= self.write_quorum,
                deps=frozenset(self.dot_at[d] for d in frozenset().union(*said)),
                executed=False,
            )

        components = self._execute(working, verdicts)
        order = [dot for component in components for dot in component]
        for dot in order:
            verdicts[dot] = verdicts[dot]._replace(executed=True)
        self.executed.update(order)
        by_dot = {cmd.dot: cmd for cmd in working}
        for dot in sorted(order, key=self.arrival.__getitem__):
            cmd = by_dot[dot]
            for replica in range(min(live, n)):
                entry = self.learnt[replica].setdefault(cmd.key, _Latest())
                if cmd.read:
                    entry.read = self.arrival[dot]
                else:
                    entry.write = self.arrival[dot]
        self.carried = [cmd for cmd in working if cmd.dot not in self.executed]
        return Round(verdicts, order, components, slow_paths)

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
