"""The dense rules of ``tests/caesar_sites_reference.py`` tied to the port of
upstream's handlers: ``n`` instances of ``fantoch_tpu/protocol/caesar.py`` on
one hot key of a fresh store (where the port's replica-wide sequence and the
reference's per-key ceiling coincide), their messages delivered by hand in the
schedule the views describe:

* every coordinator submits its own site's commands in working order and
  handles its own ``MPropose`` of each at once; then every replica handles the
  other sites' ``MPropose``s in working order;
* then the commands from the highest ``(T0, dot)`` down: the acks of the
  command's ring reach its coordinator (highest reported clock first, so the
  early slow path of ``QuorumClocks`` has heard the highest counter-proposal;
  what replicas outside the ring answer is dropped), and what the coordinator
  then sends (``MCommit``, or ``MRetry``, the first ``write`` ``MRetryAck``s and
  the ``MCommit`` they release) reaches everyone before the next command's acks
  are looked at.

Held equal: whether each member of each ring said ok, the fast / slow verdict
of every command, the committed clock of every fast command, and that every
retried command is committed above every ``T0`` of the round, by every replica
alike.  The clocks of the retried commands among themselves are the port's own
(reference departure 5: it rejects as soon as a blocker is decided, in the hash
order of a ``set``, replicas outside the ring spend timestamps too, and every
``MRetry`` is joined as it arrives; departure 6: the rejecter's process breaks
their ties)."""

import numpy as np
import pytest

from fantoch_tpu.core.command import Command
from fantoch_tpu.core.config import Config
from fantoch_tpu.core.ids import Dot, Rifl
from fantoch_tpu.core.kvs import KVOp
from fantoch_tpu.core.timing import SimTime
from fantoch_tpu.protocol import Caesar
from fantoch_tpu.protocol.caesar import MCommit, MPropose, MProposeAck, MRetry, MRetryAck
from tests.caesar_sites_reference import CaesarSitesReference, quorum_sizes, ring

SHARD, SITE_BASE = 0, 1


class Cluster:
    """``n`` ports and what they have said and not yet been told."""

    def __init__(self, n):
        self.n, self.time = n, SimTime()
        config = Config(n=n, f=n // 2, caesar_wait_condition=True, gc_interval_ms=100)
        self.ports = {pid: Caesar(pid, SHARD, config) for pid in range(1, n + 1)}
        everyone = [(pid, SHARD) for pid in self.ports]
        for port in self.ports.values():
            assert port.discover(everyone)[0]
        self.said = []  # (from, to, message), in the order said

    def collect(self, pid):
        for action in self.ports[pid].to_processes_iter():
            self.said.extend((pid, to, action.msg) for to in sorted(action.target))

    def tell(self, from_, to, msg):
        self.ports[to].handle(from_, SHARD, msg, self.time)
        self.collect(to)

    def take(self, kind, dot, to=None):
        """What was said of ``dot`` of one kind (to ``to``), taken out."""
        taken = [entry for entry in self.said if isinstance(entry[2], kind)
                 and entry[2].dot == dot and to in (None, entry[1])]
        self.said = [entry for entry in self.said if entry not in taken]
        return taken


def run_port(n, sites):
    """One round of commands at ``sites`` (in working order) through the ports:
    ``(proposed, said_ok, fast, committed)`` by dot."""
    fast_quorum, write_quorum = quorum_sizes(n)
    cluster = Cluster(n)
    dots, next_seq = [], {}
    for site in sites:
        source = SITE_BASE + site
        next_seq[source] = next_seq.get(source, 0) + 1
        dots.append(Dot(source, next_seq[source]))
    proposals = {}
    for dot in dots:  # its own site's commands first: each coordinator's, in working order
        cmd = Command.from_single(Rifl(dot.source, dot.sequence), SHARD, "hot", KVOp.put("v"))
        cluster.ports[dot.source].submit(dot, cmd, cluster.time)
        cluster.collect(dot.source)
        proposals[dot] = {to: msg for _, to, msg in cluster.take(MPropose, dot)}
        cluster.tell(dot.source, dot.source, proposals[dot][dot.source])
    for pid in cluster.ports:  # ... then every other command, in working order
        for dot in dots:
            if dot.source != pid:
                cluster.tell(dot.source, pid, proposals[dot][pid])
    proposed = {dot: proposals[dot][dot.source].clock for dot in dots}
    assert all(clock.process_id == dot.source for dot, clock in proposed.items())

    said_ok, fast, committed = {}, {}, {}
    for dot in sorted(dots, key=lambda dot: (proposed[dot].seq, dot), reverse=True):
        members = {SITE_BASE + site for site in ring(dot.source - SITE_BASE, n, fast_quorum)}
        acks = [(from_, msg) for from_, _, msg in cluster.take(MProposeAck, dot)
                if from_ in members]
        assert {from_ for from_, _ in acks} == members, "every blocker above is decided"
        said_ok[dot] = {from_ - SITE_BASE: msg.ok for from_, msg in acks}
        for from_, msg in sorted(acks, key=lambda ack: ack[1].clock, reverse=True):
            cluster.tell(from_, dot.source, msg)
        retries = cluster.take(MRetry, dot)
        fast[dot] = not retries
        for from_, to, msg in retries:
            cluster.tell(from_, to, msg)
        for from_, to, msg in cluster.take(MRetryAck, dot)[:write_quorum]:
            cluster.tell(from_, to, msg)
        commits = cluster.take(MCommit, dot)
        assert len(commits) == n
        for from_, to, msg in commits:
            cluster.tell(from_, to, msg)
        committed[dot] = commits[0][2].clock
    # every replica hands its executor the same (clock, dot) a command
    for port in cluster.ports.values():
        infos = []
        while (info := port.to_executors()) is not None:
            infos.append(info)
        assert {info.dot: info.clock for info in infos} == committed
    return proposed, said_ok, fast, committed


@pytest.mark.parametrize("n, seed", [(7, 1), (7, 2), (7, 3), (5, 1), (5, 2), (3, 1)])
def test_the_ports_verdicts_on_a_hot_key_are_the_references(n, seed):
    rng = np.random.default_rng([59, n, seed])
    sites = [int(site) for site in rng.integers(n, size=40)]
    proposed, said_ok, fast, committed = run_port(n, sites)

    rows = list(proposed)  # the dots, in working order
    got = CaesarSitesReference(n, 0, SITE_BASE).round(
        [0] * len(rows), [dot.source for dot in rows], [dot.sequence for dot in rows])
    top = max(got.proposed.values())
    for w, dot in enumerate(rows):
        assert proposed[dot].seq == got.proposed[w]
        assert said_ok[dot] == {r: said != "rejected" for r, said in got.answers[w].items()}
        assert fast[dot] == got.fast_path[w]
        if fast[dot]:  # ... and its clock is its proposal, the coordinator's process with it
            assert committed[dot] == proposed[dot] and got.clock[w] == proposed[dot].seq
        else:
            assert committed[dot].seq > top and got.clock[w] > top
    # the committed order: the fast commands by (T0, dot), every retried one after them
    fast_rows = [w for w in got.order if got.fast_path[w]]
    port_order = sorted(rows, key=lambda dot: committed[dot])
    assert [rows[w] for w in fast_rows] == port_order[: len(fast_rows)]
    assert got.order[: len(fast_rows)] == fast_rows
    if n > 3:
        assert 0 < len(fast_rows) < len(rows)  # both verdicts, both ways
        assert any(not all(oks.values()) for oks in said_ok.values())
    else:  # a ring that is everyone: nothing is retried
        assert len(fast_rows) == len(rows)
