"""The leader round and its driver against the plain reference
(`tests/paxos_reference.py`), on the CPU, small and seeded; every comparison
is exact (integers, booleans and strings, no tolerance).

1. `paxos_protocol_step` element for element: each working row's `slot`,
   `committed` and `executed`, the executed prefix of `order`, `pending`,
   `pend_dropped`, `exec_frontier` and the next state, over seeded sequences
   of rounds at n in {3, 5} and f in {1, 2}, with fills from nothing to the
   whole batch on any rows of it, `live` drawn anew each round from
   {n, f + 1, f, 0} (one jitted program a value) and a pending capacity
   under the batch, so that degraded rounds overflow and roll slots back.
2. `PaxosDeviceDriver` through `step`, `serve` under overlap a round and a
   chain a call, and `flush_pipeline`: the reference's results in
   the reference's order, each rifl once, through a degraded stretch, a
   re-queue, a slot-epoch rebase with carried slots, and the recovery.
3. The object protocol upstream's shape lives in (`protocol/fpaxos.py` over
   MultiSynod, in the simulator at n=5, f=1): one client at the leader sends
   the same sequence and gets the reference's results in its slot order."""

import dataclasses
import random

import numpy as np
import pytest

from fantoch_tpu.client import ConflictRateKeyGen, Workload
from fantoch_tpu.core import Config, Planet
from fantoch_tpu.core.command import Command
from fantoch_tpu.core.ids import Dot, Rifl
from fantoch_tpu.core.kvs import KVOp
from fantoch_tpu.parallel import mesh_step
from fantoch_tpu.protocol import FPaxos
from fantoch_tpu.run.device_runner import PaxosDeviceDriver
from fantoch_tpu.sim import Runner
from tests import paxos_reference as ref

BATCH, PENDING, KEYS, SEED = 8, 6, 5, 38


def commands(count, rng, src=1):
    """``count`` commands of one source: four writes in five, else a read,
    on a few keys, so that what a command returns depends on the order."""
    out = []
    for number in range(1, count + 1):
        value = f"{src}:{number}" if rng.random() < 0.8 else None
        out.append(ref.Command(src, number, f"k{rng.randrange(KEYS)}", value))
    return out


def to_program(cmd):
    op = KVOp.get() if cmd.value is None else KVOp.put(cmd.value)
    return Dot(cmd.src, cmd.seq), Command.from_single(Rifl(cmd.src, cmd.seq), 0, cmd.key, op)


# --- 1. the round ---------------------------------------------------------------------


@pytest.mark.parametrize("n,f", [(3, 1), (3, 2), (5, 1), (5, 2)])
def test_the_round_is_the_reference_element_for_element(n, f):
    """(n=3, f=2 is more than a deployment of three tolerates: the round takes
    its quorum f + 1 = 3 from the flag, and the arithmetic is held all the same.)"""
    rng = random.Random(SEED + 10 * n + f)
    mesh = mesh_step.make_mesh(num_replicas=n)
    programs = {live: mesh_step.jit_paxos_step(mesh, f=f, num_replicas=n, live_replicas=live)
                for live in {n, f + 1, f, 0}}
    state = mesh_step.init_paxos_state(mesh, pending_capacity=PENDING)
    reference = ref.Reference(n, f, PENDING)
    fresh = iter(commands(10_000, rng))
    waiting, sent = [], 0  # dropped commands, to be sent again first
    seen = {"degraded": 0, "overflowed": 0, "carried_executed": 0, "chosen_rounds": 0}

    def one_round(live, fill):
        nonlocal state, sent
        rows = sorted(rng.sample(range(BATCH), fill))  # any rows of the batch, not a prefix
        batch = [None] * BATCH
        for row in rows:
            batch[row] = waiting.pop(0) if waiting else next(fresh)
        sent += sum(cmd is not None and cmd.seq > sent for cmd in batch)
        valid = np.array([cmd is not None for cmd in batch])
        src = np.array([cmd.src if cmd else 0 for cmd in batch], dtype=np.int32)
        seq = np.array([cmd.seq if cmd else 0 for cmd in batch], dtype=np.int32)
        state, out = programs[live](state, valid, src, seq)
        reference.live = live
        want = reference.round(batch)

        working = want.carried_rows + [None] * (PENDING - len(want.carried_rows)) + want.batch_rows
        slot, committed, executed = (np.asarray(a) for a in (out.slot, out.committed, out.executed))
        work_src, work_seq = np.asarray(out.work_src), np.asarray(out.work_seq)
        for w, row in enumerate(working):
            if row is None:
                assert (slot[w], committed[w], executed[w]) == (-1, False, False), w
            else:
                assert (work_src[w], work_seq[w]) == row.dot
                assert (slot[w], committed[w], executed[w]) == (row.slot, row.chosen, row.executed), (w, row)
        prefix = np.asarray(out.order)[: len(want.order)].tolist()
        assert [(work_src[w], work_seq[w]) for w in prefix] == want.order
        assert int(executed.sum()) == len(want.order)
        assert (int(out.pending), int(out.pend_dropped)) == (want.pending, want.dropped)
        assert int(out.exec_frontier) == int(state.exec_frontier) == want.frontier
        assert int(state.next_slot) == want.next_slot
        kept = reference.carried + [(-1, ref.Command(-1, -1, "", None))] * (PENDING - want.pending)
        assert np.asarray(state.pend_slot).tolist() == [s for s, _ in kept]
        assert np.asarray(state.pend_src).tolist() == [cmd.src for _, cmd in kept]
        assert np.asarray(state.pend_seq).tolist() == [cmd.seq for _, cmd in kept]

        waiting.extend(want.resubmit)
        seen["degraded"] += live < f + 1
        seen["overflowed"] += want.dropped > 0
        seen["chosen_rounds"] += live >= f + 1 and fill > 0
        seen["carried_executed"] += sum(row.executed for row in want.carried_rows)

    for _ in range(60):
        one_round(rng.choice(sorted(programs)), rng.randrange(BATCH + 1))
    assert min(seen.values()) > 0, seen  # every kind of round came up
    while waiting or reference.carried:  # recovery: all live, what is left first
        one_round(n, min(len(waiting), BATCH))
    assert sorted(reference.log) == [(1, number) for number in range(1, sent + 1)]  # each once
    assert reference.frontier == reference.next_slot == sent  # a dense log


# --- 2. the driver --------------------------------------------------------------------


class Pair:
    """The driver and the reference, given the same rounds; what the driver
    returned and handed back is compared wherever nothing is in flight."""

    N, F = 5, 1

    def __init__(self, stream):
        self.driver = PaxosDeviceDriver(self.N, f=self.F, batch_size=BATCH,
                                        pending_capacity=PENDING, monitor_execution_order=True)
        self.reference = ref.Reference(self.N, self.F, PENDING)
        self.stream = list(stream)  # commands not sent yet
        self.waiting = []  # handed back by the driver, to be sent again first
        self.sent = {}  # dot -> the command, as the reference takes it
        self.results = []  # the driver's, in the order it returned them
        self.wanted = []  # the reference's (dot, returned), in its order
        self.handed_back, self.want_back = [], []  # dots, as each side gave them

    def set_live(self, live):
        assert not self.driver.has_outstanding
        self.driver._programs[1] = self.driver._precompile(mesh_step.jit_paxos_step(
            self.driver._mesh, f=self.F, num_replicas=self.N, live_replicas=live))
        self.reference.live = self.N if live is None else live

    def take_back(self):
        again = [self.sent[dot.source, dot.sequence] for dot, _ in self.driver.take_requeue()]
        self.handed_back += [cmd.dot for cmd in again]
        self.waiting += again

    def batch(self, size, fresh=True):
        """``size`` commands: what the driver handed back first, then new ones."""
        self.take_back()
        batch, self.waiting = self.waiting[:size], self.waiting[size:]
        if fresh:
            new = size - len(batch)
            batch, self.stream = batch + self.stream[:new], self.stream[new:]
        self.sent.update((cmd.dot, cmd) for cmd in batch)
        return batch

    def rounds(self, mode, sizes, fresh=True):
        """A round of each size through the driver's ``mode`` (``step``, or
        ``serve`` under overlap a round (``overlap``) or a chain of two
        (``chain``) a call) and, a round each, through the reference."""
        sizes, chain = list(sizes), 2 if mode == "chain" else 1
        while sizes:
            batches = [self.batch(size, fresh) for size in sizes[:chain]]
            sizes = sizes[chain:]
            programs = [[to_program(cmd) for cmd in batch] for batch in batches]
            self.results += self.driver.serve(programs, overlap=mode != "step")
            for batch in batches:
                want = self.reference.round(batch)
                returned = {row.dot: row.returned for row in want.carried_rows + want.batch_rows}
                self.wanted += [(dot, returned[dot]) for dot in want.order]
                self.want_back += [cmd.dot for cmd in want.resubmit]

    def settle(self):
        """Nothing in flight: both sides have said the same so far."""
        self.results += self.driver.flush_pipeline()
        self.take_back()
        got = [((r.rifl.source, r.rifl.sequence), r.op_results) for r in self.results]
        assert got == self.wanted and len({dot for dot, _ in got}) == len(got)  # each rifl once
        assert [dot for dot, _ in got] == self.reference.log
        assert self.handed_back == self.want_back
        driver, reference = self.driver, self.reference
        assert driver.executed == len(reference.log) and driver.requeued == len(self.want_back)
        assert driver.slow_paths == driver.executed and driver.fast_paths == 0  # the one path
        assert driver.stable_watermark == reference.frontier
        assert driver._slot_base + driver._next_slot == reference.next_slot
        assert driver.in_flight == len(reference.carried)
        assert driver.store._store == reference.store


def test_the_driver_is_the_reference_through_every_mode_a_requeue_and_a_rebase():
    pair = Pair(commands(400, random.Random(SEED + 1)))
    driver = pair.driver

    # all live, every mode: everything sent executes in the round that takes it
    pair.rounds("step", [BATCH, 3, 0, 7])
    pair.rounds("overlap", [BATCH, 5, BATCH, 1])
    pair.rounds("chain", [BATCH, 2, 6, BATCH])
    pair.settle()
    executed_before = driver.executed
    assert executed_before == 64 and driver.requeued == 0 and not pair.reference.carried

    # a degraded stretch (f of n live: no accept quorum): nothing executes, the lowest
    # slots are carried, what is beyond the capacity comes back; the slot space is
    # rebased in the middle of it, carried slots and all
    pair.set_live(Pair.F)
    driver.SLOT_RESET_THRESHOLD = driver._next_slot + PENDING + BATCH
    pair.rounds("step", [BATCH, 4])
    pair.rounds("overlap", [BATCH, BATCH, 3])
    pair.rounds("chain", [5, BATCH])
    pair.settle()
    del driver.SLOT_RESET_THRESHOLD  # the class's own again: one rebase and no more
    assert driver.executed == executed_before and driver.in_flight == PENDING
    assert driver.requeued > 20 and driver.slot_epochs == 1
    assert driver._slot_base == executed_before  # rebased by the frontier
    assert driver.device_counters()["device_slot_epochs"] == 1

    # recovery: the carried slots execute first, then what was handed back, then new ones
    pair.set_live(None)
    carried = [cmd.dot for _, cmd in pair.reference.carried]
    pair.rounds("overlap", [BATCH, 2, BATCH])
    pair.rounds("chain", [BATCH, BATCH])
    pair.rounds("step", [6])
    pair.settle()
    while pair.waiting:
        pair.rounds("step", [BATCH], fresh=False)
    pair.settle()
    assert pair.reference.log[executed_before:][:PENDING] == carried
    assert driver.slot_epochs == 1 and driver.in_flight == 0 and not driver.has_requeue
    assert set(pair.sent) == set(pair.reference.log)  # everything sent was answered, once


# --- 3. the object protocol in the simulator ------------------------------------------


@dataclasses.dataclass
class Scripted(Workload):
    """A workload that sends ``script``, command after command."""

    script: tuple = ()

    def _gen_cmd(self, rifl_gen, key_gen_state):
        cmd = self.script[self.command_count - 1]
        rifl = rifl_gen.next_id()
        assert (rifl.source, rifl.sequence) == cmd.dot
        return 0, to_program(cmd)[1]


def test_the_object_protocol_executes_one_clients_sequence_in_the_references_slot_order():
    script = tuple(commands(40, random.Random(SEED + 2)))
    reference = ref.Reference(5, 1, PENDING)
    want = {}
    for start in range(0, len(script), BATCH):
        done = reference.round(script[start:start + BATCH])
        want.update((row.dot, row.returned) for row in done.batch_rows)
    assert reference.log == [cmd.dot for cmd in script]  # slot order is submission order

    config = Config(n=5, f=1, leader=1).with_(
        executor_monitor_execution_order=True, gc_interval_ms=100,
        executor_executed_notification_interval_ms=100, shard_count=1)
    planet = Planet.new("gcp")
    regions = sorted(planet.regions())[:5]
    workload = Scripted(shard_count=1, key_gen=ConflictRateKeyGen(50), keys_per_command=1,
                        commands_per_client=len(script), payload_size=1, script=script)
    runner = Runner(FPaxos, planet, config, workload, 1, process_regions=list(regions),
                    client_regions=[regions[0]], seed=SEED)  # one client, at the leader
    client = runner._simulation.get_client(1)
    assert client.targets() == {1}
    answered, sound = [], client.handle

    def keep(cmd_results, time):
        answered.extend(cmd_results)
        return sound(cmd_results, time)

    client.handle = keep
    runner.reorder_messages()
    _metrics, monitors, _latencies = runner.run(extra_sim_time_ms=10_000)

    assert [(r.rifl.source, r.rifl.sequence) for r in answered] == reference.log
    by_dot = {cmd.dot: cmd for cmd in script}
    for result in answered:
        dot = (result.rifl.source, result.rifl.sequence)
        assert result.results == {by_dot[dot].key: want[dot]}
    # all five replicas applied every key's commands in the reference's slot order
    for key in {cmd.key for cmd in script}:
        in_log = [Rifl(*dot) for dot in reference.log if by_dot[dot].key == key]
        assert all(monitor.get_order(key) == in_log for monitor in monitors.values())
    assert len(monitors) == 5
