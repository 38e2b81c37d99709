"""A read's commands stay a run from the ring to the device's columns (PR 52).

Two halves, each held to a plain per-command reference written here, which is
the loop the program had before:

* the ring holds runs and counts commands, and ``DeviceRuntime._collect`` takes
  it by slices: the batches, ``queue_wait_ms``, ``queue_released``,
  ``depth_hwm``, the notes to the ingest batcher, what goes back to the
  driver's requeue and what is left in the ring are those of a ring of
  ``(dot, cmd, at_ms)`` entries popped one by one;
* ``_DriverCore._identity_columns`` writes a round's ``src`` / ``seq`` /
  ``read`` / ``valid`` columns and fills the registry a column at a time, for
  the four drivers' column sets: equal to the row loop element for element,
  keys and entries, across a sequence-window advance and a gid / slot epoch
  reset.
"""

import random
from collections import deque

import numpy as np
import pytest

from fantoch_tpu.core.command import Command
from fantoch_tpu.core.config import Config
from fantoch_tpu.core.ids import Dot, Rifl
from fantoch_tpu.core.kvs import KVOp
from fantoch_tpu.run import device_drivers, device_runner
from fantoch_tpu.run.device_drivers import (
    CaesarDeviceDriver,
    DeviceDriver,
    NewtDeviceDriver,
    PaxosDeviceDriver,
    _sites_in_turn,
    _top_sequence,
)
from fantoch_tpu.run.device_runner import DeviceRuntime
from fantoch_tpu.run.pipeline import BoundedSubmitRing

# --- the ring and _collect ---


class _ReferenceRing:
    """The ring as it was: one ``(dot, cmd, at_ms)`` entry a command."""

    def __init__(self, capacity):
        self.capacity, self.depth_hwm, self.items = capacity, 0, deque()

    def try_extend(self, items):
        depth = len(self.items) + len(items)
        if self.capacity is not None and depth > self.capacity:
            return False
        self.items.extend(items)
        self.depth_hwm = max(self.depth_hwm, depth)
        return True


class _Reference:
    """The per-command ``_collect`` (the parent's loop, a line for a line)
    over a ``_ReferenceRing``; every entry also says which push it came with,
    so that the slices a run-wise collect must take can be counted."""

    def __init__(self, batch_size, capacity):
        self.batch_size = batch_size
        self.ring = _ReferenceRing(capacity)
        self.requeue = []
        self.queue_wait_ms, self.queue_released, self.slices = 0.0, 0, 0
        self.releases = []
        self.runs = 0

    def push(self, entries, at_ms):
        self.runs += 1
        return self.ring.try_extend([(dot, cmd, at_ms, self.runs) for dot, cmd in entries])

    def collect(self, chain, now_ms):
        batches = []
        pending, self.requeue = self.requeue, []
        released = 0
        arrived_ms = 0.0
        while (pending or self.ring.items) and len(batches) < chain:
            batch = []
            while pending and len(batch) < self.batch_size:
                batch.append(pending.pop(0))
            run_before = None
            while self.ring.items and len(batch) < self.batch_size:
                dot, cmd, at_ms, run = self.ring.items.popleft()
                batch.append((dot, cmd))
                released += 1
                arrived_ms += at_ms
                self.slices += run != run_before
                run_before = run
            batches.append(batch)
        if len(batches) > 1:
            keep = 1
            while keep * 2 <= len(batches):
                keep *= 2
            for batch in reversed(batches[keep:]):
                pending[:0] = batch
            del batches[keep:]
        if pending:
            self.requeue[:0] = pending
        if released:
            self.releases.append((now_ms, released))
            self.queue_wait_ms += released * now_ms - arrived_ms
            self.queue_released += released
        return batches or [[]]


class _Requeue:
    """What ``_collect`` asks of a driver."""

    def __init__(self, batch_size):
        self.batch_size, self._requeue = batch_size, []

    def take_requeue(self):
        out, self._requeue = self._requeue, []
        return out

    def give_back(self, pending):
        self._requeue[:0] = pending


class _Releases:
    def __init__(self):
        self.noted = []

    def note_arrivals(self, now_ms, count):
        pass

    def note_release(self, now_ms, count):
        self.noted.append((now_ms, count))

    def counters(self):
        return {}


@pytest.fixture(scope="module")
def unstarted():
    return DeviceRuntime(Config(3, 1), ("127.0.0.1", 0), batch_size=8, key_buckets=64)


def _rig(runtime, monkeypatch, batch_size, capacity):
    """``runtime`` with a ring of ``capacity``, a driver that only holds a
    requeue, a batcher that only listens, zeroed counters and a clock the
    test sets (``clock[0]``, in ms and in steps of 125: eighths of a second,
    so that the runtime's ``monotonic() * 1000.0`` is the number itself and
    ``n x at_ms`` a slice and ``at_ms`` a command sum to the same float)."""
    clock = [0.0]
    monkeypatch.setattr(device_runner, "monotonic", lambda: clock[0] / 1000.0)
    monkeypatch.setattr(runtime, "driver", _Requeue(batch_size))
    monkeypatch.setattr(runtime, "_submit_queue", BoundedSubmitRing(capacity))
    monkeypatch.setattr(runtime, "_batcher", _Releases())
    for counter in ("_queue_released", "_collect_slices", "submitted"):
        monkeypatch.setattr(runtime, counter, 0)
    monkeypatch.setattr(runtime, "_queue_wait_ms", 0.0)
    return clock


def _left(ring):
    """What a ring of runs still holds, a command at a time."""
    return [(dot, cmd, at_ms) for run, at_ms in ring._runs for dot, cmd in run]


def _agree(runtime, ref):
    ring = runtime._submit_queue
    assert _left(ring) == [entry[:3] for entry in ref.ring.items]
    assert len(ring) == len(ref.ring.items) and bool(ring) == bool(ref.ring.items)
    assert ring.depth_hwm == ref.ring.depth_hwm
    assert runtime.driver._requeue == ref.requeue
    assert (runtime._queue_wait_ms, runtime._queue_released) == (ref.queue_wait_ms, ref.queue_released)
    assert runtime._batcher.noted == ref.releases
    assert runtime._collect_slices == ref.slices
    assert all(run for run, _at in ring._runs)


@pytest.mark.parametrize("seed", range(12))
def test_collect_by_slices_is_the_per_command_loop(unstarted, monkeypatch, seed):
    """Seeded random reads (runs of 1 to 2000), a session's ``submit`` of one,
    a requeue in front, the capacity hit inside a read, chains of 1 to 8."""
    rng = random.Random(5200 + seed)
    batch_size = rng.choice([64, 200, 1024, 4096])
    capacity = rng.choice([None, 3 * batch_size, 5000])
    runtime = unstarted
    clock = _rig(runtime, monkeypatch, batch_size, capacity)
    ref = _Reference(batch_size, capacity)
    sequence = 0

    def entries(count):
        nonlocal sequence
        made = [(Dot(1 + rng.randrange(5), sequence + i + 1), f"c{sequence + i + 1}") for i in range(count)]
        sequence += count
        return made

    shed = 0
    for turn in range(60):
        clock[0] += 125.0 * rng.randrange(1, 40)
        what = rng.random()
        if what < 0.55:
            # a socket read, as _admit pushes it: what the ring has room for
            # goes in together, the commands past that are shed
            read = entries(rng.choice([1, 2, 7, 300, 700, 2000]) if rng.random() < 0.5 else rng.randrange(1, 2001))
            room = runtime.room()
            admitted = read if room is None else read[:room]
            shed += len(read) - len(admitted)
            if admitted:
                runtime.submit_all(list(admitted), clock[0])
                assert ref.push(admitted, clock[0])
            if room is not None and len(read) > room:
                # at its bound: one more is refused, all or none, and nothing moved
                with pytest.raises(Exception, match="[Oo]verload"):
                    runtime.submit_all(entries(1), clock[0])
                assert not ref.push([(None, None)], clock[0])
        elif what < 0.65:
            if runtime.room() != 0:
                ((dot, cmd),) = entries(1)
                runtime.submit(dot, cmd)
                assert ref.push([(dot, cmd)], clock[0])
        elif what < 0.72:
            # an overflow's commands, handed back by a drain
            back = entries(rng.randrange(1, 2 * batch_size))
            runtime.driver._requeue.extend(back)
            ref.requeue.extend(back)
        else:
            chain = rng.randrange(1, 9)
            got = runtime._collect(turn, chain)
            assert got == ref.collect(chain, clock[0])
            assert all(len(batch) <= batch_size for batch in got)
            assert len(got) in (1, 2, 4, 8)
        _agree(runtime, ref)
    while runtime._submit_queue or runtime.driver._requeue:
        clock[0] += 125.0
        assert runtime._collect(0, 4) == ref.collect(4, clock[0])
        _agree(runtime, ref)
    assert runtime.submitted == ref.queue_released == runtime._queue_released
    assert capacity is None or (ref.ring.depth_hwm <= capacity and (shed or ref.ring.depth_hwm < capacity))
    assert runtime._collect(0, 4) == [[]]  # nothing queued: a progress round


def test_the_ring_counts_commands_whatever_the_runs():
    ring = BoundedSubmitRing(capacity=10)
    assert ring.try_extend(list("abcd"), 1.0) and ring.try_push("e", 2.0)
    assert (len(ring), ring.depth_hwm, bool(ring)) == (5, 5, True)
    assert ring.try_extend([], 3.0) and len(ring._runs) == 2  # a run of none is no run
    assert not ring.try_extend(list("fghijk"), 3.0)  # all or none
    assert (len(ring), ring.depth_hwm) == (5, 5) and ring.stats()["depth"] == 5
    assert ring.try_extend(list("fghij"), 3.0) and not ring.try_push("k", 4.0)
    assert (len(ring), ring.depth_hwm, ring.sheds) == (10, 10, 0)
    # a slice is a run or the head of one; the tail keeps its place and its time
    assert ring.take(3) == (["a", "b", "c"], 1.0) and len(ring) == 7
    assert ring.popleft() == "d" and ring.popleft() == "e" and len(ring) == 5
    assert ring.take(5) == (list("fghij"), 3.0)
    assert (len(ring), bool(ring), ring.depth_hwm) == (0, False, 10)
    with pytest.raises(IndexError):
        ring.popleft()
    unbounded = BoundedSubmitRing()
    assert unbounded.try_extend(list(range(5000)), 0.0) and len(unbounded) == 5000
    assert unbounded.stats() == {"depth": 5000, "depth_hwm": 5000, "capacity": 0, "sheds": 0}


def test_a_run_split_at_a_batch_boundary_keeps_its_order_and_its_arrival_time(unstarted, monkeypatch):
    runtime, driver = unstarted, unstarted.driver
    clock = _rig(runtime, monkeypatch, batch_size=100, capacity=None)
    first = [(Dot(1, i), f"a{i}") for i in range(1, 61)]
    second = [(Dot(2, i), f"b{i}") for i in range(1, 91)]
    runtime.submit_all(list(first), 1000.0)
    runtime.submit_all(list(second), 1125.0)
    clock[0] = 1250.0
    assert runtime._collect(1, 1) == [first + second[:40]]
    assert runtime._submit_queue._runs == deque([(second[40:], 1125.0)]) and len(runtime._submit_queue) == 50
    assert (runtime._queue_released, runtime._collect_slices) == (100, 2)
    assert runtime._queue_wait_ms == 60 * 250.0 + 40 * 125.0
    third = [(Dot(3, i), f"c{i}") for i in range(1, 11)]
    runtime.submit_all(list(third), 1375.0)
    clock[0] = 1500.0
    # the tail is first, under the time its read came at
    assert runtime._collect(2, 1) == [second[40:] + third]
    assert (runtime._queue_released, runtime._collect_slices) == (160, 4)
    assert runtime._queue_wait_ms == 60 * 250.0 + 40 * 125.0 + 50 * 375.0 + 10 * 125.0
    assert runtime._batcher.noted == [(1250.0, 100), (1500.0, 60)]
    # the snapshot has the counter beside queue_released
    monkeypatch.setattr(runtime, "driver", driver)
    runtime._publish_tallies()
    assert runtime._tallies["collect_slices"] == 4 and runtime._tallies["queue_released"] == 160


def test_a_chain_cut_to_a_power_of_two_hands_its_rest_to_the_requeue_in_order(unstarted, monkeypatch):
    runtime = unstarted
    _rig(runtime, monkeypatch, batch_size=10, capacity=None)
    back = [(Dot(9, i), f"r{i}") for i in range(1, 5)]
    run = [(Dot(1, i), f"a{i}") for i in range(1, 30)]
    runtime.driver._requeue.extend(back)
    runtime.submit_all(list(run), 5.0)
    # 33 commands are four rounds; a chain of three is cut to two
    got = runtime._collect(1, 3)
    assert got == [back + run[:6], run[6:16]]
    assert runtime.driver._requeue == run[16:26] and _left(runtime._submit_queue) == [
        (dot, cmd, 5.0) for dot, cmd in run[26:]]
    assert (runtime._queue_released, runtime._collect_slices) == (26, 3)
    assert runtime._collect(2, 8) == [run[16:26], run[26:]]
    assert (runtime._queue_released, runtime._collect_slices) == (29, 4)


def test_the_ingest_span_is_a_slices_loop_taken_only_while_the_tracer_is_on(unstarted, monkeypatch):
    runtime = unstarted
    _rig(runtime, monkeypatch, batch_size=4, capacity=None)

    class _Spans:
        enabled = False

        def __init__(self):
            self.seen = []

        def span(self, stage, rifl, dot=None, pid=None, meta=None):
            self.seen.append((stage, rifl, dot, pid, meta["round"]))

    tracer = _Spans()
    monkeypatch.setattr(runtime, "tracer", tracer)
    cmds = [Command.from_single(Rifl(7, i), 0, f"k{i}", KVOp.get()) for i in range(1, 7)]
    run = [(Dot(1, i), cmd) for i, cmd in enumerate(cmds, 1)]
    runtime.submit_all(list(run), 0.0)
    assert runtime._collect(11, 1) == [run[:4]] and tracer.seen == []
    tracer.enabled = True
    assert runtime._collect(12, 1) == [run[4:]]
    assert tracer.seen == [("ingest", cmd.rifl, dot, runtime.process_id, 12) for dot, cmd in run[4:]]


def test_collect_has_no_loop_over_commands_but_the_tracers():
    """Its source: one ``for`` over a slice, under ``tracer.enabled``; the
    other loops run over rounds and slices."""
    import inspect

    lines = inspect.getsource(DeviceRuntime._collect).splitlines()
    loops = [i for i, line in enumerate(lines) if line.strip().startswith(("for ", "while "))]
    per_command = [i for i in loops if "in run" in lines[i]]
    assert len(per_command) == 1 and lines[per_command[0] - 4].strip() == "if tracing:"
    assert sum("tracing = tracer.enabled" in line for line in lines) == 1
    assert not any("popleft" in line or "pop(0)" in line for line in lines)
    # ... nor does the driver's assembly (``_assemble`` and what it calls) walk one
    source = inspect.getsource(device_drivers)
    assert "enumerate(batch)" not in source


# --- the identity columns and the registry ---

BATCH = 16


class _RowByRow:
    """The row loops the three ``_assemble``s had, as the one helper's body:
    a numpy scalar store a column a command, ``cmd.read_only``, a registry
    store a command."""

    def _identity_columns(self, batch, src_row, seq_row, read_row=None, valid_row=None, first_gid=None):
        if batch:
            self._ensure_seq_window(batch, max(dot.sequence for dot, _ in batch))
        for i, (dot, cmd) in enumerate(batch):
            seq = dot.sequence - self._seq_base
            assert 0 <= seq < 2**31 - 1
            src_row[i] = dot.source
            seq_row[i] = seq
            if read_row is not None:
                read_row[i] = cmd.read_only
            if valid_row is not None:
                valid_row[i] = True
            key = self._packed(dot.source, seq_row[i]) if first_gid is None else first_gid + i
            self._cmds[key] = (dot, cmd)


DRIVERS = {
    "dep_commit": (DeviceDriver, dict(num_replicas=3), ("key", "src", "seq", "read")),
    "dep_commit_2key_4shard": (
        DeviceDriver, dict(num_replicas=3, shard_count=4, key_width=2), ("key", "src", "seq", "read")),
    "newt": (NewtDeviceDriver, dict(num_replicas=3), ("key", "src", "seq")),
    "caesar": (CaesarDeviceDriver, dict(num_replicas=3), ("key", "src", "seq")),
    "paxos": (PaxosDeviceDriver, dict(num_replicas=3), ("valid", "src", "seq")),
}


def _pair(name, **more):
    """The driver and its row-by-row twin, each recording what every
    ``_assemble`` staged (copies: the staging ring is reused) and the
    registry it left."""
    cls, kwargs, names = DRIVERS[name]
    kwargs = dict(kwargs, batch_size=BATCH, key_buckets=64, pending_capacity=BATCH, **more)
    pair = []
    for kind in (cls, type("RowByRow" + cls.__name__, (_RowByRow, cls), {})):
        driver = kind(**kwargs)
        assert [spec[0] for spec in driver._column_specs()] == list(names)
        driver.staged = []
        assemble = driver._assemble

        def recording(batch, driver=driver, assemble=assemble):
            staged = assemble(batch)
            columns = staged[0] if isinstance(staged[0], tuple) else staged
            driver.staged.append((tuple(np.array(c) for c in columns), dict(driver._cmds)))
            return staged

        driver._assemble = recording
        pair.append(driver)
    return pair


def _cmd(source, sequence, shard_count=1, keys=1):
    rifl = Rifl(source, sequence)
    op = KVOp.get() if sequence % 3 == 0 else KVOp.put(f"v{sequence}")
    if keys == 1:
        return Dot(source, sequence), Command.from_single(rifl, 0, f"k{sequence % 5}", op)
    return Dot(source, sequence), Command(
        rifl, {sequence % shard_count: {f"a{sequence % 5}": (op,)}, (sequence + 1) % shard_count: {f"b{sequence % 7}": (op,)}})


def _same_staging(real, ref):
    assert len(real.staged) == len(ref.staged) > 0
    for (mine, registry), (theirs, registry_ref) in zip(real.staged, ref.staged):
        for column, column_ref in zip(mine, theirs):
            assert column.dtype == column_ref.dtype and np.array_equal(column, column_ref)
        # keys and entries, and the order they were registered in
        assert list(registry.items()) == list(registry_ref.items())
        assert all(type(key) is int and type(entry) is tuple for key, entry in registry.items())


@pytest.mark.parametrize("name", DRIVERS)
def test_the_columns_and_the_registry_are_the_row_loops(name):
    """Batches of none, one and a full round, then a part-full one; from
    several sources; a dot's sequence need not ascend within a batch."""
    real, ref = _pair(name)
    sharded = DRIVERS[name][1].get("shard_count", 1)
    keys = DRIVERS[name][1].get("key_width", 1)
    sequence = 0
    for count in (0, 1, BATCH, 5, 0, BATCH):
        batch = [_cmd(1 + (sequence + i) % 3, sequence + i + 1, sharded, keys) for i in range(count)]
        sequence += count
        batch.reverse()
        for driver in (real, ref):
            driver.results = getattr(driver, "results", []) + [
                (r.rifl, r.key, tuple(r.op_results)) for r in driver.step(list(batch))]
    _same_staging(real, ref)
    assert real.results == ref.results and real.executed == ref.executed == 2 * BATCH + 6
    assert real.in_flight == ref.in_flight == 0
    # what the columns say of the last full round, against the batch itself
    columns, _registry = real.staged[-1]
    by_name = dict(zip(DRIVERS[name][2], columns))
    assert by_name["src"].tolist() == [dot.source for dot, _ in batch]
    assert by_name["seq"].tolist() == [dot.sequence for dot, _ in batch]
    if "read" in by_name:
        assert by_name["read"].tolist() == [cmd.read_only for _, cmd in batch] and by_name["read"].any()
    if "valid" in by_name:
        assert by_name["valid"].all() and not real.staged[-2][0][0].any()
    # a part-full round leaves the rows past its batch as staged
    part = dict(zip(DRIVERS[name][2], real.staged[3][0]))
    assert not part["src"][5:].any() and not part["seq"][5:].any()
    assert not part.get("read", part["src"])[5:].any() and not part.get("valid", part["src"])[5:].any()


@pytest.mark.parametrize("name", DRIVERS)
def test_across_a_sequence_window_advance(name):
    real, ref = _pair(name)
    sharded = DRIVERS[name][1].get("shard_count", 1)
    keys = DRIVERS[name][1].get("key_width", 1)
    for driver in (real, ref):
        driver.SEQ_WINDOW_MAX = 24  # instance override: an advance every other round
        sequence = 0
        for count in (BATCH, 1, BATCH, 7, BATCH, BATCH):
            batch = [_cmd(1, sequence + i + 1, sharded, keys) for i in range(count)]
            sequence += count
            driver.step(batch)
    _same_staging(real, ref)
    assert real.seq_epochs == ref.seq_epochs >= 2 and real._seq_base == ref._seq_base > 0
    assert real.executed == ref.executed == 4 * BATCH + 8
    # the columns hold window sequences: the last batch's, under the last base
    seq_column = dict(zip(DRIVERS[name][2], real.staged[-1][0]))["seq"]
    assert seq_column.tolist() == [dot.sequence - real._seq_base for dot, _ in batch]


@pytest.mark.parametrize("name", ["dep_commit", "paxos"])
def test_across_a_gid_or_slot_epoch_reset(name):
    real, ref = _pair(name)
    threshold, epochs = (
        ("GID_RESET_THRESHOLD", "gid_epochs") if name == "dep_commit" else ("SLOT_RESET_THRESHOLD", "slot_epochs"))
    for driver in (real, ref):
        sequence = 0
        for count in (BATCH, 3, BATCH, 1, BATCH):
            if count == 3:
                setattr(driver, threshold, 2 * BATCH + 1)  # instance override: a reset from here on
            batch = [_cmd(1 + i % 2, sequence + i + 1) for i in range(count)]
            sequence += count
            driver.step(batch)
    _same_staging(real, ref)
    assert getattr(real, epochs) == getattr(ref, epochs) >= 2
    assert real.executed == ref.executed == 3 * BATCH + 4
    if name == "dep_commit":
        # registered under the gids its rows get: the epoch's next gid on
        assert real._next_gid == ref._next_gid < 2 * BATCH + 1


def test_with_the_sites_taken_in_turn():
    """A coordinator at every site: the batch is put in turn before its
    columns are written, and the registry's gids follow the rows."""
    real, ref = _pair("dep_commit", num_replicas=5)
    for driver in (real, ref):
        driver.register_site(1)
        driver.register_site(3)
    sequences = {1: 0, 2: 0, 4: 0}
    batches = []
    for stretch in ((1, 1, 1, 1, 2, 2, 4, 4, 4, 4, 4, 1), (4,), (2, 2, 2, 2, 2, 2, 2, 1, 1, 4, 4, 4, 4, 4, 4, 4)):
        batch = []
        for source in stretch:
            sequences[source] += 1
            dot, cmd = _cmd(source, sequences[source])
            batch.append((dot, Command.from_single(Rifl(source, dot.sequence), 0, f"k{dot.sequence % 5}",
                                                   KVOp.get() if source == 2 else KVOp.put("v"))))
        batches.append(batch)
    for driver in (real, ref):
        for batch in batches:
            driver.step(list(batch))
    _same_staging(real, ref)
    (key, src, seq, read), registry = real.staged[0]
    turned = _sites_in_turn(batches[0])
    assert turned != batches[0] and src[:12].tolist() == [dot.source for dot, _ in turned]
    assert read[:12].tolist() == [dot.source == 2 for dot, _ in turned]
    assert list(registry.values()) == turned and list(registry) == list(range(12))
    assert real.executed == ref.executed == 29


@pytest.mark.parametrize("name", DRIVERS)
def test_a_sequence_past_the_window_still_raises_what_it_raised(name):
    """A command pinned in flight under an old sequence while the batch's top
    is a window away: the advance cannot fit both and says so; a sequence
    below the window's base is refused as it was, by an assertion."""
    real, ref = _pair(name)
    sharded = DRIVERS[name][1].get("shard_count", 1)
    keys = DRIVERS[name][1].get("key_width", 1)
    for driver in (real, ref):
        driver.step([_cmd(1, 50, sharded, keys)])
        driver._requeue.append(_cmd(1, 60, sharded, keys))  # pinned: waits to be submitted again
        with pytest.raises(RuntimeError, match="dot-sequence window cannot advance"):
            driver._assemble([_cmd(1, 61, sharded, keys), _cmd(1, 2**31 + 100, sharded, keys)])
        assert driver.seq_epochs == 0
    for driver in _pair(name):
        driver._seq_base = 1000
        with pytest.raises(AssertionError):
            driver._assemble([_cmd(1, 1001, sharded, keys), _cmd(1, 999, sharded, keys)])


def test_the_drivers_flush_test_reads_the_batchs_top_sequence():
    driver = NewtDeviceDriver(3, batch_size=BATCH, key_buckets=64, pending_capacity=BATCH)
    batch = [_cmd(1 + i % 3, 7 + (i * 5) % 11) for i in range(9)]
    assert _top_sequence(batch) == max(dot.sequence for dot, _ in batch)
    assert not driver._pipeline_flush_needed([]) and not driver._pipeline_flush_needed(batch)
    driver.SEQ_WINDOW_MAX = _top_sequence(batch)
    assert driver._pipeline_flush_needed(batch) and not driver._pipeline_flush_needed(batch[:1])
    assert driver._chain_windows_blocked([batch[:1], [], batch])
    assert not driver._chain_windows_blocked([batch[:1], []]) and not driver._chain_windows_blocked([[], []])
