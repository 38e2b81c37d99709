"""The hold on the sessions' sockets around a step
(run/device_runner.py ``DeviceRuntime._step_on_pool``; run/rw.py
``Rw.hold_reading`` / ``release_reading`` / ``on_read``): a started
``DeviceRuntime`` on the CPU behind real TCP connections, its step made as
slow as the test wants by a stand-in on the pool thread that waits for the
test's word, so that what arrives "during a step" is exact.
"""

import asyncio
import threading

import jax
import pytest

# as tests/test_device_runner.py: the device drivers' scan bodies crash
# jaxlib 0.4.x flakily while tracing
if tuple(int(x) for x in jax.__version__.split(".")[:2]) < (0, 5):
    pytest.skip("jax<0.5: device-driver scan tracing segfaults flakily", allow_module_level=True)

from fantoch_tpu.core import Command, Config, KVOp, Rifl
from fantoch_tpu.run import device_runner as dr
from fantoch_tpu.run import rw
from fantoch_tpu.run.harness import free_port
from fantoch_tpu.run.prelude import ClientHi, ClientHiAck, Submit

BATCH = 16  # a round's rows


def _frame(client, seq, key, value="v"):
    return rw.frame(Submit(Command.from_single(Rifl(client, seq), 0, key, KVOp.put(value))))


async def _until(condition, what, tries=2000):
    for _ in range(tries):
        if condition():
            return
        await asyncio.sleep(0.005)
    raise AssertionError(f"never: {what}")


class _Client:
    """One connection of a client: raw frames out, raw reply bytes in."""

    def __init__(self, reader, writer):
        self.reader, self.writer = reader, writer

    @classmethod
    async def connect(cls, addr, *client_ids):
        self = cls(*await asyncio.open_connection(*addr))
        self.writer.write(rw.frame(ClientHi(list(client_ids))))
        assert rw.deserialize(await self.frame()) == ClientHiAck()
        return self

    async def frame(self):
        (length,) = rw._LEN.unpack(await asyncio.wait_for(self.reader.readexactly(4), 10))
        return await asyncio.wait_for(self.reader.readexactly(length), 10)

    async def replies(self, n):
        return [await self.frame() for _ in range(n)]

    async def close(self):
        self.writer.close()
        await self.writer.wait_closed()


class _Held:
    """A started runtime whose steps wait on the pool thread for the
    test's word: ``step()`` lets exactly one through."""

    def __init__(self, protocol="epaxos", **config):
        self.addr = ("127.0.0.1", free_port())
        # the ingest gate is off: a dispatch carries what the ring holds
        self.runtime = dr.DeviceRuntime(
            Config(3, 1, shard_count=1, ingest_deadline_ms=0.0, **config), self.addr,
            protocol=protocol, batch_size=BATCH, key_buckets=64,
        )
        self.entered = threading.Semaphore(0)
        self.go = threading.Semaphore(0)
        # what each step saw on the pool thread: the rows it dispatched, and
        # whether each live session's transport was being read
        self.seen = []
        serve = self.runtime.driver.serve

        def slow(batches, pipeline):
            self.seen.append((sum(map(len, batches)), self.reading()))
            self.entered.release()
            assert self.go.acquire(timeout=20)
            return serve(batches, pipeline)

        self.runtime.driver.serve = slow

    def reading(self):
        return sorted(s.rw._writer.transport.is_reading() for s in self.runtime._sessions)

    async def __aenter__(self):
        await self.runtime.start()
        return self

    async def __aexit__(self, *exc):
        self.go.release(100)  # whatever still waits goes through
        await self.runtime.stop()

    async def in_step(self):
        """Wait until a step stands on the pool thread."""
        await _until(lambda: self.entered.acquire(blocking=False), "a step on the pool thread")

    async def step(self):
        """Let the step that stands through, and wait for its round's end."""
        rounds = self.runtime.stages.counters()["stage_round_n"]
        self.go.release()
        await _until(lambda: self.runtime.stages.counters()["stage_round_n"] > rounds, "the round's end")

    def tallies(self):
        self.runtime._publish_tallies()
        return self.runtime._tallies


def _serve(script, eager=False, **config):
    async def go():
        if eager:
            # a task runs at its creation, up to its first wait: another
            # order of a turn's work than the loop's own
            asyncio.get_running_loop().set_task_factory(asyncio.eager_task_factory)
        async with _Held(**config) as held:
            return await script(held)

    return asyncio.run(go())


def test_commands_sent_during_a_held_step_are_read_once_at_its_end_and_answered_as_by_an_unheld_server(monkeypatch):
    """Three sessions, each with a chain on a key of its own (the returned
    previous values show the order of admission within the session): the
    first session's first command goes into a round whose step stands; the
    fourteen others arrive meanwhile, in two writes a session; no socket is
    read until the step returns, then each is read once, its commands one
    run of the ring, all answered by the next round.  A server that reads
    its sockets during the step gives each session the same bytes."""

    async def script(held):
        runtime = held.runtime
        holds = rw.Rw.hold_reading is hold_reading
        clients = [await _Client.connect(held.addr, c) for c in (1, 2, 3)]
        clients[0].writer.write(_frame(1, 1, "key1", "1"))
        await held.in_step()
        if holds:
            assert held.seen[0] == (1, [False, False, False]) and runtime._reads_held
        reads_before = held.tallies()["session_reads"]
        for c, client in enumerate(clients, start=1):
            # five commands a session, in two writes (the first session's first is in the step)
            frames = [_frame(c, seq, f"key{c}", str(seq)) for seq in range(1, 6)][c == 1:]
            client.writer.write(b"".join(frames[:2]))
            await client.writer.drain()
            await asyncio.sleep(0.02)
            client.writer.write(b"".join(frames[2:]))
            await client.writer.drain()
        await asyncio.sleep(0.1)
        during = held.tallies()
        if holds:
            # nothing was read while the step stood
            assert during["session_reads"] == reads_before and during["submitted"] == 1
        else:
            assert during["session_reads"] >= reads_before + 3 and during["submitted"] == 15
        await held.step()
        got = [await clients[0].replies(1), [], []]
        # the next dispatch: what the release's reads brought, all of it
        await held.in_step()
        after = held.tallies()
        assert held.seen[1][0] == 14 and after["submitted"] == 15
        if holds:
            # one read a socket, one run of the ring each
            assert after["session_reads"] == reads_before + 3 and after["collect_slices"] == 1 + 3
        await held.step()
        for c, client in enumerate(clients):
            got[c] += await client.replies(4 + (c > 0))
        counted = held.tallies()
        for client in clients:
            await client.close()
        return got, counted

    hold_reading = rw.Rw.hold_reading
    got, counted = _serve(script)
    # in each session's order: the previous values of its chain
    for c, replies in enumerate(got, start=1):
        results = [rw.deserialize(frame).cmd_result for frame in replies]
        assert [r.rifl for r in results] == [Rifl(c, seq) for seq in range(1, 6)]
        assert [r.results[f"key{c}"] for r in results] == [(None,), ("1",), ("2",), ("3",), ("4",)]
    # the round of one command was held, and the one of fourteen, most of a round
    assert (counted["device_held_dispatches"], counted["device_dispatches"]) == (2, 2)
    monkeypatch.setattr(rw.Rw, "hold_reading", lambda self: None)  # a server whose hold holds nothing
    unheld, _ = _serve(script)
    assert unheld == got


@pytest.mark.parametrize("rows, clients, counted", [
    (1, 2, (1, 1)), (BATCH, 2, (1, 1)),
    # two full rounds and three rows: three dispatches, whatever each carries
    (2 * BATCH + 3, 2, (3, 3)),
    # no session is live: nothing to hold, nothing counted
    (1, 0, (0, 1)), (2 * BATCH + 3, 0, (0, 3)),
])
def test_every_step_holds_the_live_sessions_sockets_whatever_it_dispatches(rows, clients, counted):
    """``device_held_dispatches`` counts the dispatches whose step held a
    live session's socket; every transport is read again after it."""

    async def script(held):
        runtime = held.runtime
        conns = [await _Client.connect(held.addr, c) for c in range(1, clients + 1)]
        await _until(lambda: len(runtime._sessions) == clients, "the sessions live")
        for seq in range(1, rows + 1):
            cmd = Command.from_single(Rifl(9, seq), 0, f"k{seq}", KVOp.put("v"))
            runtime.submit(runtime.dot_gen.next_id(), cmd)
        seen = []
        while runtime.driver.executed < rows:
            await held.in_step()
            seen.append((held.seen[-1], runtime._reads_held))
            await held.step()
        await asyncio.sleep(0.05)
        out = seen, held.tallies(), held.reading(), runtime._reads_held, set(runtime._reads_due)
        for conn in conns:
            await conn.close()
        return out

    seen, tallies, reading_after, held_after, due_after = _serve(script, serving_chain_max=1)
    assert seen[0] == ((min(rows, BATCH), [False] * clients), True)
    assert all(reading == [False] * clients and held for (_, reading), held in seen)
    assert reading_after == [True] * clients and held_after is False and not due_after
    assert (tallies["device_held_dispatches"], tallies["device_dispatches"]) == counted


def test_a_step_that_raises_leaves_every_transport_reading():
    async def script(held):
        runtime = held.runtime
        clients = [await _Client.connect(held.addr, c) for c in (1, 2, 3)]
        seen = []

        def boom():
            seen.append(held.reading())
            raise RuntimeError("the step's own")

        with pytest.raises(RuntimeError, match="the step's own"):
            await runtime._step_on_pool(1, boom)
        out = seen, held.reading(), runtime._reads_held, runtime._held_dispatches
        # ... and the sessions serve on
        clients[0].writer.write(_frame(1, 1, "a"))
        await held.in_step()
        await held.step()
        assert rw.deserialize((await clients[0].replies(1))[0]).cmd_result.rifl == Rifl(1, 1)
        for client in clients:
            await client.close()
        return out

    seen, reading_after, held_after, counted = _serve(script)
    assert seen == [[False, False, False]]
    assert reading_after == [True, True, True] and held_after is False and counted == 0


def test_a_hello_during_a_held_step_is_acknowledged_before_it_returns_and_its_session_is_read_after_it():
    async def script(held):
        runtime = held.runtime
        first = await _Client.connect(held.addr, 1)
        first.writer.write(_frame(1, 1, "a"))
        await held.in_step()
        assert runtime._reads_held and held.reading() == [False]
        # the listener is never held: the hello's ack comes while the step stands
        late = await _Client.connect(held.addr, 2)
        await _until(lambda: len(runtime._sessions) == 2, "the second session live")
        assert runtime._reads_held and held.reading() == [False, False]
        reads = held.tallies()["session_reads"]
        late.writer.write(_frame(2, 1, "b"))
        await late.writer.drain()
        await asyncio.sleep(0.1)
        assert held.tallies()["session_reads"] == reads and runtime.submitted == 1
        await held.step()
        await held.in_step()  # the late session's command, read at the release
        assert held.seen[1][0] == 1
        await held.step()
        got = [rw.deserialize((await c.replies(1))[0]).cmd_result.rifl for c in (first, late)]
        for client in (first, late):
            await client.close()
        return got

    assert _serve(script) == [Rifl(1, 1), Rifl(2, 1)]


def test_a_session_that_closes_while_held_is_dropped_at_the_release_and_leaks_nothing():
    async def script(held):
        runtime = held.runtime
        stays, goes = await _Client.connect(held.addr, 1), await _Client.connect(held.addr, 2)
        stays.writer.write(_frame(1, 1, "a"))
        goes.writer.write(_frame(2, 1, "b"))
        await _until(lambda: runtime.submitted == 2 or held.seen, "the first commands in")
        await held.in_step()
        # more of its commands, then its close, all while the step stands
        goes.writer.write(_frame(2, 2, "b"))
        await goes.close()
        await asyncio.sleep(0.05)
        assert len(runtime._sessions) == 2  # its end is not seen before the release
        await held.step()
        await _until(lambda: len(runtime._sessions) == 1, "the closed session dropped")
        while runtime.driver.executed < 3:
            await held.in_step()
            await held.step()
        assert rw.deserialize((await stays.replies(1))[0]).cmd_result.rifl == Rifl(1, 1)
        await asyncio.sleep(0.05)
        out = len(runtime._sessions), dict(runtime.rifl_sessions), held.reading(), runtime.failure
        await stays.close()
        await _until(lambda: not runtime._sessions, "the last session dropped")
        return out

    # what it sent before its close was executed for the cluster; nothing of it is kept
    assert _serve(script) == (1, {}, [True], None)


@pytest.mark.parametrize("eager", [False, True], ids=["loop_order", "eager_tasks"])
def test_what_the_kernel_kept_is_in_the_next_round_even_where_the_ring_was_not_empty_at_the_release(eager):
    """A read that lands in the turn a hold begins in is admitted during
    the step, so the ring is not empty when the step returns: the next
    round waits for the released sockets' reads all the same, and carries
    both, instead of carrying the straggler alone and holding the sockets,
    full by now, for a second step.  The wait is for the reads themselves
    (``_reads_in``), not for a count of the loop's turns: under another
    order of a turn's work it holds as well."""

    async def script(held):
        runtime = held.runtime
        clients = [await _Client.connect(held.addr, c) for c in (1, 2)]
        clients[0].writer.write(_frame(1, 1, "a"))
        await held.in_step()
        # the straggler: in the ring while the step stands
        runtime.submit(runtime.dot_gen.next_id(), Command.from_single(Rifl(9, 1), 0, "s", KVOp.put("v")))
        for c, client in enumerate(clients, start=1):
            client.writer.write(b"".join(_frame(c, seq, f"k{c}") for seq in (2, 3, 4)))
            await client.writer.drain()
        await asyncio.sleep(0.05)
        assert runtime.submitted == 2
        await held.step()
        await held.in_step()
        carried = held.seen[1][0], runtime.submitted
        await held.step()
        for client in clients:
            await client.close()
        return carried

    assert _serve(script, eager=eager) == (7, 8)


def test_a_part_of_a_frame_waiting_at_the_release_does_not_keep_the_next_round_back():
    """The release waits for the read of each socket that has bytes
    waiting, not for a whole frame of it: a client that stalls inside a
    frame stalls its own command and no other's."""

    async def script(held):
        runtime = held.runtime
        slow, other = await _Client.connect(held.addr, 1), await _Client.connect(held.addr, 2)
        other.writer.write(_frame(2, 1, "b"))
        await held.in_step()
        frame = _frame(1, 1, "a")
        slow.writer.write(frame[:7])  # the length and three bytes of the payload
        other.writer.write(_frame(2, 2, "b"))
        await slow.writer.drain()
        await other.writer.drain()
        await asyncio.sleep(0.05)
        await held.step()
        await held.in_step()  # the other session's command goes on
        carried = held.seen[1][0], set(runtime._reads_due), runtime.submitted
        await held.step()
        got = [rw.deserialize(f).cmd_result.rifl for f in await other.replies(2)]
        slow.writer.write(frame[7:])
        await held.in_step()
        await held.step()
        got.append(rw.deserialize((await slow.replies(1))[0]).cmd_result.rifl)
        for client in (slow, other):
            await client.close()
        return carried, got

    assert _serve(script) == ((1, set(), 2), [Rifl(2, 1), Rifl(2, 2), Rifl(1, 1)])


@pytest.mark.parametrize("eager", [False, True], ids=["loop_order", "eager_tasks"])
def test_rounds_that_follow_each_other_without_a_wait_are_each_held_and_the_sockets_read_between_them(eager):
    """Commands left in the device's pending buffer make rounds follow
    each other with no idle wait between them: the released sockets'
    reads still come in before the next round is collected."""

    async def script(held):
        runtime = held.runtime
        client = await _Client.connect(held.addr, 1)
        runtime.driver.__class__ = type("Pending", (type(runtime.driver),), {"in_flight": property(lambda self: 1)})
        client.writer.write(_frame(1, 1, "a"))
        await held.in_step()
        carried = []
        for seq in (2, 3, 4):
            # a command a step, sent while it stands; rounds follow at once (in_flight > 0)
            client.writer.write(_frame(1, seq, "a"))
            await client.writer.drain()
            await asyncio.sleep(0.02)
            await held.step()
            await held.in_step()
            carried.append(held.seen[-1])
        await client.close()
        return carried

    # each step's command is in the very next round, and every round was held
    assert _serve(script, eager=eager) == [(1, [False])] * 3


# --- Rw: the hold beside the reader's own pause ---


async def _pair(limit):
    """A served connection whose reader pauses above ``2 * limit`` bytes,
    as ``Rw`` over it, and the client's writer."""
    accepted = asyncio.get_running_loop().create_future()
    server = await asyncio.start_server(lambda r, w: accepted.set_result((r, w)), "127.0.0.1", 0, limit=limit)
    _, writer = await asyncio.open_connection(*server.sockets[0].getsockname())
    reader, served = await asyncio.wait_for(accepted, 5)
    return server, rw.Rw(reader, served), writer


def test_a_transport_the_reader_paused_itself_stays_paused_through_a_hold_and_a_held_one_through_a_read():
    async def go():
        server, conn, writer = await _pair(limit=64)
        transport, reader = conn._writer.transport, conn._reader
        frames = [_frame(1, seq, "k") for seq in range(1, 9)]
        try:
            # the reader's own flow control: more than twice its limit stands in it
            writer.write(b"".join(frames[:4]))
            await _until(lambda: reader._paused, "the reader's own pause")
            assert not transport.is_reading()
            conn.hold_reading()
            conn.release_reading()
            assert reader._paused and not transport.is_reading()  # not the hold's to undo
            # a read during a hold ends the reader's pause and not the hold
            conn.hold_reading()
            got = await asyncio.wait_for(conn.recv_all(), 5)
            assert len(got) == 4 and not reader._paused and not transport.is_reading()
            writer.write(b"".join(frames[4:]))
            await writer.drain()
            await asyncio.sleep(0.05)
            assert not len(reader._buffer)  # what arrives stays in the kernel
            conn.release_reading()
            assert transport.is_reading()
            got += await asyncio.wait_for(conn.recv_all(), 5)
            while len(got) < 8:
                got += await asyncio.wait_for(conn.recv_all(), 5)
            assert [cmd.rifl for cmd in got] == [Rifl(1, seq) for seq in range(1, 9)]
            # a release with no hold, and a hold of a closed connection, are nothing
            conn.release_reading()
            assert transport.is_reading() and conn.fileno() == transport.get_extra_info("socket").fileno()
            conn.close()
            conn.hold_reading()
            conn.release_reading()
        finally:
            writer.close()
            server.close()
            await server.wait_closed()

    asyncio.run(go())


def test_on_read_is_called_once_when_the_next_read_returns_whatever_it_brought():
    async def go():
        server, conn, writer = await _pair(limit=2 ** 16)
        frames = [_frame(1, seq, "k") for seq in (1, 2)]
        calls = []
        try:
            # a part of a frame: the read returns to recv_all, which waits on for the rest
            conn.on_read = lambda: calls.append("part")
            writer.write(frames[0][:5])
            reading = asyncio.ensure_future(conn.recv_all())
            await _until(lambda: calls, "the read of a part of a frame")
            assert calls == ["part"] and conn.on_read is None and not reading.done()
            writer.write(frames[0][5:])
            assert [cmd.rifl for cmd in await asyncio.wait_for(reading, 5)] == [Rifl(1, 1)]
            assert calls == ["part"]  # once
            # whole frames: called before they are handed out, in the same step of the task
            conn.on_read = lambda: calls.append("whole")
            writer.write(frames[1])
            assert [cmd.rifl for cmd in await asyncio.wait_for(conn.recv_all(), 5)] == [Rifl(1, 2)]
            # the end
            conn.on_read = lambda: calls.append("end")
            writer.close()
            assert await asyncio.wait_for(conn.recv_all(), 5) is None
            assert calls == ["part", "whole", "end"] and conn.on_read is None
        finally:
            conn.close()
            writer.close()
            server.close()
            await server.wait_closed()

    asyncio.run(go())


def test_the_hold_is_one_path_that_reads_no_option():
    """Every step is held: nothing of the configuration, the environment or
    the command line, and nothing of what the dispatch carries, decides."""
    import inspect

    step = inspect.getsource(dr.DeviceRuntime._step_on_pool)
    for word in ("config", "environ", "getenv", "batch_size"):
        assert word not in step
    assert "session.rw.hold_reading()" in step and "session.rw.release_reading()" in step
    assert "self._held_dispatches += driver.dispatches - dispatched" in step
    assert inspect.signature(dr.DeviceRuntime._step_on_pool).parameters.keys() == {"self", "round_id", "step", "args"}
