"""When a round is left in flight (PR 58): ``DeviceRuntime._driver_task``
turns ``PipelineCore.serve``'s overlap on by what a dispatch carries (a
round in flight already, or at least ``OVERLAP_MIN_FILL`` of one round's
rows), a round in flight is retired at the next dispatch or by the
quiet-ring retire, and ``_DriverCore._enqueue`` starts its copy back.

The runtime's cases run a started ``DeviceRuntime`` on the CPU behind real
TCP connections with ``serving_pipeline_depth=1`` (the opt-in to the overlap
where the device is the host), its steps let through one at a time
(tests/test_read_hold.py ``_Held``), so what each dispatch carries and what
is in flight when it is made are exact.
"""

import asyncio

import jax
import pytest

# as tests/test_device_runner.py: the device drivers' scan bodies crash
# jaxlib 0.4.x flakily while tracing
if tuple(int(x) for x in jax.__version__.split(".")[:2]) < (0, 5):
    pytest.skip("jax<0.5: device-driver scan tracing segfaults flakily", allow_module_level=True)

from fantoch_tpu.core import Command, KVOp, Rifl
from fantoch_tpu.run import device_runner as dr
from fantoch_tpu.run import rw
from fantoch_tpu.run.prelude import Submit
from tests.test_device_runner import DRAIN_DRIVERS, _put
from tests.test_read_hold import BATCH, _Client, _Held, _until

PROTOCOLS = list(DRAIN_DRIVERS)
HALF = int(dr.OVERLAP_MIN_FILL * BATCH)  # the rows from which a dispatch is left in flight
KEYS = 4  # every group writes them all


class _Group(_Client):
    """One connection of ``HALF`` closed-loop clients: a cycle's commands
    go out in one write, a command a client, each a put on one of ``KEYS``
    keys (a put returns the value before it, so the replies show the order
    of execution on a key)."""

    @classmethod
    async def connect(cls, addr, group, clients=HALF):
        ids = [1 + group * HALF + i for i in range(clients)]
        self = await super().connect(addr, *ids)
        self.ids = ids
        return self

    async def send(self, cycle, rows=None):
        self.writer.write(b"".join(
            rw.frame(Submit(Command.from_single(
                Rifl(client, cycle), 0, f"k{client % KEYS}", KVOp.put(f"{client}.{cycle}"))))
            for client in self.ids[:rows]))
        await self.writer.drain()
        await asyncio.sleep(0.02)  # the bytes are in the kernel's buffer


def _serve(script, protocol, overlap=True):
    depth = {"serving_pipeline_depth": 1} if overlap else {}

    async def go():
        # a dispatch is one round: what it carries is what the test wrote
        async with _Held(protocol, serving_chain_max=1, **depth) as held:
            return await script(held)

    return asyncio.run(go())


def _results(frames):
    return [rw.deserialize(frame).cmd_result for frame in frames]


GROUPS, CYCLES = 3, 3


async def _closed_loop(held):
    """``GROUPS`` connections take turns, a dispatch each: the commands of
    the next dispatch arrive while this one's step stands, and a client's
    next command follows the reply to its last (under the overlap that is
    the reply its group read two steps back)."""
    runtime = held.runtime
    groups = [await _Group.connect(held.addr, g) for g in range(GROUPS)]
    await _until(lambda: len(runtime._sessions) == GROUPS, "the sessions live")
    turns = [(cycle, g) for cycle in range(1, CYCLES + 1) for g in range(GROUPS)]
    got = [[] for _ in groups]
    await groups[0].send(1)
    for turn, following in zip(turns, turns[1:] + [None]):
        await held.in_step()
        assert held.seen[-1][0] == HALF, (turn, held.seen)
        if following is not None:
            cycle, g = following
            if cycle > 1:
                got[g] += await groups[g].replies(HALF)
            await groups[g].send(cycle)
        await held.step()
    # the clients stop: what is in flight is retired all the same
    for g, group in enumerate(groups):
        got[g] += await group.replies(CYCLES * HALF - len(got[g]))
    await _until(lambda: not runtime.driver.has_outstanding, "nothing in flight")
    tallies = held.tallies()
    for group in groups:
        await group.close()
    return got, tallies, len(held.seen)


@pytest.mark.parametrize("protocol", PROTOCOLS)
def test_a_closed_loop_of_half_rounds_is_overlapped_and_answered_as_without_overlap(protocol):
    got, tallies, dispatched = _serve(_closed_loop, protocol)
    rounds = GROUPS * CYCLES
    assert dispatched == tallies["device_dispatches"] == rounds
    # every dispatch carried half a round, and every one but the first
    # was made over the one before it
    assert tallies["device_overlapped_dispatches"] == rounds
    assert tallies["device_pipelined_rounds"] == rounds - 1
    assert tallies["device_transfers"] == 2 * rounds
    assert tallies["executed"] == tallies["replied"] == rounds * HALF
    # every command answered once, a session's in its clients' order
    results = [_results(frames) for frames in got]
    for g, mine in enumerate(results):
        by_client = {}
        for result in mine:
            by_client.setdefault(result.rifl.source, []).append(result.rifl.sequence)
        assert by_client == {1 + g * HALF + i: list(range(1, CYCLES + 1)) for i in range(HALF)}
    # ... and in its key's order: the values the puts returned chain every
    # write of a key, from nothing to the last, each once
    written = {}
    for result in (r for mine in results for r in mine):
        ((key, (before,)),) = result.results.items()
        assert key == f"k{result.rifl.source % KEYS}" and before not in written.setdefault(key, {})
        written[key][before] = f"{result.rifl.source}.{result.rifl.sequence}"
    for key, after in written.items():
        value, chain = None, 0
        while value in after:
            value, chain = after[value], chain + 1
        assert chain == len(after) == rounds * HALF // KEYS, key
    # the same bytes to every session as from the server that fetches
    # each round where it dispatched it
    plain, plain_tallies, _ = _serve(_closed_loop, protocol, overlap=False)
    assert plain == got
    assert plain_tallies["device_overlapped_dispatches"] == 0
    assert plain_tallies["device_pipelined_rounds"] == 0
    assert plain_tallies["device_dispatches"] == rounds


@pytest.mark.parametrize("protocol", PROTOCOLS)
@pytest.mark.parametrize("rows, deferred", [(1, 0), (HALF - 1, 0), (HALF, 1), (BATCH, 1)])
def test_a_lone_dispatch_is_fetched_at_once_under_half_a_round_and_by_the_quiet_ring_from_it(
        protocol, rows, deferred):
    """One dispatch and then silence: under half a round it is answered by
    the round that carried it (a lone closed-loop command, a part-full
    round); from half a round on it is left in flight, and the driver
    task's next turn, finding the ring quiet, retires it: its replies
    strand in neither case."""

    async def script(held):
        runtime = held.runtime
        group = await _Group.connect(held.addr, 0, clients=rows)
        await _until(lambda: len(runtime._sessions) == 1, "the session live")
        await group.send(1)
        await held.in_step()
        await held.step()
        replies = _results(await group.replies(rows))
        await _until(lambda: not runtime.driver.has_outstanding, "nothing in flight")
        await asyncio.sleep(0.05)
        out = replies, held.tallies(), [seen[0] for seen in held.seen]
        await group.close()
        return out

    replies, tallies, seen = _serve(script, protocol)
    assert seen == [rows]  # one dispatch; the retire is no dispatch
    assert sorted(r.rifl for r in replies) == sorted(
        Rifl(client, 1) for client in range(1, rows + 1))
    assert tallies["replied"] == tallies["executed"] == rows
    assert tallies["device_dispatches"] == 1 and tallies["device_transfers"] == 2
    assert tallies["device_overlapped_dispatches"] == deferred
    assert tallies["device_pipelined_rounds"] == 0
    # the round that carried the commands answered them, or a second one
    # that dispatched nothing did
    assert tallies["stage_round_n"] == 1 + deferred and tallies["stage_fetch_n"] == 1


@pytest.mark.parametrize("protocol", PROTOCOLS)
def test_part_full_rounds_one_after_the_other_are_never_left_in_flight(protocol):
    async def script(held):
        runtime = held.runtime
        group = await _Group.connect(held.addr, 0)
        await _until(lambda: len(runtime._sessions) == 1, "the session live")
        counted = []
        for cycle in (1, 2, 3):
            await group.send(cycle, HALF - 1)
            await held.in_step()
            await held.step()
            # answered by the round that carried them
            counted.append((held.tallies()["replied"], runtime.driver.has_outstanding))
            await group.replies(HALF - 1)
        out = counted, held.tallies()
        await group.close()
        return out

    counted, tallies = _serve(script, protocol)
    assert counted == [(n * (HALF - 1), False) for n in (1, 2, 3)]
    assert tallies["device_dispatches"] == 3
    assert tallies["device_overlapped_dispatches"] == tallies["device_pipelined_rounds"] == 0


def test_a_straggler_behind_a_round_in_flight_is_dispatched_over_it():
    """With a round in flight the path stays on whatever the next dispatch
    carries: the straggler's dispatch retires the round before it, in
    order, and is itself retired by the quiet ring."""

    async def script(held):
        runtime = held.runtime
        group, late = await _Group.connect(held.addr, 0), await _Group.connect(held.addr, 1)
        await _until(lambda: len(runtime._sessions) == 2, "the sessions live")
        await group.send(1)
        await held.in_step()
        await late.send(1, 1)  # while the half round's step stands
        await held.step()
        await held.in_step()
        assert held.tallies()["replied"] == 0  # the half round is in flight
        await held.step()
        first = _results(await group.replies(HALF))
        assert held.tallies()["replied"] >= HALF
        second = _results(await late.replies(1))
        await _until(lambda: not runtime.driver.has_outstanding, "nothing in flight")
        out = first, second, held.tallies(), [seen[0] for seen in held.seen]
        for conn in (group, late):
            await conn.close()
        return out

    first, second, tallies, seen = _serve(script, "epaxos")
    assert seen == [HALF, 1]
    assert [r.rifl for r in first] == [Rifl(c, 1) for c in range(1, HALF + 1)]
    assert [r.rifl for r in second] == [Rifl(HALF + 1, 1)]
    # the straggler wrote k1 after the half round's two writers of it
    assert second[0].results == {f"k{(HALF + 1) % KEYS}": (f"{HALF + 1 - KEYS}.1",)}
    assert tallies["device_overlapped_dispatches"] == 2 and tallies["device_pipelined_rounds"] == 1
    assert tallies["replied"] == HALF + 1


# --- the copy back a dispatch starts (run/device_drivers.py _DriverCore._enqueue) ---


@pytest.mark.parametrize("protocol", PROTOCOLS)
def test_a_dispatch_starts_its_one_copy_back_and_crosses_twice(protocol, monkeypatch):
    """A dispatch left in flight has started the copy of its one packed
    output to the host, and no other, before anyone asks for it; the fetch
    that retires it is the second crossing, as before."""
    from jax._src.array import ArrayImpl

    cls, _walk, n, extra = DRAIN_DRIVERS[protocol]
    driver = cls(n, batch_size=8, key_buckets=64, **extra)
    driver.step([_put(1, 1, "warm", "v")])  # the program is ready
    started = []
    copy_to_host_async = ArrayImpl.copy_to_host_async

    def spy(array):
        started.append(array)
        return copy_to_host_async(array)

    monkeypatch.setattr(ArrayImpl, "copy_to_host_async", spy)
    before = driver.transfers
    assert driver.serve([[_put(1, s, "hot", f"v{s}") for s in (2, 3, 4)]], overlap=True) == []
    ((_round, token),) = driver._inflight
    (copied,) = started
    assert copied is driver._token_outputs(token).packed
    assert driver.transfers == before + 1  # the columns went up; nothing was fetched
    monkeypatch.undo()
    assert len(driver.flush_pipeline()) == 3
    assert driver.transfers == before + 2 and driver.overlapped_dispatches == 1
