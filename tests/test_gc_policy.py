"""The cyclic collector under the device-step server's schedule
(run/collector.py, ``DeviceRuntime.start`` / ``stop`` / ``_driver_task``):
the start-up heap frozen out of it, no full collection but the driver's
own, those inside their share of wall time, and the process left as it
was found.
"""

import asyncio
import gc
import json
import weakref

import jax
import pytest

# as tests/test_device_runner.py: the device drivers' scan bodies crash
# jaxlib 0.4.x flakily while tracing
if tuple(int(x) for x in jax.__version__.split(".")[:2]) < (0, 5):
    pytest.skip("jax<0.5: device-driver scan tracing segfaults flakily", allow_module_level=True)

from fantoch_tpu.client import ConflictRateKeyGen, Workload
from fantoch_tpu.core import Command, Config, KVOp, Rifl
from fantoch_tpu.run.collector import OUT_OF_REACH, CollectorSchedule
from fantoch_tpu.run.device_runner import DeviceRuntime
from fantoch_tpu.run.harness import run_device_server

NEW_COUNTERS = ("gc_full_scheduled", "gc_full_unscheduled", "gc_collected")


def _runtime(**kw):
    return DeviceRuntime(
        Config(3, 1, shard_count=1), ("127.0.0.1", 0), batch_size=8, key_buckets=64, **kw
    )


def _collector_state():
    """What a runtime may not leave changed.  (A stopped runtime leaves
    nothing frozen: the few hundred objects this interpreter starts with
    in its permanent generation go back to the oldest one with the rest.)"""
    return gc.get_threshold(), gc.isenabled(), list(gc.callbacks)


async def _executed(runtime, n):
    for _ in range(1500):
        if runtime.failure is not None:
            raise runtime.failure
        if runtime.driver.executed >= n:
            return
        await asyncio.sleep(0.01)
    raise AssertionError(f"{runtime.driver.executed} of {n} executed")


def _put(runtime, seq, key, value):
    cmd = Command.from_single(Rifl(9, seq), 0, key, KVOp.put(value))
    runtime.submit(runtime.dot_gen.next_id(), cmd)


# --- start() takes the collector over, stop() hands it back ---


def test_start_freezes_the_heap_and_stop_restores_the_collector():
    found = _collector_state()
    assert found[0][2] < OUT_OF_REACH

    async def go():
        runtime = _runtime()
        assert _collector_state() == found  # a runtime not started holds nothing
        await runtime.start()
        during = gc.get_threshold(), gc.get_freeze_count()
        runtime._publish_tallies()
        frozen = runtime._tallies["gc_frozen_objects"]
        await runtime.stop()
        return during, frozen

    (threshold, frozen_now), frozen = asyncio.run(go())
    assert threshold == (found[0][0], found[0][1], OUT_OF_REACH)
    # jax and the compiled round alone are tens of thousands of objects (a
    # frozen object still goes when its last reference does)
    assert frozen_now > 10_000 and frozen > 10_000
    assert _collector_state() == found and gc.get_freeze_count() == 0


@pytest.mark.parametrize("order", ["in_turn", "nested", "nested_first_out_last"])
def test_runtimes_started_and_stopped_leave_the_process_as_it_was(order):
    found = _collector_state()

    async def go():
        a, b = _runtime(), _runtime()
        await a.start()
        if order == "in_turn":
            await a.stop()
            assert _collector_state() == found and gc.get_freeze_count() == 0
            await b.start()
        else:
            await b.start()
            first, second = (a, b) if order == "nested" else (b, a)
            await first.stop()
            # the other still serves: frozen, no automatic full collection
            assert gc.get_freeze_count() > 0 and gc.get_threshold()[2] == OUT_OF_REACH
            a, b = first, second
        assert gc.get_freeze_count() > 0 and gc.get_threshold()[2] == OUT_OF_REACH
        await b.stop()
        await a.stop()  # a second stop hands nothing back twice

    asyncio.run(go())
    assert _collector_state() == found and gc.get_freeze_count() == 0


def test_a_custom_threshold_found_is_the_one_put_back():
    found = gc.get_threshold()
    gc.set_threshold(900, 12, 14)
    try:
        async def go():
            runtime = _runtime()
            await runtime.start()
            during = gc.get_threshold()
            await runtime.stop()
            return during

        assert asyncio.run(go()) == (900, 12, OUT_OF_REACH)
        assert gc.get_threshold() == (900, 12, 14)
    finally:
        gc.set_threshold(*found)


# --- the schedule, on a clock the test owns ---


class _Clock:
    """Nanoseconds the test moves; a full collection takes ``d`` of them."""

    def __init__(self, d):
        self.now, self.d = 10**9, d

    def __call__(self):
        return self.now

    def hook(self, schedule):
        def on_gc(phase, info):
            if phase == "stop" and info["generation"] == 2:
                self.now += self.d
            schedule.note(phase, info)
        return on_gc


@pytest.mark.parametrize("d_ms", [0.2, 7, 50])
def test_the_schedule_never_spends_more_than_its_share_and_runs_when_due(d_ms):
    d = int(d_ms * 1e6)
    clock = _Clock(d)
    schedule = CollectorSchedule(clock=clock)
    hook = clock.hook(schedule)
    step = 3_000_000  # the driver task looks every 3 ms
    multiple = CollectorSchedule.PAUSE_MULTIPLE
    assert multiple == 64
    assert not schedule.run_if_due()  # it holds nothing yet
    schedule.take_over()
    gc.callbacks.append(hook)
    try:
        began, ran = clock.now, []
        while clock.now - began < 40 * (multiple + 1) * d:
            clock.now += step
            at = clock.now
            if schedule.run_if_due():
                assert clock.now == at + d
                ran.append(at)
    finally:
        gc.callbacks.remove(hook)
        schedule.hand_back()
    elapsed = clock.now - began
    # never more than its share: d is followed by none for 64 d
    gaps = [b - a for a, b in zip(ran, ran[1:])]
    assert min(gaps) >= (multiple + 1) * d
    assert len(ran) * d <= elapsed / (multiple + 1) + d
    # and it does run when due: at the first look after the pause
    assert max(gaps) < (multiple + 1) * d + step
    assert schedule.scheduled == len(ran) >= elapsed // ((multiple + 1) * d + step) >= 30
    assert schedule.unscheduled == 0


def test_a_collection_nobody_asked_for_is_counted_and_spends_the_budget_too():
    clock = _Clock(5_000_000)
    schedule = CollectorSchedule(clock=clock)
    hook = clock.hook(schedule)
    schedule.take_over()
    gc.callbacks.append(hook)
    try:
        gc.collect(1)  # a young collection is nobody's business
        assert schedule.counters()["gc_full_unscheduled"] == 0
        gc.collect()
        assert schedule.counters()["gc_full_unscheduled"] == 1
        clock.now += 64 * 5_000_000 - 1
        assert not schedule.run_if_due()
        clock.now += 1
        assert schedule.run_if_due()
    finally:
        gc.callbacks.remove(hook)
        schedule.hand_back()
    assert schedule.counters()["gc_full_scheduled"] == 1
    assert schedule.counters()["gc_frozen_objects"] == 0  # handed back


# --- through a served runtime ---


class _Probe:
    pass


def test_a_cycle_made_on_the_served_path_goes_with_the_next_scheduled_collection():
    async def go():
        runtime = _runtime()
        await runtime.start()
        schedule = runtime._collector
        schedule._not_before = 2**62  # none is due for now
        probe = _Probe()
        probe.me = probe
        alive = weakref.ref(probe)
        _put(runtime, 1, "k", probe)
        del probe
        await _executed(runtime, 1)
        gc.collect(1)  # the store holds it: promoted into the oldest generation
        # overwritten: the store lets go of it, the reply that returns it
        # as the previous value has nobody to go to
        _put(runtime, 2, "k", "x")
        _put(runtime, 3, "other", "y")
        await _executed(runtime, 3)
        for seq in range(4, 8):  # rounds that leave the last results behind
            _put(runtime, seq, "other", "y")
            await _executed(runtime, seq)
        gc.collect(1)
        held = alive() is not None, schedule.scheduled
        schedule._not_before = 0  # now one is due
        for seq in range(8, 40):
            _put(runtime, seq, "other", "y")
            await _executed(runtime, seq)
            if alive() is None:
                break
        runtime._publish_tallies()
        tallies = dict(runtime._tallies)
        await runtime.stop()
        return held, alive() is None, tallies

    held, gone, tallies = asyncio.run(go())
    assert held == (True, 0)  # young collections do not reach it
    assert gone
    assert tallies["gc_full_scheduled"] >= 1 and tallies["gc_full_unscheduled"] == 0
    assert tallies["gc_collected"] >= 1
    assert tallies["stage_gc_n"] == tallies["gc_full_scheduled"]


@pytest.mark.parametrize("protocol", ["epaxos", "newt"])
def test_no_full_collection_but_the_drivers_own_over_a_few_thousand_commands(protocol):
    commands = 400
    runtime, clients = asyncio.run(
        run_device_server(
            Config(3, 1, shard_count=1, serving_pipeline_depth=1),  # overlap, on the CPU too
            Workload(
                shard_count=1, key_gen=ConflictRateKeyGen(50), keys_per_command=1,
                commands_per_client=commands, payload_size=100,
            ),
            client_count=8, protocol=protocol, batch_size=64, pending_capacity=64,
        )
    )
    t = runtime._tallies  # the last round's
    assert t["executed"] == t["replied"] == 8 * commands
    assert t["gc_full_unscheduled"] == 0 and t["gc_full_scheduled"] == t["stage_gc_n"] >= 2
    t = {**runtime._collector.counters(), **runtime.stages.counters()}  # the stopped runtime's
    assert t["gc_full_unscheduled"] == 0 and t["gc_full_scheduled"] == t["stage_gc_n"]
    # the budget, on the real clock: full collections took at most their share
    # of the time between the first one's start and the last one's end
    spans = [(t0, t1) for name, t0, t1, *_ in runtime.stages.ring if name == "gc"]
    assert len(spans) == t["stage_gc_n"]
    for (_, ended), (started, _) in zip(spans, spans[1:]):
        assert started >= ended
    for (t0, t1), (started, _) in zip(spans, spans[1:]):
        assert started - t1 >= CollectorSchedule.PAUSE_MULTIPLE * (t1 - t0)
    assert gc.get_freeze_count() == 0


def test_the_counters_are_in_the_sample_and_in_the_snapshot(tmp_path):
    async def go():
        runtime = _runtime(metrics_file=str(tmp_path / "snap.json"))
        runtime._write_metrics_snapshot()
        with open(tmp_path / "snap.json") as fh:
            first = json.load(fh)
        await runtime.start()
        _put(runtime, 1, "k", "v")
        await _executed(runtime, 1)
        runtime._publish_tallies()
        sample = runtime.telemetry_sample()
        await runtime.stop()
        with open(tmp_path / "snap.json") as fh:
            return first, sample, json.load(fh)

    first, (counters, gauges, _hists), last = asyncio.run(go())
    for name in NEW_COUNTERS:
        assert first[name] == 0 and name in counters and last[name] >= first[name], name
    assert first["gc_frozen_objects"] == 0
    assert gauges["gc_frozen_objects"] == last["gc_frozen_objects"] > 10_000
    assert "gc_frozen_objects" not in counters
    assert last["gc_full_scheduled"] >= 1 and last["stage_gc_n"] >= 1
