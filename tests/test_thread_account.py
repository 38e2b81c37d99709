"""Who had the CPU (observability/device.py ``ThreadAccount``,
``classify_stall``, ``StageRecorder.stall`` / ``settle_stalls`` / ``dump``,
and what ``DeviceRuntime`` publishes of them): the served path's two
threads read as on a CPU or not, a kernel without ``schedstat``, every
counter in the first snapshot, the late wake-ups of the loop by class, and
``round_spans.json`` with its ``stalls`` beside what it had.
"""

import asyncio
import json
import os
import threading
import time

import jax
import pytest

# as tests/test_device_runner.py: the device drivers' scan bodies crash
# jaxlib 0.4.x flakily while tracing
if tuple(int(x) for x in jax.__version__.split(".")[:2]) < (0, 5):
    pytest.skip("jax<0.5: device-driver scan tracing segfaults flakily", allow_module_level=True)

from fantoch_tpu.core import Config
from fantoch_tpu.observability import device as obs
from fantoch_tpu.observability.device import (
    COMPUTE_STAGES,
    CPU_STAGES,
    STALL_CLASSES,
    STALLS_KEPT,
    AccountSample,
    StageRecorder,
    ThreadAccount,
    classify_stall,
)

MS = 1_000_000
RUNQ_KEYS = ("thread_loop_runq_ms", "thread_step_runq_ms", "host_runq_ms")
ACCOUNT_KEYS = (
    "thread_loop_cpu_ms", "thread_step_cpu_ms", "host_cpu_ms", "proc_cpu_ms",
    "proc_minflt", "proc_majflt", "proc_nivcsw",
)


def _spent(loop_cpu=0, step_cpu=0, loop_runq=0, step_runq=0, proc_cpu=0):
    return AccountSample(loop_cpu, step_cpu, loop_runq, step_runq, proc_cpu, 0, 0, 0)


# --- the account ---


def _two_step_threads(account, cpu_ms=120):
    """One thread that spins until it has burnt ``cpu_ms`` of CPU and one
    that sleeps as long, both registered as a step's; returns them parked
    (alive, so their clocks can be read) and the event that lets them go."""
    done = [threading.Event(), threading.Event()]
    release = threading.Event()

    def spin():
        account.register("step")
        until = time.thread_time_ns() + cpu_ms * MS
        while time.thread_time_ns() < until:
            pass
        done[0].set()
        release.wait(10)

    def sleep():
        account.register("step")
        time.sleep(cpu_ms / 1000)
        done[1].set()
        release.wait(10)

    threads = [threading.Thread(target=spin), threading.Thread(target=sleep)]
    for thread in threads:
        thread.start()
    assert all(event.wait(10) for event in done)
    return threads, release


def test_a_spinning_thread_reads_as_cpu_a_sleeping_one_does_not_and_the_pool_is_their_sum():
    account = ThreadAccount()
    before = account.sample()
    threads, release = _two_step_threads(account)
    try:
        after = account.sample()
        spinner, sleeper = (thread[2] for thread in account._threads["step"])
        if spinner < sleeper:
            spinner, sleeper = sleeper, spinner
        # within a factor of two of the 120 ms burnt, and of the nothing slept
        assert 120 * MS <= spinner <= 240 * MS
        assert sleeper <= 60 * MS
        assert after.step_cpu_ns == spinner + sleeper
        # this thread waited on two events meanwhile
        assert after.loop_cpu_ns - before.loop_cpu_ns <= 60 * MS
        # the process's clock saw the spinner too
        assert after.proc_cpu_ns - before.proc_cpu_ns >= 60 * MS
        assert all(b >= a for a, b in zip(before, after))
        counters = account.counters()
        assert set(ACCOUNT_KEYS) <= set(counters)
        assert counters["host_cpu_ms"] == pytest.approx(
            counters["thread_loop_cpu_ms"] + counters["thread_step_cpu_ms"], abs=0.01
        )
        if account.has_runq:
            assert counters["host_runq_ms"] == pytest.approx(
                counters["thread_loop_runq_ms"] + counters["thread_step_runq_ms"], abs=0.01
            )
    finally:
        release.set()
        for thread in threads:
            thread.join(10)
    assert not any(thread.is_alive() for thread in threads)
    # threads that have ended keep their last reading (the kernel's thread may
    # outlive the join by a moment and be read once more); closing twice is free
    ended = account.sample()
    assert after.step_cpu_ns <= ended.step_cpu_ns <= after.step_cpu_ns + 50 * MS
    account.close()
    account.close()
    assert account.sample().step_runq_ns == ended.step_runq_ns


def test_registering_a_thread_twice_counts_it_once():
    account = ThreadAccount()
    account.register("loop")
    account.register("step")  # this thread is the loop's already
    assert [len(account._threads[role]) for role in ("loop", "step")] == [1, 0]
    assert account.sample().step_cpu_ns == 0
    account.close()


@pytest.mark.parametrize("schedstat", ["missing", "unreadable", "not_three_numbers"])
def test_a_kernel_without_schedstat_leaves_the_run_queue_out_and_raises_nothing(
    schedstat, tmp_path, monkeypatch
):
    if schedstat == "missing":
        template = str(tmp_path / "none" / "%d")
    else:
        template = str(tmp_path / "%d")
        path = template % threading.get_native_id()
        if schedstat == "unreadable":
            os.mkdir(path)  # opens, and every read of it fails
        else:
            with open(path, "w") as fh:
                fh.write("off\n")
    monkeypatch.setattr(ThreadAccount, "SCHEDSTAT", template)
    account = ThreadAccount()
    counters = account.counters()
    assert not account.has_runq
    assert not set(RUNQ_KEYS) & set(counters)
    assert set(ACCOUNT_KEYS) <= set(counters) and counters["thread_loop_cpu_ms"] > 0
    sample = account.sample()
    assert sample.loop_runq_ns == sample.step_runq_ns == 0
    # a late wake-up is then never the machine's
    assert classify_stall(100 * MS, AccountSample(*(b - a for a, b in zip(sample, account.sample())))) in (
        "busy", "blocked"
    )
    account.close()


# --- the classes of a stall ---


@pytest.mark.parametrize("late_ms,spent,expected", [
    # the four classes
    (100, _spent(loop_cpu=90 * MS, step_cpu=10 * MS), "busy"),
    (100, _spent(loop_cpu=10 * MS, step_cpu=85 * MS), "gil"),
    (100, _spent(loop_cpu=5 * MS, step_cpu=5 * MS, loop_runq=60 * MS, step_runq=30 * MS), "runq"),
    (100, _spent(loop_cpu=5 * MS, step_cpu=5 * MS, loop_runq=1 * MS, proc_cpu=95 * MS), "blocked"),
    (300, _spent(), "blocked"),
    # the first match wins
    (100, _spent(loop_cpu=60 * MS, step_cpu=60 * MS, loop_runq=60 * MS), "busy"),
    (100, _spent(loop_cpu=40 * MS, step_cpu=60 * MS, loop_runq=60 * MS), "gil"),
    # exactly half holds, a nanosecond under it does not
    (100, _spent(loop_cpu=50 * MS), "busy"),
    (100, _spent(loop_cpu=50 * MS - 1, step_cpu=1), "gil"),
    (100, _spent(loop_cpu=25 * MS - 1, step_cpu=25 * MS, loop_runq=25 * MS, step_runq=25 * MS), "runq"),
    (100, _spent(loop_cpu=25 * MS - 1, step_cpu=25 * MS, loop_runq=25 * MS, step_runq=25 * MS - 1),
     "blocked"),
    # two threads that hand the lock back and forth stay under the half each: together they count
    # (on the chip: 236 ms late with 100 ms of the loop's CPU and 60 of the step's)
    (236, _spent(loop_cpu=100 * MS, step_cpu=60 * MS, proc_cpu=230 * MS), "gil"),
    (100, _spent(loop_cpu=30 * MS, step_cpu=30 * MS), "gil"),
    (100, _spent(loop_cpu=20 * MS, step_cpu=20 * MS, loop_runq=51 * MS), "runq"),
    # a thread outside the served path ran: neither served thread did
    (78, _spent(loop_cpu=20 * MS, proc_cpu=120 * MS), "blocked"),
])
def test_a_late_wake_up_is_classed_by_what_its_interval_cost(late_ms, spent, expected):
    assert expected in STALL_CLASSES
    assert classify_stall(late_ms * MS, spent) == expected
    rec = StageRecorder(ring=8)
    assert rec.stall(1000 * MS, (1000 + late_ms) * MS, spent) == expected
    counters = rec.counters()
    by_class = {kind: counters[f"loop_stall_{kind}_ms"] for kind in STALL_CLASSES}
    assert by_class == {kind: (late_ms if kind == expected else 0) for kind in STALL_CLASSES}
    assert counters["stage_loop_stall_ms"] == late_ms and counters["stage_loop_stall_n"] == 1
    assert counters["loop_stopped_ms"] == (late_ms if expected in ("runq", "blocked") else 0)
    assert rec.ring[-1][:3] == ("loop_stall", 1000 * MS, (1000 + late_ms) * MS)


@pytest.mark.parametrize("late_ms,spent,kept", [
    (49.999999, _spent(), False),  # under 50 ms: counted, not kept
    (50, _spent(), True),
    (50, _spent(step_cpu=40 * MS), True),  # the other thread's turn (`gil`) is kept too
    (5000, _spent(loop_cpu=4000 * MS), False),  # the loop's own work never is
])
def test_a_stall_is_kept_from_50_ms_on_unless_it_was_the_loops_own_work(late_ms, spent, kept, tmp_path):
    rec = StageRecorder(ring=64)
    with rec.span("collect", 7):
        pass
    t0 = rec.ring[-1][2] + 10 * MS  # due 10 ms after that span closed
    t1 = t0 + int(late_ms * MS)
    rec.record("step", t0 - 5 * MS, t1 + 2 * MS, 7, "round")  # open across the stall
    rec.record("fetch", t1 + 3 * MS, t1 + 4 * MS, 8, "step")  # after it
    kind = rec.stall(t0, t1, spent)
    rec.dump(str(tmp_path / "round_spans.json"))
    with open(tmp_path / "round_spans.json") as fh:
        stalls = json.load(fh)["stalls"]
    assert len(stalls) == (1 if kept else 0)
    if kept:
        (stall,) = stalls
        assert (stall["t0_ns"], stall["t1_ns"], stall["class"]) == (t0, t1, kind)
        assert stall["spent"] == spent._asdict() and len(stall["spent"]) == 8
        # the window: what closed in the 100 ms before it, what was open across it
        # (filed after the stall itself), and the stall's own entry; nothing from after
        assert [row[0] for row in stall["spans"]] == ["collect", "step", "loop_stall"]


def test_of_the_kept_stalls_the_64_longest_stay_and_the_dump_has_them_in_order():
    rec = StageRecorder(ring=16)
    at = 10_000 * MS
    lates = [50 + (i * 37) % 101 for i in range(100)]  # 100 of 50..150 ms, shuffled
    for late in lates:
        rec.stall(at, at + late * MS, _spent(loop_runq=late * MS))
        at += 1000 * MS
    # they wait for the spans open across them to close: none is filed before its time
    rec.settle_stalls(lates[0] * MS)
    assert not rec._stalls and len(rec._unsettled) == 100
    rec.settle_stalls(10_000 * MS + lates[0] * MS + obs.STALL_SETTLE_NS)
    assert len(rec._stalls) == 1 and len(rec._unsettled) == 99
    rec.settle_stalls()
    assert len(rec._stalls) == STALLS_KEPT == 64 and not rec._unsettled
    kept = sorted(late for late, _, _ in rec._stalls)
    assert kept == [late * MS for late in sorted(lates)[-64:]]
    assert rec.counters()["loop_stall_runq_ms"] == sum(lates)  # every one is counted


def test_wait_is_wall_minus_cpu_of_the_stages_that_only_compute():
    rec = StageRecorder(ring=8)
    assert set(COMPUTE_STAGES) < set(CPU_STAGES) and rec.wait_ns() == 0
    wall = {"assemble": 900, "execute": 800, "collect": 70, "deliver": 600, "publish": 50,
            "enqueue": 4000, "fetch": 3000, "step": 9000, "round": 12000}
    cpu = {"assemble": 500, "execute": 650, "collect": 60, "deliver": 450, "publish": 50,
           "enqueue": 700, "fetch": 100, "step": 4000}
    for name, value in wall.items():
        rec.ns[name] = value * MS
    for name, value in cpu.items():
        rec.cpu_ns[name], rec.timed_ns[name] = value * MS, wall[name] * MS  # every span took its pair
    # enqueue, fetch and the step as a whole wait by design: they are not in it
    assert rec.wait_ns() == (400 + 150 + 10 + 150 + 0) * MS
    # where one span in five took its pair, their share is laid over the whole
    rec.timed_ns["assemble"], rec.cpu_ns["assemble"] = 180 * MS, 100 * MS
    assert rec.wait_ns() == pytest.approx((400 + 150 + 10 + 150 + 0) * MS)
    rec.timed_ns["deliver"], rec.cpu_ns["deliver"] = 0, 0  # none yet: nothing is claimed
    assert rec.wait_ns() == pytest.approx((400 + 150 + 10) * MS)
    counters = rec.counters()
    for suffix in ("_cpu_ms", "_timed_ms"):
        assert set(CPU_STAGES) == {
            key[len("stage_"):-len(suffix)] for key in counters if key.endswith(suffix)
        }
    assert counters["stage_enqueue_cpu_ms"] == 700 and counters["stage_enqueue_ms"] == 4000
    assert counters["stage_enqueue_timed_ms"] == 4000 and counters["stage_assemble_timed_ms"] == 180


def test_a_span_of_a_cpu_stage_sums_its_threads_cpu_time_inside_its_wall_time():
    rec = StageRecorder(ring=8)
    with rec.span("execute", 1):
        until = time.thread_time_ns() + 30 * MS
        while time.thread_time_ns() < until:
            pass
    with rec.span("deliver", 1):
        time.sleep(0.05)
    with rec.span("gate_wait", 1):  # not a stage of CPU_STAGES: no reading
        pass
    assert 30 * MS <= rec.cpu_ns["execute"] <= rec.ns["execute"] == rec.timed_ns["execute"]
    assert rec.cpu_ns["deliver"] <= 25 * MS and rec.ns["deliver"] >= 50 * MS
    assert "gate_wait" not in rec.cpu_ns
    assert rec.wait_ns() >= 25 * MS


def test_a_stage_takes_its_cpu_pair_at_most_once_in_the_spacing():
    """Where the kernel serves the CPU clock itself a pair is two system
    calls: an open round of 10 ms takes them for one round in five, a
    saturated round (longer than the spacing) for every one."""
    rec = StageRecorder(ring=8)
    for _ in range(3):  # back to back: the first takes the pair
        with rec.span("collect", 1):
            pass
    first = rec.timed_ns["collect"]
    assert 0 < first < rec.ns["collect"] and rec.n["collect"] == 3
    rec.cpu_due["collect"] = 0  # the spacing has passed
    with rec.span("collect", 2):
        pass
    assert rec.timed_ns["collect"] > first
    assert rec.cpu_due["collect"] == rec.ring[-1][1] + obs.CPU_PAIR_EVERY_NS
    assert rec.cpu_due["deliver"] == 0  # each stage has its own


# --- what the runtime publishes ---


def _runtime(**kw):
    from fantoch_tpu.run.device_runner import DeviceRuntime

    return DeviceRuntime(
        Config(3, 1, shard_count=1), ("127.0.0.1", 0), batch_size=8, key_buckets=64, **kw
    )


def test_every_counter_of_the_account_is_in_a_runtimes_first_snapshot():
    runtime = _runtime()
    first = runtime._tallies
    expected = (
        *ACCOUNT_KEYS,
        *(f"stage_{name}_cpu_ms" for name in CPU_STAGES),
        *(f"stage_{name}_timed_ms" for name in CPU_STAGES),
        "session_decode_cpu_ms", "session_decode_timed_ms",
        "session_admit_cpu_ms", "session_admit_timed_ms", "stage_wait_ms",
        *(f"loop_stall_{kind}_ms" for kind in STALL_CLASSES), "loop_stopped_ms",
    )
    assert [key for key in expected if key not in first] == []
    if runtime.account.has_runq:
        assert [key for key in RUNQ_KEYS if key not in first] == []
    assert all(isinstance(first[key], (int, float)) for key in expected)
    # the start-up's account: this thread has imported jax and built the driver
    assert first["thread_loop_cpu_ms"] > 0 and first["thread_step_cpu_ms"] == 0
    assert first["proc_cpu_ms"] >= first["host_cpu_ms"] * 0.5
    # all of them monotone counters for the series and /metrics: none is a gauge
    counters, gauges, _ = runtime.telemetry_sample()
    assert [key for key in expected if key not in counters] == []
    assert not set(gauges) & set(expected) and not set(gauges) & set(RUNQ_KEYS)
    runtime.account.close()


def test_stage_wait_ms_adds_the_session_planes_two_counters_to_the_stages():
    runtime = _runtime()
    stages = runtime.stages
    for name, wall, cpu in (("assemble", 700, 450), ("deliver", 300, 280),
                            ("enqueue", 5000, 900)):  # enqueue is not a computing stage
        stages.ns[name], stages.timed_ns[name], stages.cpu_ns[name] = wall * MS, wall * MS, cpu * MS
    tally = runtime._decode_tally
    tally[0], tally[3], tally[4] = 400 * MS, 390 * MS + 500_000, 400 * MS
    # one admit pass in ten took its CPU pair: 35 ms of wall, 31 on the CPU
    session = runtime.session_tallies
    session.admit_ns, session.admit_timed_ns, session.admit_cpu_ns = 350 * MS, 35 * MS, 31 * MS
    runtime._publish_tallies()
    t = runtime._tallies
    assert t["session_decode_cpu_ms"] == 390.5 and t["session_decode_timed_ms"] == 400.0
    assert t["session_admit_cpu_ms"] == 31.0 and t["session_admit_timed_ms"] == 35.0
    assert t["stage_wait_ms"] == pytest.approx(250 + 20 + 9.5 + 40)
    runtime.account.close()


def test_the_probe_classes_a_stop_of_the_loop_and_the_dump_keeps_its_window(tmp_path):
    """The loop's thread sleeps for 80 ms with nothing else running: neither
    served thread was on a CPU, so the late wake-up is ``blocked`` (or, on a
    machine that took the core away meanwhile, ``runq``), it is ``stopped``
    time, the four classes sum to ``loop_stall_ms``, and ``round_spans.json``
    carries it under ``stalls`` beside the keys it had."""
    from benchmark import run as bench_run

    async def go():
        runtime = _runtime(metrics_file=str(tmp_path / "snap.json"))
        await runtime.start()
        await asyncio.sleep(0.05)  # the probe is asleep in its 10 ms
        time.sleep(0.08)           # the loop does not run for 80 ms, and burns nothing
        await asyncio.sleep(0.05)
        t0 = time.monotonic()
        await runtime.stop()
        return runtime, t0

    runtime, t0 = asyncio.run(go())
    t = runtime._tallies
    by_class = {kind: t[f"loop_stall_{kind}_ms"] for kind in STALL_CLASSES}
    assert t["loop_stalls"] >= 1 and t["loop_stall_ms"] >= 60.0
    assert sum(by_class.values()) == pytest.approx(t["loop_stall_ms"], abs=0.01)
    assert t["loop_stopped_ms"] == pytest.approx(by_class["runq"] + by_class["blocked"], abs=0.002)
    assert t["loop_stopped_ms"] >= 60.0
    with open(tmp_path / "round_spans.json") as fh:
        ring = json.load(fh)
    assert list(ring) == ["clock", "columns", "spans", "stalls"]
    assert ring["clock"] == "monotonic_ns"
    assert ring["columns"] == ["name", "t0_ns", "t1_ns", "round", "thread", "parent", "read_rows"]
    # (a loaded machine may add a stop of its own: the sleep's is the one with the idle loop)
    stalls = sorted((stall for stall in ring["stalls"] if stall["t1_ns"] - stall["t0_ns"] >= 60 * MS),
                    key=lambda stall: stall["spent"]["loop_cpu_ns"])
    assert stalls and stalls[0]["class"] in ("runq", "blocked")
    assert set(stalls[0]["spent"]) == set(AccountSample._fields)
    assert stalls[0]["spent"]["loop_cpu_ns"] < 40 * MS
    entry = [row for row in ring["spans"] if row[0] == "loop_stall" and row[1] == stalls[0]["t0_ns"]]
    assert entry and entry[0] in stalls[0]["spans"]
    # the reader of the ring reads what it read: the same value from the dump
    # as it is and from one without `stalls`
    reader = bench_run._module(os.path.join(bench_run.ROOT, "benchmark"), "readers", "round_span_percentile")
    ctx = {"snapshot_end": {"profile_dir": str(tmp_path)}, "t0": t0 - 10.0, "counted_s": 20.0}
    with_stalls = reader.read(ctx, stage="loop_stall", q=50, at_least=1)
    assert with_stalls is not None and with_stalls > 20.0
    del ring["stalls"]
    with open(tmp_path / "round_spans.json", "w") as fh:
        json.dump(ring, fh)
    assert reader.read(ctx, stage="loop_stall", q=50, at_least=1) == with_stalls


def test_a_pool_thread_registers_itself_at_its_first_step(tmp_path):
    from fantoch_tpu.core import Command, KVOp, Rifl

    async def go():
        runtime = _runtime(metrics_file=str(tmp_path / "snap.json"))
        await runtime.start()
        # the round is compiled before the server listens, so the steps
        # themselves have to cost the thread more than a tick of its CPU
        # clock (10 ms on some kernels): sixty rounds
        for seq in range(1, COMMANDS + 1):
            cmd = Command.from_single(Rifl(9, seq), 0, f"k{seq % 50}", KVOp.put("v"))
            runtime.submit(runtime.dot_gen.next_id(), cmd)
        for _ in range(3000):
            if runtime.failure is not None:
                raise runtime.failure
            if runtime.driver.executed >= COMMANDS:
                break
            await asyncio.sleep(0.01)
        await runtime.stop()
        return runtime

    COMMANDS = 480
    runtime = asyncio.run(go())
    t = runtime._tallies
    assert runtime.driver.executed == COMMANDS
    assert len(runtime.account._threads["step"]) >= 1 and len(runtime.account._threads["loop"]) == 1
    assert t["thread_step_cpu_ms"] > 0 and t["stage_step_cpu_ms"] > 0
    # the step's thread is on its CPU inside its step spans, and little elsewhere
    assert t["thread_step_cpu_ms"] >= t["stage_step_cpu_ms"] * 0.5
    for name in CPU_STAGES:
        assert 0 <= t[f"stage_{name}_cpu_ms"] <= t[f"stage_{name}_ms"] + 1.0, name
    assert t["stage_wait_ms"] >= -1.0
    with open(tmp_path / "snap.json") as fh:
        snap = json.load(fh)
    assert snap["host_cpu_ms"] == t["host_cpu_ms"] and "loop_stopped_ms" in snap


def test_obs_watch_shows_the_served_threads_cpu_and_the_stopped_time():
    from fantoch_tpu.bin.obs import _render_watch

    frame = _render_watch({
        # 870 ms of the two threads' CPU a second of wall; 312 ms stopped so far
        "p1": {"rate": {"submitted": 30000.0, "replied": 30000.0, "host_cpu_ms": 870.0},
               "ctr": {"shed_submissions": 0, "loop_stopped_ms": 312.4}, "g": {"device_idle_frac": 0.97}, "h": {}},
        # a source that is no device-step server has neither
        "clients": {"rate": {"submitted": 30000.0}, "ctr": {}, "g": {}, "h": {}},
    }).splitlines()
    assert frame[0].split()[-3:] == ["idle", "cpu%", "stop"]
    assert frame[1].split()[0] == "clients" and frame[1].split()[-2:] == ["-", "-"]
    assert frame[2].split()[0] == "p1" and frame[2].split()[-3:] == ["0.97", "87", "312"]
