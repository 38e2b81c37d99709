"""The loop's thread's account of its own time (PR 51;
observability/device.py ``TimedSelector``, ``StageRecorder.turn`` and the
``read`` stage, what ``DeviceRuntime`` publishes of them, ``bin/server``'s
loop made over the selector): a turn, a sleep and a poll that cannot sleep
from two clock reads a visit to the selector; a socket read's pass as one
span from the clock reads ``Rw.recv_all`` and ``_admit`` already take,
annotated only under a capture; what no stage names on either thread; the
chain tuner's own S; and one served run through ``bin/server``'s entry.
"""

import asyncio
import json
import os
import selectors
import signal
import subprocess
import sys
import threading
import time

import jax
import pytest

# as tests/test_device_runner.py: the device drivers' scan bodies crash
# jaxlib 0.4.x flakily while tracing
if tuple(int(x) for x in jax.__version__.split(".")[:2]) < (0, 5):
    pytest.skip("jax<0.5: device-driver scan tracing segfaults flakily", allow_module_level=True)

from fantoch_tpu.observability import device as obs
from fantoch_tpu.observability.device import (
    LOOP_NAMED_STAGES,
    LOOP_ROW_NS,
    STEP_NAMED_STAGES,
    StageRecorder,
    TimedSelector,
)
from fantoch_tpu.run import device_runner, device_session, rw
from tests.test_cli import REPO, cli_env, free_port
from tests.test_session_reads import _Served, _submit

MS = 1_000_000
LOOP_KEYS = (
    "loop_turns", "loop_busy_ms", "loop_poll_wait_ms", "loop_poll_ready_ms",
    "loop_poll_ready_n", "loop_unnamed_ms",
)


# --- the selector that reads the clock ---


class _Clock:
    """A clock the test moves: what the selector and the recorder read."""

    def __init__(self, at=1000 * MS):
        self.at = at

    def __call__(self):
        return self.at


def _timed(monkeypatch, took_ns):
    """A ``TimedSelector`` over an inner ``select`` that takes
    ``took_ns(timeout)`` of the clock and sees nothing, reporting to a
    recorder on that clock."""
    clock, asked = _Clock(), []

    def inner(self, timeout=None):
        asked.append(timeout)
        clock.at += took_ns(timeout)
        return []

    monkeypatch.setattr(selectors.DefaultSelector, "select", inner)
    selector, rec = TimedSelector(), StageRecorder(ring=64)
    rec.clock = clock
    return selector, rec, clock, asked


def test_a_selector_without_a_recorder_is_the_selector_it_extends(monkeypatch):
    selector, rec, clock, asked = _timed(monkeypatch, lambda timeout: 5 * MS)
    assert selector.select(0.25) == [] and asked == [0.25]
    assert rec.loop_thread is None and rec.loop_turns == 0
    assert not set(LOOP_KEYS) & set(rec.counters())
    selector.close()


def test_busy_and_the_two_polls_sum_to_the_time_since_the_first_call(monkeypatch):
    """Timeout 0 goes to ``ready``, any other to ``wait``; what lies between
    a call's return and the next call's start is the turn; a turn of 1 ms
    and more is a row of the ring, a shorter one is counted only."""
    selector, rec, clock, asked = _timed(
        monkeypatch, lambda timeout: 40 * MS if timeout is None or timeout > 0 else 3 * MS)
    selector.recorder = rec
    origin = clock.at
    # (timeout asked, the turn after the call's return)
    visits = [(0, 7 * MS), (None, 400_000), (0.5, LOOP_ROW_NS), (0, LOOP_ROW_NS - 1), (0, 2 * MS), (-1, 0)]
    turns = []
    for timeout, turn in visits:
        selector.select(timeout)
        turns.append((clock.at, clock.at + turn))
        clock.at += turn
    assert asked == [timeout for timeout, _ in visits]
    counters = rec.counters()
    # the last visit's turn is open: five closed turns, six polls
    assert counters["loop_turns"] == 5
    assert counters["loop_busy_ms"] == pytest.approx((7 * MS + 400_000 + 2 * LOOP_ROW_NS - 1 + 2 * MS) / 1e6, abs=1e-3)
    assert counters["loop_poll_ready_n"] == 4  # 0, 0, 0 and -1: none of them can sleep
    assert counters["loop_poll_ready_ms"] == 12.0 and counters["loop_poll_wait_ms"] == 80.0
    assert rec.loop_t0_ns == origin and rec.loop_thread == threading.get_ident()
    elapsed = rec.loop_returned_ns - origin
    assert rec.loop_busy_ns + rec.loop_poll_wait_ns + rec.loop_poll_ready_ns == elapsed
    assert elapsed == clock.at - origin  # the sixth visit's turn is empty
    rows = [row for row in rec.ring if row[0] == "turn"]
    assert [(row[1], row[2]) for row in rows] == [turns[0], turns[2], turns[4]]
    assert all(row[4] == threading.get_ident() and row[5] is None for row in rows)
    # nothing named closed in a turn: all of it is unnamed
    assert counters["loop_unnamed_ms"] == counters["loop_busy_ms"]
    # a turn is no stage: its time is loop_busy_ms alone
    assert "stage_turn_ms" not in counters
    selector.close()


def test_a_real_loop_over_the_selector_accounts_for_its_wall_time():
    """``asyncio.SelectorEventLoop(selector)`` through the public API: a
    sleep is ``wait``, a spin of the loop's thread is ``busy`` and a row."""
    selector, rec = TimedSelector(), StageRecorder(ring=64)

    async def go():
        selector.recorder = rec
        await asyncio.sleep(0.05)
        until = time.monotonic() + 0.02
        while time.monotonic() < until:
            pass
        await asyncio.sleep(0)
        await asyncio.sleep(0.01)

    with asyncio.Runner(loop_factory=lambda: asyncio.SelectorEventLoop(selector)) as runner:
        runner.run(go())
    assert rec.loop_busy_ns + rec.loop_poll_wait_ns + rec.loop_poll_ready_ns == rec.loop_returned_ns - rec.loop_t0_ns
    assert rec.loop_poll_wait_ns >= 55 * MS and rec.loop_busy_ns >= 19 * MS
    assert rec.loop_poll_ready_n >= 1  # the sleep(0): callbacks were ready
    assert any(row[0] == "turn" and row[2] - row[1] >= 19 * MS for row in rec.ring)


# --- what no stage names ---


def _on_a_loop(rec, at=1000 * MS):
    """The calling thread is the loop's: a first visit to the selector
    that returned at ``at``."""
    rec.turn(at - 10, at, True)
    return at


def test_loop_unnamed_takes_off_the_named_rows_of_the_loops_thread_alone():
    rec = StageRecorder(ring=64)
    t0 = _on_a_loop(rec)
    rec.record("collect", t0 + 1 * MS, t0 + 3 * MS, 1)           # named: 2 ms
    rec.record("handoff", t0 + 3 * MS, t0 + 4 * MS, 1, "round")  # spans an await: not named
    rec.record("gc", t0 + 5 * MS, t0 + 6 * MS)                   # inside the deliver below
    rec.record("gc", t0 + 6 * MS, t0 + 6 * MS + 500_000)         # and a second one
    rec.record("deliver", t0 + 4 * MS, t0 + 8 * MS, 1)           # named: 4 ms, the two within it once
    other = threading.Thread(target=rec.record, args=("gc", t0 + 8 * MS, t0 + 9 * MS))
    other.start()  # a collection that fired on the step's thread
    other.join()
    rec.record("read", t0 + 9 * MS, t0 + 9 * MS + 250_000, row=False)  # named, and no row
    # the open turn's spans are not taken off before it closes
    assert rec.counters()["loop_unnamed_ms"] == 0.0 and rec.loop_named_ns == 0
    rec.turn(t0 + 10 * MS, t0 + 11 * MS, False)
    counters = rec.counters()
    assert counters["loop_busy_ms"] == 10.0 and counters["loop_poll_wait_ms"] == 1.0
    assert counters["loop_unnamed_ms"] == pytest.approx(10.0 - 2.0 - 4.0 - 0.25)
    assert counters["stage_gc_ms"] == 2.5 and counters["stage_gc_n"] == 3  # the stage's own sum has all three
    assert counters["stage_read_ms"] == 0.25 and counters["stage_read_n"] == 1
    assert [row[0] for row in rec.ring] == ["collect", "handoff", "gc", "gc", "deliver", "gc", "turn"]
    # the next turn starts from nothing
    rec.record("publish", t0 + 11 * MS, t0 + 12 * MS, 1)
    rec.turn(t0 + 12 * MS + 500_000, t0 + 13 * MS, True)
    assert rec.counters()["loop_unnamed_ms"] == pytest.approx(3.75 + 0.5)
    assert 0 <= rec.loop_named_ns <= rec.loop_busy_ns


def test_the_named_stages_are_the_ones_that_compute_on_the_loop():
    assert LOOP_NAMED_STAGES == {"read", "collect", "deliver", "publish", "snapshot", "gc", "precompile"}
    assert LOOP_NAMED_STAGES <= set(obs.ROUND_STAGES)
    assert STEP_NAMED_STAGES == ("assemble", "enqueue", "fetch", "execute")
    assert "turn" not in obs.ROUND_STAGES and LOOP_ROW_NS == MS


def test_a_recorder_before_its_first_visit_names_nothing_of_the_loop():
    """Start-up's spans on the loop's thread (the chain programs made before
    the runtime's first await) close before the account's origin."""
    rec = StageRecorder(ring=8)
    with rec.span("precompile", 1):
        pass
    _on_a_loop(rec)
    rec.turn(1000 * MS + 5 * MS, 1000 * MS + 6 * MS, False)
    assert rec.loop_named_ns == 0 and rec.counters()["loop_unnamed_ms"] == 5.0


def test_step_unnamed_is_the_step_less_its_four_stages_from_a_stepped_driver():
    """A driver stepped under a ``step`` span, as ``_step_on_pool`` steps it."""
    from tests.test_device_runner import _driver, _puts

    driver = _driver()
    stages = driver.stages
    assert stages.counters()["step_unnamed_ms"] == 0.0  # in the first snapshot
    for seqs in (range(1, 9), range(9, 14)):
        with stages.span("step", driver.dispatches + 1):
            driver.serve([_puts(seqs)[0]], False)
    assert driver.executed == 13
    counters = stages.counters()
    parts = sum(counters[f"stage_{name}_ms"] for name in STEP_NAMED_STAGES)
    assert all(counters[f"stage_{name}_n"] >= 2 for name in STEP_NAMED_STAGES)
    assert counters["step_unnamed_ms"] == pytest.approx(counters["stage_step_ms"] - parts, abs=0.005)
    assert 0 <= counters["step_unnamed_ms"] <= counters["stage_step_ms"]
    assert not set(LOOP_KEYS) & set(counters)


# --- a socket read's pass ---


class _Notes:
    """What ``StageRecorder._annotation`` is asked for, in place of
    ``jax.profiler.TraceAnnotation``."""

    def __init__(self):
        self.open, self.closed = [], []

    def __call__(self, name, **kw):
        notes = self

        class _Note:
            def __enter__(self):
                notes.open.append(name)

            def __exit__(self, *exc):
                notes.closed.append(name)

        return _Note()


class _CountedClock:
    """``time.monotonic_ns`` under a module's own name, every value kept."""

    def __init__(self):
        self.values = []

    def __call__(self):
        self.values.append(time.monotonic_ns())
        return self.values[-1]


@pytest.mark.parametrize("frames", [1, 40])
def test_a_read_is_one_span_from_the_clock_reads_its_walk_and_its_admit_take(frames, monkeypatch):
    """``stage_read_ms`` from ``recv_all``'s first clock read to ``_admit``'s
    last: decode + admit and the gap between the two, with no clock read of
    its own; annotated as ``fantoch/decode`` and ``fantoch/admit`` only while
    the recorder says a capture runs."""

    async def go():
        async with _Served(monitor=False) as served:
            runtime, session = served.runtime, served.session
            stages = runtime.stages
            session.rw._stages = stages  # as DeviceRuntime._on_client makes its Rw
            notes = stages._annotation = _Notes()
            walk_clock, admit_clock = _CountedClock(), _CountedClock()
            admits = []
            admit = session._admit

            def counted_admit(msgs):
                before = len(admit_clock.values)
                admit(msgs)
                admits.append(admit_clock.values[before:])

            session._admit = counted_admit
            before = served.tallies()
            monkeypatch.setattr(rw, "monotonic_ns", walk_clock)
            monkeypatch.setattr(device_session, "monotonic_ns", admit_clock)
            reads = 3
            for n in range(reads):
                if n == 2:
                    stages.capturing = True  # the capture's start sets it (on the class)
                data = b"".join(
                    rw.frame(_submit(1 + i % 3, n * frames + i + 1, f"k{i % 7}")) for i in range(frames))
                await served.read(data)
            del stages.capturing
            monkeypatch.undo()
            await served.replies(reads * frames)
            after = served.tallies()
            return stages, notes, walk_clock.values, admits, before, after

    stages, notes, walks, admits, before, after = asyncio.run(go())
    grown = {key: after[key] - before[key] for key in ("stage_read_ms", "stage_read_n", "session_decode_ms",
                                                       "session_admit_ms", "session_reads", "submitted")}
    assert grown["stage_read_n"] == grown["session_reads"] == 3 and grown["submitted"] == 3 * frames
    # two clock reads a walk and two an admit pass, as before the span: it takes none
    assert len(walks) == 6 and [len(pair) for pair in admits] == [2, 2, 2]
    spans = [(walks[2 * n], admits[n][1]) for n in range(3)]
    assert grown["stage_read_ms"] == pytest.approx(sum(t1 - t0 for t0, t1 in spans) / 1e6, abs=0.005)
    parts = grown["session_decode_ms"] + grown["session_admit_ms"]
    gaps = sum(admits[n][0] - walks[2 * n + 1] for n in range(3)) / 1e6
    assert grown["stage_read_ms"] == pytest.approx(parts + gaps, abs=0.005) and gaps >= 0
    # a pass of a millisecond and more is a row, and only such a one
    rows = [(row[1], row[2]) for row in stages.ring if row[0] == "read"]
    assert rows == [span for span in spans if span[1] - span[0] >= LOOP_ROW_NS]
    # the third read alone ran under the flag (every span of a round annotates itself always)
    named = [name for name in notes.open if name in ("fantoch/decode", "fantoch/admit")]
    assert named == ["fantoch/decode", "fantoch/admit"]
    assert [name for name in notes.closed if name in named] == named
    assert not StageRecorder.capturing


class _NoSocket:
    def get_extra_info(self, name):
        return None


def test_the_walk_closes_its_annotation_where_a_frame_is_refused():
    stages = StageRecorder(ring=8)
    notes = stages._annotation = _Notes()
    stages.capturing = True

    async def go():
        reader = asyncio.StreamReader()
        conn = rw.Rw(reader, _NoSocket(), decode_tally=[0] * 7, stages=stages)
        reader.feed_data(rw._LEN.pack(2) + b"\x7fx")  # no such kind
        with pytest.raises(rw.ProtocolError, match="unknown frame kind"):
            await conn.recv_all()

    asyncio.run(go())
    assert notes.open == notes.closed == ["fantoch/decode"]


def test_a_capture_sets_the_flag_for_its_length_and_clears_it(tmp_path, monkeypatch):
    from jax import profiler

    from fantoch_tpu.observability import exposition

    seen = []
    monkeypatch.setattr(profiler, "start_trace", lambda path, **kw: seen.append(("start", StageRecorder.capturing)))
    monkeypatch.setattr(profiler, "stop_trace", lambda: seen.append(("stop", StageRecorder.capturing)))
    rec = StageRecorder(ring=8)
    assert not rec.capturing
    result = asyncio.run(exposition.capture_device_profile(str(tmp_path), 5))
    assert result["ms"] == 5 and seen == [("start", True), ("stop", True)]
    assert not rec.capturing and not StageRecorder.capturing
    # a capture that fails leaves no flag behind
    monkeypatch.setattr(profiler, "start_trace", lambda path, **kw: 1 / 0)
    assert "error" in asyncio.run(exposition.capture_device_profile(str(tmp_path), 5))
    assert not StageRecorder.capturing


# --- what the runtime publishes ---


def _runtime(**kw):
    from fantoch_tpu.core import Config

    return device_runner.DeviceRuntime(
        Config(3, 1, shard_count=1), ("127.0.0.1", 0), batch_size=8, key_buckets=64, **kw
    )


def test_a_runtime_on_a_plain_loop_leaves_the_loops_six_counters_out():
    runtime = _runtime()
    first = runtime._tallies
    assert not set(LOOP_KEYS) & set(first)
    # what does not hang on the selector is in the first snapshot all the same
    assert first["step_unnamed_ms"] == 0.0 and first["stage_read_ms"] == 0.0 and first["stage_read_n"] == 0
    assert first["serving_chain"] == 1 and first["chain_adjustments"] == 0
    runtime.account.close()


def test_a_runtime_given_the_loops_selector_publishes_the_six():
    selector = TimedSelector()
    runtime = _runtime(loop_selector=selector)
    assert selector.recorder is runtime.stages
    assert not set(LOOP_KEYS) & set(runtime._tallies)  # no visit reported yet
    selector.select(0)
    selector.select(0.001)
    runtime._publish_tallies()
    t = runtime._tallies
    assert set(LOOP_KEYS) <= set(t)
    assert t["loop_turns"] == 1 and t["loop_poll_ready_n"] == 1 and t["loop_poll_wait_ms"] >= 1.0
    counters, gauges, _ = runtime.telemetry_sample()
    assert set(LOOP_KEYS) <= set(counters) and {"step_unnamed_ms", "chain_adjustments"} <= set(counters)
    assert "serving_chain" in gauges and "serving_chain_len" in gauges
    runtime.account.close()
    selector.close()


def test_serving_chain_and_chain_adjustments_follow_a_tuner_that_doubles():
    runtime = _runtime()
    tuner = runtime._chain_tuner
    assert tuner.chain_max >= 4
    tuner.observe(0, 0.0, 0.0, 0)
    # the dispatch call costs as much as the round on the device: S doubles
    tuner.observe(8, 80.0, 80.0, 8)
    runtime._publish_tallies()
    assert (runtime._tallies["serving_chain"], runtime._tallies["chain_adjustments"]) == (2, 1)
    tuner.observe(16, 160.0, 160.0, 24)
    # and a call that costs next to nothing halves it
    tuner.observe(24, 160.1, 400.0, 56)
    runtime._publish_tallies()
    assert (runtime._tallies["serving_chain"], runtime._tallies["chain_adjustments"]) == (2, 3)
    # what the last dispatch carried is the driver's own gauge, beside it
    assert runtime._tallies["serving_chain_len"] == runtime.driver.chain_len
    runtime.account.close()


def test_obs_watch_shows_the_loops_share_of_the_window():
    from fantoch_tpu.bin.obs import _render_watch

    frame = _render_watch({
        # 912 ms outside the selector a second of wall
        "p1": {"rate": {"submitted": 70000.0, "loop_busy_ms": 912.0, "host_cpu_ms": 1010.0},
               "ctr": {"shed_submissions": 0, "loop_stopped_ms": 0.0}, "g": {"device_idle_frac": 0.9}, "h": {}},
        # a runtime on a plain loop has no such counter
        "p2": {"rate": {"submitted": 10.0, "host_cpu_ms": 20.0}, "ctr": {}, "g": {}, "h": {}},
        # a loop that slept through the window reads 0, not "-"
        "p3": {"rate": {"loop_busy_ms": 0.0}, "ctr": {}, "g": {}, "h": {}},
    }).splitlines()
    assert frame[0].split()[-4:] == ["loop%", "idle", "cpu%", "stop"]
    assert frame[1].split()[0] == "p1" and frame[1].split()[-4:] == ["91", "0.90", "101", "0"]
    assert frame[2].split()[0] == "p2" and frame[2].split()[-4:] == ["-", "-", "2", "-"]
    assert frame[3].split()[0] == "p3" and frame[3].split()[-4] == "0"


# --- one served run through bin/server's own entry ---

# ``bin/server``'s ``main`` as ``python -m fantoch_tpu.bin.server`` runs it, in a
# process that also keeps the recorder's two ends of the account as they stood
# when the final snapshot was taken (the loop turns a few times more while
# ``asyncio.run`` winds it down)
_ENTRY = """
import json, sys
from fantoch_tpu.run import device_runner
emit_final = device_runner.DeviceRuntime.emit_final
def kept(self):
    emit_final(self)
    with open(sys.argv[1], "w") as fh:
        json.dump({"t0_ns": self.stages.loop_t0_ns, "returned_ns": self.stages.loop_returned_ns}, fh)
device_runner.DeviceRuntime.emit_final = kept
from fantoch_tpu.bin import server
server.main(sys.argv[2:])
"""


def test_a_served_run_through_the_servers_entry_accounts_for_the_loops_time(tmp_path):
    port = free_port()
    snap_path, ends_path = tmp_path / "snapshot.json", tmp_path / "ends.json"
    server = subprocess.Popen(
        [sys.executable, "-c", _ENTRY, str(ends_path), "--protocol", "epaxos", "--device-step",
         "--client-port", str(port), "--device-batch", "32", "--device-key-buckets", "64",
         "-n", "3", "-f", "1", "--metrics-file", str(snap_path), "--metrics-interval", "200"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=cli_env(), cwd=REPO,
    )
    try:
        out = subprocess.run(
            [sys.executable, "-m", "fantoch_tpu.bin.client", "--ids", "1-4",
             "--addresses", f"0=127.0.0.1:{port}", "--commands-per-client", "150",
             "--conflict-rate", "50", "--payload-size", "8"],
            capture_output=True, text=True, timeout=240, env=cli_env(), cwd=REPO,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert json.loads(out.stdout.strip().splitlines()[-1])["commands"] == 600
    finally:
        server.send_signal(signal.SIGTERM)
        try:
            _, err = server.communicate(timeout=60)
        except subprocess.TimeoutExpired:
            server.kill()
            _, err = server.communicate()
    assert server.returncode == 0, err[-2000:]
    with open(snap_path) as fh:
        snap = json.load(fh)
    with open(ends_path) as fh:
        ends = json.load(fh)
    new = (*LOOP_KEYS, "step_unnamed_ms", "stage_read_ms", "stage_read_n", "serving_chain", "chain_adjustments")
    assert [key for key in new if key not in snap] == []
    assert snap["executed"] == 600 and snap["stage_read_n"] == snap["session_reads"] > 0
    elapsed_ms = (ends["returned_ns"] - ends["t0_ns"]) / 1e6
    assert elapsed_ms > 100.0
    polls = snap["loop_poll_wait_ms"] + snap["loop_poll_ready_ms"]
    assert snap["loop_busy_ms"] == pytest.approx(elapsed_ms - polls, rel=0.01)
    assert 0 <= snap["loop_unnamed_ms"] <= snap["loop_busy_ms"]
    assert snap["loop_turns"] > snap["loop_poll_ready_n"] > 0
    assert 0 <= snap["step_unnamed_ms"] <= snap["stage_step_ms"]
    # a read is its walk, its admit pass and the few lines between them
    parts = snap["session_decode_ms"] + snap["session_admit_ms"]
    assert parts * 0.9 <= snap["stage_read_ms"] <= parts + 0.05 * snap["stage_read_n"]
    # the ring was written with the account's rows under their names
    with open(os.path.join(snap["profile_dir"], "round_spans.json")) as fh:
        ring = json.load(fh)
    loop_threads = {row[4] for row in ring["spans"] if row[0] == "turn"}
    assert len(loop_threads) <= 1
    assert all(row[2] - row[1] >= LOOP_ROW_NS for row in ring["spans"] if row[0] in ("turn", "read"))
