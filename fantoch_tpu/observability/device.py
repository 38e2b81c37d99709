"""Device-plane counters: per-dispatch tallies and XLA recompile events.

The device planes (the resident votes-table plane, the serving drivers,
the batched graph resolver) do their work in fused dispatches, so
per-item latency attribution stops at the batch boundary — what remains
observable is *per-dispatch*: how many dispatches, how full each batch
was, how much kernel wall time, and whether XLA recompiled mid-run (the
classic silent latency cliff).  These counters ride two channels:

- folded into the periodic metrics snapshot
  (:class:`fantoch_tpu.run.observe.ProcessMetrics.device`);
- emitted as tracer counter events so a Perfetto timeline shows them
  next to the spans of the batches they carried.

Recompiles are counted by subscribing to ``jax.monitoring`` duration
events; the subscription is process-global and idempotent.  With the
persistent compilation cache on (core/compile_cache.py), the raw
``.../backend_compile_duration`` event is ambiguous — it wraps
``compile_or_get_cached``, so it fires for disk retrievals too.  The
listener therefore PAIRS each duration event with the cache hit/miss
event that jax emits immediately before it: a duration event preceded
by a cache hit is a retrieval (counted in :func:`cache_hit_count`, its
wall in :func:`compile_ms` — retrieval stalls serving just like a
compile, only shorter), everything else is a TRUE compile.  That makes
``jax_recompiles == 0`` the proof a warm-cache sweep never paid XLA.

The served path's host time is split by :class:`StageRecorder`: one span
per stage of a round (never per command), summed into ``stage_<name>_ms``
/ ``stage_<name>_n`` beside the tallies above, annotated on the
profiler's clock, and kept in a bounded ring that the runtime writes out
when it stops.  Who had the CPU meanwhile is :class:`ThreadAccount`'s: the
CPU time and the run-queue wait of the served path's two threads, which
:func:`classify_stall` reads at the two ends of a late wake-up of the loop.
The loop's thread accounts for its own time through :class:`TimedSelector`:
two clock reads around each visit to the selector split its wall time into
turns (``loop_busy_ms``), sleeps (``loop_poll_wait_ms``) and polls that
cannot sleep (``loop_poll_ready_ms``: the wait for the interpreter lock),
and what no stage names of a turn is ``loop_unnamed_ms``.
"""

from __future__ import annotations

import heapq
import json
import os
import resource
import selectors
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, NamedTuple, Optional, Tuple

_recompiles = 0
_compile_ms = 0.0
_cache_hits = 0
_cache_misses = 0
_pending_hits = 0
_subscribed = False


def subscribe_recompiles() -> bool:
    """Start counting XLA backend compiles and persistent-cache traffic
    (idempotent; returns whether the jax.monitoring hooks installed).
    Safe to call before any jax work — the listeners cost nothing until
    a compile happens."""
    global _subscribed
    if _subscribed:
        return True
    from jax import monitoring

    # jax calls a listener as callback(event, [duration,] **kwargs); the
    # keywords (fun_name=..., ...) are not used here
    def _on_event(key: str, **_kwargs) -> None:
        global _cache_hits, _cache_misses, _pending_hits
        # the persistent-cache outcome events fire BEFORE the duration
        # event of the compile-or-retrieve they describe (verified on the
        # pinned jax); a pending hit reclassifies that duration event as
        # a retrieval
        if key.endswith("compilation_cache/cache_hits"):
            _cache_hits += 1
            _pending_hits += 1
        elif key.endswith("compilation_cache/cache_misses"):
            _cache_misses += 1

    def _on_duration(key: str, secs: float, **_kwargs) -> None:
        global _recompiles, _compile_ms, _pending_hits
        if key.endswith("backend_compile_duration"):
            if _pending_hits > 0:
                _pending_hits -= 1
            else:
                _recompiles += 1
            # cumulative compile WALL, not just the count: one ~50s cold
            # compile starves heartbeats/serving for its whole duration
            # (PR 14's resolve_graph_plane_step programs) — a count of 1
            # hides that; the milliseconds name it.  Retrieval wall is
            # included: a warm run's compile_ms is the disk-load cost.
            _compile_ms += secs * 1000.0

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _subscribed = True
    return True


def recompile_count() -> int:
    """TRUE XLA backend compiles observed since
    :func:`subscribe_recompiles` (0 when never subscribed); persistent-
    cache retrievals are excluded — see the module docstring."""
    return _recompiles


def compile_ms() -> float:
    """Cumulative XLA backend compile-or-retrieve wall milliseconds since
    :func:`subscribe_recompiles` — host-process-global like
    :func:`recompile_count` (co-hosted runtimes must not sum it)."""
    return round(_compile_ms, 1)


def cache_hit_count() -> int:
    """Persistent-compilation-cache hits (disk retrievals instead of XLA
    compiles) since :func:`subscribe_recompiles`."""
    return _cache_hits


def cache_miss_count() -> int:
    """Persistent-compilation-cache misses (programs that went to XLA)
    since :func:`subscribe_recompiles`."""
    return _cache_misses


# fold semantics per counter kind: most keys are monotone tallies and
# SUM across executors; gauges would be nonsense summed — ratios are
# dropped (derive_idle_frac recomputes from the folded walls) and
# configuration gauges fold by max
_RATIO_KEYS = frozenset({"device_idle_frac"})
_GAUGE_MAX_KEYS = frozenset(
    {
        "device_pipeline_depth",
        "pred_plane_slot_capacity",
        "graph_plane_slot_capacity",
        # plane health gauge (0 healthy / 1 rebuilding / 2 suspect /
        # 3 failed — ordered by numeric severity, so the max IS the
        # worst health across co-hosted executors)
        "table_plane_health",
        "pred_plane_health",
        "graph_plane_health",
    }
)


def merge_counters(
    into: Dict[str, float], add: Optional[Dict[str, float]]
) -> Dict[str, float]:
    """Accumulate one executor's counter dict into a process-level one
    (used by the metrics snapshot fold): tallies sum, ratio keys are
    skipped (:func:`derive_idle_frac` recomputes them from the folded
    busy/span walls), configuration gauges (pipeline depth) fold by
    max."""
    if add:
        for name, value in add.items():
            if name in _RATIO_KEYS:
                continue
            if name in _GAUGE_MAX_KEYS:
                into[name] = max(into.get(name, 0), value)
            else:
                into[name] = into.get(name, 0) + value
    return into


def derive_idle_frac(counters: Dict[str, float]) -> Dict[str, float]:
    """Recompute ``device_idle_frac`` from (possibly folded)
    ``device_busy_ms`` / ``device_span_ms`` wall totals: the fraction of
    the serving span the device sat idle waiting on host assembly/emit —
    the number the pipelined serving loop (run/pipeline.py) exists to
    drive toward 0.  Spans of co-hosted executors overlap in wall time,
    so after a fold this is an approximation (busy and span inflate
    together); per-driver counters are exact."""
    span = counters.get("device_span_ms", 0.0)
    if span and span > 0:
        busy = counters.get("device_busy_ms", 0.0)
        counters["device_idle_frac"] = round(
            max(0.0, 1.0 - busy / span), 4
        )
    return counters


# --- the thread account (who had the CPU) ---


class AccountSample(NamedTuple):
    """One reading of :class:`ThreadAccount`, cumulative; the difference
    of two is what the interval between them cost."""

    loop_cpu_ns: int  # the loop's thread on a CPU
    step_cpu_ns: int  # every thread that has run a step, summed
    loop_runq_ns: int  # runnable and waiting for a CPU (0 without schedstat)
    step_runq_ns: int
    proc_cpu_ns: int  # every thread of the process, the runtime's own included
    minflt: int  # page faults served without / with a read from disk
    majflt: int
    nivcsw: int  # involuntary context switches


class ThreadAccount:
    """The CPU time and the run-queue wait of the served path's two
    threads, beside what the whole process used: the event loop's thread
    (the one that creates the account) and every pool thread that has run
    a step (:meth:`register`, called on that thread; one worker in
    practice, summed over all seen).  Per thread, cumulative since the
    thread started: its CPU clock (``pthread_getcpuclockid``, what
    ``time.thread_time_ns`` reads for the caller) and the second field of
    ``/proc/self/task/<tid>/schedstat``, the nanoseconds it was runnable
    and given no CPU, through a descriptor opened once (the first field
    is the kernel's own count of the time on a CPU, which the clock
    gives).  A kernel without ``schedstat`` leaves the ``*_runq_ms``
    counters out and raises nothing.  A thread that has ended keeps its
    last reading.

    :meth:`sample` is for the loop's thread (at each wake-up of the lag
    probe, and where the tallies are published if the probe's last is
    stale); nothing here runs per command.  Every read is a system call
    where the kernel serves these clocks itself (6-20 us each on the
    chip hosts' sandboxed kernel, whose CPU clocks also tick in steps of
    10 ms; 0.3-0.6 us on a plain Linux): hence one sample for both
    callers, and the spacing of the spans' CPU pairs below."""

    SCHEDSTAT = "/proc/self/task/%d/schedstat"

    def __init__(self):
        self.has_runq = True  # until a thread cannot open and read its schedstat
        self._seen: set = set()
        # role -> [cpu clock id, schedstat fd or None, cpu ns, runq ns] per thread
        self._threads: Dict[str, List[list]] = {"loop": [], "step": []}
        self._last: Optional[Tuple[int, AccountSample]] = None  # (monotonic ns, sample)
        self.register("loop")

    def register(self, role: str) -> None:
        """The calling thread, under ``role`` (``loop`` or ``step``);
        free after the first call on a thread."""
        ident = threading.get_ident()
        if ident in self._seen:
            return
        self._seen.add(ident)
        fd = None
        if self.has_runq:
            try:
                fd = os.open(self.SCHEDSTAT % threading.get_native_id(), os.O_RDONLY)
                int(os.pread(fd, 64, 0).split()[1])
            except (OSError, IndexError, ValueError):
                # no schedstat here, or not its three numbers
                self.has_runq = False
                if fd is not None:
                    os.close(fd)
                    fd = None
        # appended whole: the loop's thread may be reading the list
        self._threads[role].append([time.pthread_getcpuclockid(ident), fd, 0, 0])

    def _read(self, role: str) -> Tuple[int, int]:
        cpu = runq = 0
        for thread in self._threads[role]:
            clock_id, fd = thread[0], thread[1]
            try:
                thread[2] = time.clock_gettime_ns(clock_id)
                if fd is not None:
                    thread[3] = int(os.pread(fd, 64, 0).split()[1])
            except OSError:
                pass  # the thread has ended: its last reading stands
            cpu += thread[2]
            runq += thread[3]
        return cpu, runq

    def sample(self) -> AccountSample:
        loop_cpu, loop_runq = self._read("loop")
        step_cpu, step_runq = self._read("step")
        usage = resource.getrusage(resource.RUSAGE_SELF)
        sample = AccountSample(
            loop_cpu, step_cpu, loop_runq, step_runq, time.process_time_ns(),
            usage.ru_minflt, usage.ru_majflt, usage.ru_nivcsw,
        )
        self._last = (time.monotonic_ns(), sample)
        return sample

    def counters(self, fresh_ns: int = 0) -> Dict[str, float]:
        """A sample under the snapshot's names: the last one taken if it
        is at most ``fresh_ns`` old (the probe's, while it runs on time),
        else a new one."""
        if self._last is not None and time.monotonic_ns() - self._last[0] <= fresh_ns:
            s = self._last[1]
        else:
            s = self.sample()
        out = {
            "thread_loop_cpu_ms": round(s.loop_cpu_ns / 1e6, 3),
            "thread_step_cpu_ms": round(s.step_cpu_ns / 1e6, 3),
            "host_cpu_ms": round((s.loop_cpu_ns + s.step_cpu_ns) / 1e6, 3),
            "proc_cpu_ms": round(s.proc_cpu_ns / 1e6, 3),
            "proc_minflt": s.minflt,
            "proc_majflt": s.majflt,
            "proc_nivcsw": s.nivcsw,
        }
        if self.has_runq:
            out["thread_loop_runq_ms"] = round(s.loop_runq_ns / 1e6, 3)
            out["thread_step_runq_ms"] = round(s.step_runq_ns / 1e6, 3)
            out["host_runq_ms"] = round((s.loop_runq_ns + s.step_runq_ns) / 1e6, 3)
        return out

    def close(self) -> None:
        """The descriptors; a later sample repeats the last run-queue
        reading."""
        for threads in self._threads.values():
            for thread in threads:
                if thread[1] is not None:
                    os.close(thread[1])
                    thread[1] = None


STALL_CLASSES = ("busy", "gil", "runq", "blocked")
STALL_KEEP_NS = 50_000_000  # a stall at least this late, and not ``busy``, is kept
STALLS_KEPT = 64  # the longest
STALL_LEAD_NS = 100_000_000  # a kept stall's window of the ring starts this long before it
STALL_SETTLE_NS = 1_000_000_000  # ... and is cut once the spans open across it have closed


def classify_stall(late_ns: int, spent: AccountSample) -> str:
    """What kept the loop from waking up on time, from what the interval
    since its previous wake-up cost (``spent``, a difference of two
    samples).  The first rule that holds, each against half the late
    time: the loop's thread was on a CPU that long (``busy``: a long turn
    of the loop, the program's own work), else the two served threads
    together were (``gil``: the loop waited for the other served thread,
    the lock or its turn; together, because two threads that hand the
    lock back and forth may each stay under the half), else the two
    together were runnable and given no CPU that long (``runq``: the
    machine), else neither ran nor was runnable (``blocked``: asleep in
    the kernel, in page faults, a driver call, or on a lock held by a
    thread outside the served path)."""
    if 2 * spent.loop_cpu_ns >= late_ns:
        return "busy"
    if 2 * (spent.loop_cpu_ns + spent.step_cpu_ns) >= late_ns:
        return "gil"
    if 2 * (spent.loop_runq_ns + spent.step_runq_ns) >= late_ns:
        return "runq"
    return "blocked"


# --- round-stage spans (the served path's host time, per round) ---

# the stages of a served round (run/device_runner.py ``_driver_task`` and
# run/pipeline.py name the sites); declared up front so every counter is
# in the first snapshot and a reader can take deltas of all of them
ROUND_STAGES = (
    "idle_wait", "gate_wait", "collect", "handoff", "step", "assemble",
    "enqueue", "fetch", "execute", "resume", "deliver", "publish", "round",
    # on the loop beside the rounds: the telemetry tick's snapshot write,
    # the probe's late wake-ups, the interpreter's full collections
    "snapshot", "loop_stall", "gc",
    # a socket read's pass on the loop: from the first byte ``Rw.recv_all``
    # walks to ``_admit`` returning, from the clock reads those two take
    "read",
    # before the first round: a chain program compiled or loaded
    # (``precompile_chains``; the span's round is the chain length); and
    # the round's second program, where clients of a second site register
    "precompile",
    # inside ``execute``: the host's Tarjan over the rows of a key's run
    # the device's resolver did not cut (a coordinator at every site)
    "finish",
)
# the stages that neither sleep nor wait on the device by design: what
# their wall time holds beyond their CPU time is time their thread was not
# running (``wait_ns``)
COMPUTE_STAGES = ("assemble", "execute", "collect", "deliver", "publish")
# the stages whose spans also sum their thread's CPU time
# (``stage_<name>_cpu_ms``): those, the step as a whole, and the two whose
# remainder is the lock, the runtime or the device
CPU_STAGES = ("step", "enqueue", "fetch") + COMPUTE_STAGES
# a stage, and each of the session plane's two counters, takes its CPU
# pair at most this often (counted from the start of the last span that
# took one): two system calls a pair where the kernel serves the clock
# itself, against an open round of 10 ms with eight such spans.  A
# saturated round is longer than this, so there every span has its pair;
# elsewhere ``stage_<name>_timed_ms`` is the wall time of the spans that do
CPU_PAIR_EVERY_NS = 50_000_000
# closed spans kept for the dump: a round closes 13, so about 5,000 rounds
# (two to three open-loop runs of 20 s, many more saturated ones)
SPAN_RING = 65536
# the spans that close on the loop's thread and compute there: what a turn
# of the loop holds beyond them is ``loop_unnamed_ms``.  Not ``round``,
# ``handoff``, ``resume``, ``gate_wait``, ``idle_wait``, ``loop_stall``:
# they span awaits
LOOP_NAMED_STAGES = frozenset(
    {"read", "collect", "deliver", "publish", "snapshot", "gc", "precompile"}
)
# the stages the step is made of: what it holds beyond them is ``step_unnamed_ms``
STEP_NAMED_STAGES = ("assemble", "enqueue", "fetch", "execute")
# a turn of the loop, and a socket read's pass, is a row of the ring from
# this length on; a shorter one is counted only (an open cell makes
# thousands a second, and the ring has to hold a window's ``round`` spans)
LOOP_ROW_NS = 1_000_000


def off_cpu_ns(wall_ns: int, timed_ns: int, cpu_ns: int) -> float:
    """Of ``wall_ns`` the part off the CPU, from the spans among it that
    took a CPU pair (``timed_ns`` of wall, ``cpu_ns`` on a CPU): exact
    where all did, else their share laid over the whole."""
    return wall_ns * (timed_ns - cpu_ns) / timed_ns if timed_ns else 0.0


class _Span:
    """One open stage: ``with recorder.span(name, round)``.  ``t0`` and
    ``t1`` (``time.monotonic_ns``) stay readable after the block."""

    __slots__ = (
        "_rec", "name", "round", "parent", "_cpu0", "_note", "t0", "t1", "read_rows",
    )

    def __init__(self, rec, name, round_id, parent):
        self._rec = rec
        self.name = name
        self.round = round_id
        self.parent = parent
        # the reads among what the span's round executed, set inside the
        # block by a ``round`` span of the dep-commit round; else None
        self.read_rows = None
        # thread CPU time at entry, for a stage of CPU_STAGES
        self._cpu0 = 0 if name in rec.cpu_ns else None
        self._note = rec._annotation("fantoch/" + name, round=round_id)
        self.t0 = self.t1 = 0

    def __enter__(self):
        stack = self._rec._stack()
        if self.parent is None and stack:
            self.parent = stack[-1]
        stack.append(self.name)
        self._note.__enter__()
        self.t0 = time.monotonic_ns()
        # the CPU reads inside the wall reads: wall - CPU is never the
        # cost of the reads themselves
        if self._cpu0 is not None:
            rec = self._rec
            if self.t0 >= rec.cpu_due[self.name]:
                rec.cpu_due[self.name] = self.t0 + CPU_PAIR_EVERY_NS
                self._cpu0 = time.thread_time_ns()
            else:
                self._cpu0 = None
        return self

    def __exit__(self, *exc):
        rec = self._rec
        timed = self._cpu0 is not None
        if timed:
            rec.cpu_ns[self.name] += time.thread_time_ns() - self._cpu0
        self.t1 = time.monotonic_ns()
        if timed:
            rec.timed_ns[self.name] += self.t1 - self.t0
        self._note.__exit__(*exc)
        rec._stack().pop()
        rec.record(self.name, self.t0, self.t1, self.round, self.parent, self.read_rows)
        return False


class StageRecorder:
    """Where a served round's host time goes.  One recorder per driver
    (``PipelineCore._init_pipeline`` creates it, ``DeviceRuntime`` shares
    it), three sinks per span, four for a stage of ``CPU_STAGES``:
    cumulative wall time and count per stage (:meth:`counters`, folded
    into the metrics snapshot), a ``jax.profiler.TraceAnnotation`` named
    ``fantoch/<stage>`` so the span lands in a profiler capture on the
    clock of the device planes (near free while no capture runs), a
    bounded ring of closed spans ``(name, t0_ns, t1_ns, round, thread,
    parent, read_rows)`` for :meth:`dump`, and, for ``step``, ``enqueue``, ``fetch``,
    ``assemble``, ``execute``, ``collect``, ``deliver`` and ``publish``,
    the CPU time of the span's thread (``stage_<name>_cpu_ms``, with the
    wall time of the same spans as ``stage_<name>_timed_ms``: a stage
    takes the pair at most once in ``CPU_PAIR_EVERY_NS``).

    The clock is ``time.monotonic_ns``, the one load generators stamp
    ``due`` / ``sent`` / ``acked`` with.  Spans open on the event loop
    and on the pool thread that runs the step; each stage is written by
    one thread at a time, so no lock is taken.

    The recorder also files the loop's late wake-ups (:meth:`stall`): each
    under its class (:func:`classify_stall`), and the longest that were
    not the loop's own work with the window of the ring around them.

    Where the loop runs over a :class:`TimedSelector` that hands its clock
    reads here (:meth:`turn`), the recorder keeps the loop's thread's own
    account too: turns, sleeps, the polls that cannot sleep, and of the
    turns what ``LOOP_NAMED_STAGES`` do not name.  A recorder no selector
    reports to leaves those counters out."""

    clock = staticmethod(time.monotonic_ns)
    # a profiler capture is running (observability/exposition.py sets and
    # clears it; the profiler is the process's, so is the flag): the spans
    # too frequent to annotate always, a socket read's walk and ``_admit``,
    # annotate themselves while it is set (:meth:`annotate`)
    capturing = False

    def __init__(self, ring: int = SPAN_RING):
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self.ns: Dict[str, int] = dict.fromkeys(ROUND_STAGES, 0)
        self.n: Dict[str, int] = dict.fromkeys(ROUND_STAGES, 0)
        # of the spans that took a CPU pair: their thread's CPU time,
        # their wall time, and when the stage's next pair is due
        self.cpu_ns: Dict[str, int] = dict.fromkeys(CPU_STAGES, 0)
        self.timed_ns: Dict[str, int] = dict.fromkeys(CPU_STAGES, 0)
        self.cpu_due: Dict[str, int] = dict.fromkeys(CPU_STAGES, 0)
        self.ring: Deque[
            Tuple[str, int, int, int, int, Optional[str], Optional[int]]
        ] = deque(maxlen=ring)
        self.stall_ns: Dict[str, int] = dict.fromkeys(STALL_CLASSES, 0)
        # kept stalls: those still waiting for their window of the ring,
        # oldest first, and a heap of (late ns, when due, record)
        self._unsettled: List[Dict[str, Any]] = []
        self._stalls: List[Tuple[int, int, Dict[str, Any]]] = []
        self._local = threading.local()
        # the loop's thread, by its selector's clock reads (:meth:`turn`):
        # None until a first visit to the selector is reported
        self.loop_thread: Optional[int] = None
        self.loop_t0_ns = 0  # that first visit's start: the account's origin
        self.loop_returned_ns = 0  # the last visit's return: the open turn's start
        self.loop_turns = 0
        self.loop_busy_ns = 0
        self.loop_poll_wait_ns = 0
        self.loop_poll_ready_ns = 0
        self.loop_poll_ready_n = 0
        # wall time of the closed turns under a span of LOOP_NAMED_STAGES,
        # and the open turn's: (start, ns) of each such span closed in it
        # that no later one encloses
        self.loop_named_ns = 0
        self._turn_named: List[Tuple[int, int]] = []

    def _stack(self) -> List[str]:
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def span(self, name: str, round_id: int = 0, parent: Optional[str] = None) -> _Span:
        """Context manager for one stage of round ``round_id``.  The
        parent is the innermost span open on this thread unless named
        (a span whose parent runs on the other thread names it)."""
        return _Span(self, name, round_id, parent)

    def annotate(self, name: str):
        """An entered annotation ``name`` on the profiler's clock, for a
        caller that has seen :attr:`capturing` set; it calls
        ``__exit__(None, None, None)`` on what it gets."""
        note = self._annotation(name)
        note.__enter__()
        return note

    def record(self, name: str, t0_ns: int, t1_ns: int, round_id: int = 0,
               parent: Optional[str] = None, read_rows: Optional[int] = None,
               row: bool = True) -> None:
        """A closed interval whose ends were read elsewhere (a hand-off
        between threads, a late wake-up, a socket read's pass): counters
        and, unless ``row`` is false, ring; no annotation."""
        took = t1_ns - t0_ns
        self.ns[name] = self.ns.get(name, 0) + took
        self.n[name] = self.n.get(name, 0) + 1
        thread = threading.get_ident()
        if row:
            self.ring.append((name, t0_ns, t1_ns, round_id, thread, parent, read_rows))
        if thread == self.loop_thread and name in LOOP_NAMED_STAGES:
            # the rows of one thread close in the order of their ends,
            # nested or apart: what this one encloses of those already
            # taken is in it (an unscheduled collection inside ``deliver``)
            named = self._turn_named
            while named and named[-1][0] >= t0_ns:
                named.pop()
            named.append((t0_ns, took))

    def turn(self, called_ns: int, returned_ns: int, ready: bool) -> None:
        """One visit of the loop to its selector (:class:`TimedSelector`,
        on the loop's thread): ``ready`` says the call could not sleep
        (timeout 0: callbacks were waiting), so what it took beyond the
        system call is the wait to take the interpreter lock back.  The
        turn that ended at ``called_ns`` began at the previous visit's
        return: its wall time is the loop's thread outside the selector,
        a forced hand-over of the lock in the middle included; the spans
        of ``LOOP_NAMED_STAGES`` that closed in it are its named part.  A
        turn of ``LOOP_ROW_NS`` and more is a row of the ring."""
        began = self.loop_returned_ns
        if began:
            busy = called_ns - began
            self.loop_turns += 1
            self.loop_busy_ns += busy
            if busy >= LOOP_ROW_NS:
                self.ring.append(("turn", began, called_ns, 0, self.loop_thread, None, None))
        else:
            self.loop_thread = threading.get_ident()
            self.loop_t0_ns = called_ns
        named = self._turn_named
        if named:
            # no span of the list is open across a visit to the selector
            self.loop_named_ns += sum(took for _, took in named)
            named.clear()
        if ready:
            self.loop_poll_ready_ns += returned_ns - called_ns
            self.loop_poll_ready_n += 1
        else:
            self.loop_poll_wait_ns += returned_ns - called_ns
        self.loop_returned_ns = returned_ns

    def stall(self, due_ns: int, woke_ns: int, spent: AccountSample) -> str:
        """A wake-up of the loop that was due at ``due_ns`` and came at
        ``woke_ns``; ``spent`` is what the interval since the previous
        wake-up cost.  A ``loop_stall`` entry of the ring, the late time
        under its class, and, where it was at least ``STALL_KEEP_NS`` and
        not ``busy``, a record for the dump."""
        self.record("loop_stall", due_ns, woke_ns)
        late_ns = woke_ns - due_ns
        kind = classify_stall(late_ns, spent)
        self.stall_ns[kind] += late_ns
        if kind != "busy" and late_ns >= STALL_KEEP_NS:
            self._unsettled.append({
                "t0_ns": due_ns, "t1_ns": woke_ns, "class": kind,
                "spent": spent._asdict(),
            })
        return kind

    def settle_stalls(self, now_ns: Optional[int] = None) -> None:
        """Give each kept stall that ended ``STALL_SETTLE_NS`` before
        ``now_ns`` (every one, without it) its window of the ring: the
        rows that overlap ``STALL_LEAD_NS`` before it through its end, cut
        late so that the spans open across the stall have closed and are
        among them.  Then it is filed; of those filed the ``STALLS_KEPT``
        longest stay."""
        while self._unsettled and (
            now_ns is None or now_ns - self._unsettled[0]["t1_ns"] >= STALL_SETTLE_NS
        ):
            stall = self._unsettled.pop(0)
            lo, hi = stall["t0_ns"] - STALL_LEAD_NS, stall["t1_ns"]
            rows = []
            # a copy first: the step's thread appends meanwhile.  Rows are
            # in closing order: none before the first that closed ahead of
            # the window overlaps it, bar a span recorded a few clock
            # reads after its end was taken
            for row in reversed(list(self.ring)):
                if row[2] < lo:
                    break
                if row[1] <= hi:
                    rows.append(row)
            stall["spans"] = rows[::-1]
            heapq.heappush(
                self._stalls, (stall["t1_ns"] - stall["t0_ns"], stall["t0_ns"], stall)
            )
            if len(self._stalls) > STALLS_KEPT:
                heapq.heappop(self._stalls)

    def ms(self, *names: str) -> float:
        """Cumulative wall milliseconds of the named stages."""
        return sum(self.ns.get(name, 0) for name in names) / 1e6

    def wait_ns(self) -> float:
        """Wall minus CPU time of ``COMPUTE_STAGES``: what those stages'
        wall time holds of their thread not running."""
        return sum(
            off_cpu_ns(self.ns[name], self.timed_ns[name], self.cpu_ns[name])
            for name in COMPUTE_STAGES
        )

    def counters(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for name, total in self.ns.items():
            out[f"stage_{name}_ms"] = round(total / 1e6, 3)
            out[f"stage_{name}_n"] = self.n[name]
        for name, total in self.cpu_ns.items():
            out[f"stage_{name}_cpu_ms"] = round(total / 1e6, 3)
            out[f"stage_{name}_timed_ms"] = round(self.timed_ns[name] / 1e6, 3)
        # the late wake-ups of stage_loop_stall_ms by class, and the part
        # of them in which neither served thread ran
        for kind, total in self.stall_ns.items():
            out[f"loop_stall_{kind}_ms"] = round(total / 1e6, 3)
        out["loop_stopped_ms"] = round(
            (self.stall_ns["runq"] + self.stall_ns["blocked"]) / 1e6, 3
        )
        # what no stage names, on both threads: of the step, and (where a
        # selector reports the loop's turns) of the turns closed so far
        out["step_unnamed_ms"] = round(
            (self.ns["step"] - sum(self.ns[name] for name in STEP_NAMED_STAGES)) / 1e6, 3
        )
        if self.loop_thread is not None:
            out["loop_turns"] = self.loop_turns
            out["loop_busy_ms"] = round(self.loop_busy_ns / 1e6, 3)
            out["loop_poll_wait_ms"] = round(self.loop_poll_wait_ns / 1e6, 3)
            out["loop_poll_ready_ms"] = round(self.loop_poll_ready_ns / 1e6, 3)
            out["loop_poll_ready_n"] = self.loop_poll_ready_n
            out["loop_unnamed_ms"] = round(
                (self.loop_busy_ns - self.loop_named_ns) / 1e6, 3
            )
        return out

    def dump(self, path: str) -> None:
        """The ring as JSON: ``spans`` rows in closing order, times in
        ns of ``time.monotonic_ns``; ``stalls``, the kept late wake-ups of
        the loop in the order they happened, each with its two ends, its
        class, what the interval from the wake-up before it cost
        (``spent``, the fields of :class:`AccountSample`) and its own
        ``spans``."""
        self.settle_stalls()
        stalls = sorted((stall for _, _, stall in self._stalls), key=lambda s: s["t0_ns"])
        with open(path, "w") as fh:
            json.dump(
                {
                    "clock": "monotonic_ns",
                    "columns": [
                        "name", "t0_ns", "t1_ns", "round", "thread", "parent", "read_rows",
                    ],
                    "spans": list(self.ring),
                    "stalls": stalls,
                },
                fh,
            )


class TimedSelector(selectors.DefaultSelector):
    """The selector of a loop whose thread accounts for its time: each
    ``select`` reads the recorder's clock before and after the call and
    hands both to :meth:`StageRecorder.turn`.  ``bin/server`` makes its
    loop over one (``asyncio.SelectorEventLoop(selector)``) and gives it
    to the ``DeviceRuntime``, which sets :attr:`recorder`; until then,
    and on any other loop, it is the selector it extends."""

    recorder: Optional[StageRecorder] = None

    def select(self, timeout=None):
        recorder = self.recorder
        if recorder is None:
            return super().select(timeout)
        called = recorder.clock()
        events = super().select(timeout)
        # asyncio asks with timeout 0 whenever callbacks are ready
        recorder.turn(called, recorder.clock(), timeout is not None and timeout <= 0)
        return events
