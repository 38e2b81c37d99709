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
when it stops.
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

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
    # before the first round: a chain program compiled or loaded
    # (``precompile_chains``; the span's round is the chain length)
    "precompile",
)
SPAN_RING = 65536  # closed spans kept for the dump (~40 min of open-loop rounds)


class _Span:
    """One open stage: ``with recorder.span(name, round)``.  ``t0`` and
    ``t1`` (``time.monotonic_ns``) stay readable after the block."""

    __slots__ = ("_rec", "name", "round", "parent", "_cpu0", "_note", "t0", "t1")

    def __init__(self, rec, name, round_id, parent, cpu):
        self._rec = rec
        self.name = name
        self.round = round_id
        self.parent = parent
        self._cpu0 = 0 if cpu else None  # thread CPU time at entry, where asked for
        self._note = rec._annotation("fantoch/" + name, round=round_id)
        self.t0 = self.t1 = 0

    def __enter__(self):
        stack = self._rec._stack()
        if self.parent is None and stack:
            self.parent = stack[-1]
        stack.append(self.name)
        self._note.__enter__()
        if self._cpu0 is not None:
            self._cpu0 = time.thread_time_ns()
        self.t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        self.t1 = time.monotonic_ns()
        rec = self._rec
        if self._cpu0 is not None:
            rec.cpu_ns[self.name] = (
                rec.cpu_ns.get(self.name, 0) + time.thread_time_ns() - self._cpu0
            )
        self._note.__exit__(*exc)
        rec._stack().pop()
        rec.record(self.name, self.t0, self.t1, self.round, self.parent)
        return False


class StageRecorder:
    """Where a served round's host time goes.  One recorder per driver
    (``PipelineCore._init_pipeline`` creates it, ``DeviceRuntime`` shares
    it), three sinks per span: cumulative wall time and count per stage
    (:meth:`counters`, folded into the metrics snapshot), a
    ``jax.profiler.TraceAnnotation`` named ``fantoch/<stage>`` so the
    span lands in a profiler capture on the clock of the device planes
    (near free while no capture runs), and a bounded ring of closed spans
    ``(name, t0_ns, t1_ns, round, thread, parent)`` for :meth:`dump`.

    The clock is ``time.monotonic_ns``, the one load generators stamp
    ``due`` / ``sent`` / ``acked`` with.  Spans open on the event loop
    and on the pool thread that runs the step; each stage is written by
    one thread at a time, so no lock is taken."""

    clock = staticmethod(time.monotonic_ns)

    def __init__(self, ring: int = SPAN_RING):
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self.ns: Dict[str, int] = dict.fromkeys(ROUND_STAGES, 0)
        self.n: Dict[str, int] = dict.fromkeys(ROUND_STAGES, 0)
        self.cpu_ns: Dict[str, int] = {"step": 0}
        self.ring: Deque[Tuple[str, int, int, int, int, Optional[str]]] = deque(
            maxlen=ring
        )
        self._local = threading.local()

    def _stack(self) -> List[str]:
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def span(self, name: str, round_id: int = 0, parent: Optional[str] = None,
             cpu: bool = False) -> _Span:
        """Context manager for one stage of round ``round_id``.  The
        parent is the innermost span open on this thread unless named
        (a span whose parent runs on the other thread names it);
        ``cpu`` also sums the thread's CPU time (``stage_<name>_cpu_ms``)."""
        return _Span(self, name, round_id, parent, cpu)

    def record(self, name: str, t0_ns: int, t1_ns: int, round_id: int = 0,
               parent: Optional[str] = None) -> None:
        """A closed interval whose ends were read elsewhere (a hand-off
        between threads, a late wake-up): counters and ring, no
        annotation."""
        self.ns[name] = self.ns.get(name, 0) + t1_ns - t0_ns
        self.n[name] = self.n.get(name, 0) + 1
        self.ring.append(
            (name, t0_ns, t1_ns, round_id, threading.get_ident(), parent)
        )

    def ms(self, *names: str) -> float:
        """Cumulative wall milliseconds of the named stages."""
        return sum(self.ns.get(name, 0) for name in names) / 1e6

    def counters(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for name, total in self.ns.items():
            out[f"stage_{name}_ms"] = round(total / 1e6, 3)
            out[f"stage_{name}_n"] = self.n[name]
        for name, total in self.cpu_ns.items():
            out[f"stage_{name}_cpu_ms"] = round(total / 1e6, 3)
        return out

    def dump(self, path: str) -> None:
        """The ring as JSON: ``spans`` rows in closing order, times in
        ns of ``time.monotonic_ns``."""
        with open(path, "w") as fh:
            json.dump(
                {
                    "clock": "monotonic_ns",
                    "columns": ["name", "t0_ns", "t1_ns", "round", "thread", "parent"],
                    "spans": list(self.ring),
                },
                fh,
            )
