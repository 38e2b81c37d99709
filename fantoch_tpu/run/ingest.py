"""Adaptive ingest batching for the serving edge.

A round's cost is mostly fixed: on the chip's own host ``enqueue`` +
``fetch`` take 12-15 ms of a 19-25 ms open-loop round whatever the
round carries (PERF.md section 5), so a serving loop that dispatches
the instant anything is queued pays that per trickle, not per batch.
This module is the accumulate-fuse-dispatch-lazily discipline of the
GraphBLAS nonblocking-execution line (PAPERS.md) applied to that edge,
shared by every serving surface
(``DeviceRuntime._driver_task``, the process runner's executor pools,
the sim's open-loop arrivals, and ``OrderingPool`` shard rounds):

* :class:`AdaptiveIngestBatcher` — hold queued submissions until a
  **size target** or a **deadline budget** fills.  The size target
  tracks the recent queue-arrival rate (EWMA): the expected number of
  arrivals inside one deadline window, so under saturation rounds go
  out full and under a trickle the target collapses to 1 and nothing
  waits.  The deadline bounds the latency a queued command can pay to
  batching.  An **idle-system fast path** releases a lone closed-loop
  command immediately — sync latency never regresses.
* :class:`ChainAutoTuner` — pick S, the serving rounds fused per device
  dispatch (the batches of one ``PipelineCore.serve``), from the measured per-round
  host dispatch overhead vs in-dispatch device time (the PR 6 busy/span
  counters): grow S while the dispatch round-trip still dominates a
  round, shrink once it is amortized, clamp at
  ``Config.serving_chain_max``.

Each tuning value has one home: a ``Config`` field that one CLI flag
sets, read here with the module's default where the field is unset.

Time is injected (float milliseconds): the run layer passes a monotonic
wall clock, the sim its virtual clock — the batcher itself never reads
a clock, which is what makes the sim wire-through deterministic
(same-seed byte-identical traces with the batcher on).
"""

from __future__ import annotations

import math
from typing import Any, List, Optional, Sequence, Tuple

# the default latency budget a queued command may pay to batching: a
# tenth of the 19-25 ms an open-loop round takes on the chip's own host
# (PERF.md section 5), and small against any cross-region commit
DEFAULT_INGEST_DEADLINE_MS = 2.0
# chain-length ceiling for the auto-tuner: 8 rounds per dispatch already
# cuts per-round dispatch overhead 8x while keeping result lag bounded
DEFAULT_SERVING_CHAIN_MAX = 8


def resolve_ingest_deadline_ms(config: Any) -> float:
    """``Config.ingest_deadline_ms``, or 2 ms where the field is unset.
    0 is a valid value: batching off, release immediately.  Callers that
    stay immediate unless a deadline was asked for (the sim, the host
    executor pools) test ``config.ingest_deadline_ms is not None``."""
    deadline = config.ingest_deadline_ms
    return DEFAULT_INGEST_DEADLINE_MS if deadline is None else float(deadline)


def resolve_serving_chain_max(config: Any) -> int:
    """``Config.serving_chain_max``, or 8 where the field is unset.  1
    disables chaining: every dispatch carries one round."""
    chain_max = config.serving_chain_max
    return DEFAULT_SERVING_CHAIN_MAX if chain_max is None else chain_max


class AdaptiveIngestBatcher:
    """Release-gating for one serving queue: size target or deadline.

    The caller owns the queue; the batcher only decides *when* to
    release.  Protocol per iteration: ``note_arrivals(now_ms, n)`` as
    submissions land, then ``poll(now_ms, queued, idle_system)`` —
    ``(True, None)`` means release everything queued now,
    ``(False, wait_ms)`` means hold for up to ``wait_ms`` more (or until
    more arrivals make the size target), ``(False, None)`` means the
    queue is empty.  After a release, ``note_release(now_ms, rows)``
    closes the window and tallies the cause.

    Release causes:

    * **fast** — ``idle_system`` (nothing in flight anywhere): a lone
      closed-loop command dispatches immediately, whatever the EWMA
      says.  This is the sync-latency guarantee.
    * **size** — ``queued >= target`` where ``target`` is the expected
      arrivals per deadline window, ``ceil(ewma_rate * deadline)``
      clamped to ``[1, max_target]`` (or the fixed ``--ingest-target``
      override).  A cold EWMA targets 1, so batching only engages once
      sustained load is *measured*.
    * **deadline** — the oldest queued command has waited the full
      budget.

    A gap longer than ~8 deadline windows hard-resets the EWMA instead
    of decaying it: an idle period ends the throughput regime, and the
    first command after it must not inherit a stale high target.
    """

    __slots__ = (
        "deadline_ms", "max_target", "fixed_target", "_alpha",
        "_rate_per_ms", "_accum", "_last_arrival_ms", "_window_start",
        "_cause", "arrivals", "releases", "released_rows",
        "releases_fast", "releases_size", "releases_deadline",
    )

    def __init__(
        self,
        deadline_ms: float,
        max_target: int,
        fixed_target: Optional[int] = None,
        alpha: float = 0.2,
    ):
        assert deadline_ms >= 0 and max_target >= 1
        assert fixed_target is None or fixed_target >= 1
        self.deadline_ms = float(deadline_ms)
        self.max_target = int(max_target)
        self.fixed_target = fixed_target
        self._alpha = float(alpha)
        self._rate_per_ms = 0.0  # EWMA arrivals per millisecond
        self._accum = 0.0  # arrivals recorded at _last_arrival_ms
        self._last_arrival_ms: Optional[float] = None
        self._window_start: Optional[float] = None  # oldest unreleased wait
        self._cause: Optional[str] = None
        self.arrivals = 0
        self.releases = 0
        self.released_rows = 0
        self.releases_fast = 0
        self.releases_size = 0
        self.releases_deadline = 0

    def note_arrivals(self, now_ms: float, n: int = 1) -> None:
        """Fold ``n`` submissions arriving at ``now_ms`` into the EWMA
        and open the deadline window if it is not already open."""
        if n <= 0:
            return
        self.arrivals += n
        if self._window_start is None:
            self._window_start = now_ms
        last = self._last_arrival_ms
        self._last_arrival_ms = now_ms
        if last is None:
            self._accum = float(n)
            return
        dt = now_ms - last
        if dt <= 0.0:
            self._accum += n
            return
        inst = self._accum / dt
        self._accum = float(n)
        idle_bound = max(self.deadline_ms, 0.125) * 8.0
        if dt >= idle_bound:
            # the throughput regime ended across the gap: snap, don't
            # decay — a closed-loop client must see target 1 at once
            self._rate_per_ms = inst
        else:
            self._rate_per_ms += self._alpha * (inst - self._rate_per_ms)

    def rate_per_s(self) -> float:
        return self._rate_per_ms * 1000.0

    def target(self) -> int:
        """The current size target (rows that trigger a release)."""
        if self.fixed_target is not None:
            return min(self.fixed_target, self.max_target)
        if self.deadline_ms <= 0:
            return 1
        expected = math.ceil(self._rate_per_ms * self.deadline_ms)
        return max(1, min(int(expected), self.max_target))

    def poll(
        self, now_ms: float, queued: int, idle_system: bool = False
    ) -> Tuple[bool, Optional[float]]:
        """``(release, wait_ms)`` for ``queued`` pending submissions at
        ``now_ms``; ``idle_system`` is the fast-path witness (nothing in
        flight downstream — the queued command is alone in the system)."""
        if queued <= 0:
            self._window_start = None
            return (False, None)
        if self._window_start is None:
            # arrivals the caller never noted individually (e.g. drained
            # from an inner queue): the window opens at first sight
            self._window_start = now_ms
        if self.deadline_ms <= 0:
            self._cause = "size"
            return (True, None)
        if idle_system:
            self._cause = "fast"
            return (True, None)
        if queued >= self.target():
            self._cause = "size"
            return (True, None)
        waited = now_ms - self._window_start
        if waited >= self.deadline_ms:
            self._cause = "deadline"
            return (True, None)
        return (False, self.deadline_ms - waited)

    def note_release(self, now_ms: float, rows: int) -> None:
        """Tally one release of ``rows`` commands and close the window
        (the next arrival or poll reopens it)."""
        self.releases += 1
        self.released_rows += rows
        cause = self._cause or "size"
        if cause == "fast":
            self.releases_fast += 1
        elif cause == "deadline":
            self.releases_deadline += 1
        else:
            self.releases_size += 1
        self._cause = None
        self._window_start = None

    def counters(self) -> dict:
        """Tallies for the metrics snapshot (``ingest_target`` and
        ``ingest_rate_per_s`` are gauges, the rest monotone)."""
        return {
            "ingest_arrivals": self.arrivals,
            "ingest_releases": self.releases,
            "ingest_released_rows": self.released_rows,
            "ingest_releases_fast": self.releases_fast,
            "ingest_releases_size": self.releases_size,
            "ingest_releases_deadline": self.releases_deadline,
            "ingest_target": self.target(),
            "ingest_rate_per_s": round(self.rate_per_s(), 1),
        }


class ChainAutoTuner:
    """Auto-tuned S for chained serving (the batches of one ``serve``).

    Starts at S=1 and adjusts from deltas of the shared PipelineCore
    counters: per-round host dispatch overhead
    (``dispatch_wall_ms / rounds``) vs per-round in-dispatch device time
    (``busy_ms / rounds``).  While the dispatch call still costs more
    than ``grow_frac`` of a round's device time, fusing more rounds per
    dispatch keeps paying — S doubles (fast convergence from cold).
    Once overhead falls under ``shrink_frac`` the chain HALVES
    (hysteresis between the two bands keeps S stable).  S moves on a
    strict pow2 schedule — double up, halve down, ceiling at the pow2
    floor of ``chain_max`` — because each chain length is a program of
    its own: a decrement schedule would bake every value in
    ``[1, chain_max]`` into a distinct compiled signature (the compile
    wall), while pow2 bounds the set at O(log chain_max) programs, few
    enough that a server loads every one of them before it serves
    (:meth:`ladder`; :meth:`limit_to` keeps the tuner off a length that
    could not be made ready).
    Observations under ``min_dispatches`` new dispatches are deferred so
    one jittery round cannot thrash S.
    """

    __slots__ = (
        "chain", "chain_max", "grow_frac", "shrink_frac",
        "min_dispatches", "adjustments", "_last",
    )

    def __init__(
        self,
        chain_max: int,
        grow_frac: float = 0.25,
        shrink_frac: float = 0.05,
        min_dispatches: int = 8,
    ):
        assert chain_max >= 1
        self.chain = 1
        # pow2 floor: the largest chain the tuner will emit.  chain_max
        # itself may be arbitrary (config/env), but every EMITTED S must
        # come from the pow2 ladder (see the class docstring)
        self.chain_max = 1
        while self.chain_max * 2 <= int(chain_max):
            self.chain_max *= 2
        self.grow_frac = float(grow_frac)
        self.shrink_frac = float(shrink_frac)
        self.min_dispatches = int(min_dispatches)
        self.adjustments = 0
        self._last: Optional[Tuple[float, float, float, float]] = None

    def ladder(self) -> List[int]:
        """Every chain length the tuner may emit: the powers of two up
        to ``chain_max``."""
        lengths = [1]
        while lengths[-1] * 2 <= self.chain_max:
            lengths.append(lengths[-1] * 2)
        return lengths

    def limit_to(self, ready: Sequence[int]) -> None:
        """Keep the tuner on the lengths of ``ready``: the ceiling drops
        to the top of the ladder's unbroken run of ready lengths."""
        top = 1
        for length in self.ladder()[1:]:
            if length not in ready:
                break
            top = length
        self.chain_max = top
        self.chain = min(self.chain, top)

    def observe(
        self,
        dispatches: float,
        dispatch_wall_ms: float,
        busy_ms: float,
        rounds: float,
    ) -> int:
        """Feed cumulative counters; returns the (possibly adjusted)
        chain length.  Call as often as convenient — the tuner
        rate-limits itself by dispatch count."""
        if self._last is None:
            self._last = (dispatches, dispatch_wall_ms, busy_ms, rounds)
            return self.chain
        d_disp = dispatches - self._last[0]
        if d_disp < self.min_dispatches:
            return self.chain
        d_wall = dispatch_wall_ms - self._last[1]
        d_busy = busy_ms - self._last[2]
        d_rounds = rounds - self._last[3]
        self._last = (dispatches, dispatch_wall_ms, busy_ms, rounds)
        if d_rounds <= 0 or d_busy <= 0:
            return self.chain
        ratio = (d_wall / d_rounds) / (d_busy / d_rounds)
        if ratio > self.grow_frac and self.chain < self.chain_max:
            self.chain = min(self.chain * 2, self.chain_max)
            self.adjustments += 1
        elif ratio < self.shrink_frac and self.chain > 1:
            # halve, not decrement: stay on the pow2 ladder so shrink
            # never mints a fresh compiled chain program
            self.chain //= 2
            self.adjustments += 1
        return self.chain


def plan_ingest_releases(
    arrival_ms: Sequence[float], batcher: AdaptiveIngestBatcher
) -> List[Tuple[float, int, int]]:
    """Replay a sorted arrival-time column through a batcher, returning
    the release plan ``[(release_ms, start, end)]`` over half-open index
    groups — the offline coalescing used by ``OrderingPool`` shard
    rounds (and the unit tests' oracle for the online loops).  A
    deadline that expires between two arrivals releases at the deadline
    instant, without the later arrival; the tail releases at its
    window's deadline."""
    out: List[Tuple[float, int, int]] = []
    start = 0
    for i, t in enumerate(arrival_ms):
        pending = i - start
        if pending:
            opened = batcher._window_start
            deadline_at = (
                None if opened is None or batcher.deadline_ms <= 0
                else opened + batcher.deadline_ms
            )
            if deadline_at is not None and t >= deadline_at:
                batcher.poll(deadline_at, pending)
                # a deadline release by construction; the poll at the
                # computed instant can land 1 ulp short of the budget
                # (opened + d - opened < d in floats), so the cause is
                # pinned rather than trusted to the comparison
                batcher._cause = "deadline"
                batcher.note_release(deadline_at, pending)
                out.append((deadline_at, start, i))
                start = i
        batcher.note_arrivals(t, 1)
        pending = i + 1 - start
        release, _wait = batcher.poll(t, pending)
        if release:
            batcher.note_release(t, pending)
            out.append((t, start, i + 1))
            start = i + 1
    n = len(arrival_ms)
    if start < n:
        opened = batcher._window_start
        deadline_tail = opened is not None and batcher.deadline_ms > 0
        t = (
            opened + batcher.deadline_ms if deadline_tail
            else arrival_ms[n - 1]
        )
        batcher.poll(t, n - start)
        if deadline_tail:
            # pinned for the same 1-ulp reason as the in-loop release
            batcher._cause = "deadline"
        batcher.note_release(t, n - start)
        out.append((t, start, n))
    return out
