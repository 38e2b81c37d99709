"""The shared dispatch/drain pipeline core for device-resident serving.

Every device serving plane pays the same round shape: assemble a batch on
the host, dispatch one fused device program (async), fetch its outputs
(blocking), emit results.  The host bounds every cell (the device idles
87-99% of a saturated capture), and the blocking fetch is host time in
which nobody works: the step's thread waits for the device and the copy
back, and the loop's thread has nothing to read while the step holds the
sockets, 1.2-11.6 ms a round, 4-16% of a saturated window (PERF.md
section 6, PR 58).  So dispatch may run N rounds ahead of drain: a round
left in flight is fetched when its results are next wanted, its copy to
the host follows its program on the device (the drivers' ``_enqueue``
starts it), and the host delivers, reads and collects meanwhile, the
nonblocking-execution move of the GraphBLAS lazy-evaluation line
(PAPERS.md) applied to consensus serving.  The price is a step of
delivery lag, so who engages it decides by what a dispatch carries
(``DeviceRuntime._driver_task``, run/device_runner.py): a throughput
round, not an open loop's.

This module is the one place that machinery lives (the ROADMAP item-5
refactor seam): drivers implement a ``dispatch(batch) -> token`` /
``drain(token) -> results`` split and inherit

  * :class:`PipelineCore` — a depth-K in-flight ring of round tokens
    behind one entry (``serve``, its one-round form ``step``, and
    ``flush_pipeline`` for the tail), the stage
    recorder whose spans split a dispatch into ``assemble`` / ``enqueue``
    and a drain into ``fetch`` / ``execute``
    (observability/device.py ``StageRecorder``), and the device
    busy/idle instrument (``device_idle_frac``);
  * :class:`IngestRing` — K+1 pre-staged host staging slots for batch
    assembly, each one buffer with a round's columns as views of it,
    cycled round-robin so the buffer a still-in-flight round reads (jax
    may alias host numpy zero-copy on the CPU backend) is never
    rewritten under it;
  * :func:`packed_round` — a round function wrapped so that a dispatch
    crosses to the device once and comes back once: the staged columns
    go up as that one buffer and what a drain reads comes down as one
    array (:class:`PackedOutput`).

Depth semantics: ``pipeline_depth`` is the maximum number of
dispatched-but-undrained rounds ``serve(..., overlap=True)`` leaves in
flight, i.e. the delivery lag in rounds.  Depth 1 is the classic
double-buffered overlap; deeper pipelines amortize jittery transfer
latency at the cost of K rounds of result lag.  ``serve`` without overlap
(and so ``step``) always flushes first, so mixing the two is safe.

Donation discipline (the PR 4 XLA-ownership rule): the pipeline never
donates host staging buffers — only the drivers' device-resident *state*
is donated, and state rebuilds go through ``jnp.array`` copies.  Staging
columns are plain (non-donated) inputs, so ring reuse after drain is the
only aliasing hazard, and the ring's size (depth + 1) closes it.
"""

from __future__ import annotations

from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

DEFAULT_PIPELINE_DEPTH = 1


def resolve_pipeline_depth(config: Any) -> int:
    """``Config.serving_pipeline_depth``, or 1 (the classic one-deep
    overlap) where the field is unset.  The range check is
    ``Config.__post_init__``'s."""
    depth = config.serving_pipeline_depth
    return DEFAULT_PIPELINE_DEPTH if depth is None else depth


def packed_shape(specs) -> Tuple[int, int]:
    """The shape of the one ``int32`` array a round's staged columns
    make, ``(rows, B)``: a column of shape ``(B,)`` is a row of it, one of
    shape ``(B, k)`` is ``k`` rows (its transpose), in spec order."""
    (batch,) = {shape[0] for _name, shape, _dtype, _fill in specs}
    rows = sum(shape[1] if len(shape) == 2 else 1 for _name, shape, _dtype, _fill in specs)
    return rows, batch


def packed_columns(packed, specs) -> tuple:
    """The columns ``specs`` names as views of ``packed``
    (``int32[..., rows, B]``, :func:`packed_shape`; the leading axes are
    kept: the rounds of a chain), in spec order.  Static slices, so the
    host's staging buffer (numpy) and the device's program (a traced
    array) read one layout; every view is ``int32``, a ``bool`` column
    as 0/1."""
    columns, row = [], 0
    for _name, shape, dtype, _fill in specs:
        assert np.dtype(dtype) in (np.int32, np.bool_), "a staged column is int32 or bool"
        if len(shape) == 2:
            columns.append(packed[..., row:row + shape[1], :].swapaxes(-1, -2))
            row += shape[1]
        else:
            columns.append(packed[..., row, :])
            row += 1
    return tuple(columns)


class StagedColumns(tuple):
    """One dispatch's staged columns, in spec order, filled with their
    fill values: views of ``packed``, the one C-contiguous ``int32``
    buffer that goes to the device (``lead``: the rounds of a chain, a
    leading axis of the buffer and of every column)."""

    def __new__(cls, specs, lead: tuple = ()):
        packed = np.empty(lead + packed_shape(specs), dtype=np.int32)
        self = super().__new__(cls, packed_columns(packed, specs))
        self.packed = packed
        self.fills = tuple(fill for _name, _shape, _dtype, fill in specs)
        self.reset()
        return self

    def reset(self) -> None:
        for column, fill in zip(self, self.fills):
            column.fill(fill)


class PackedOutput:
    """How a round's output tuple lies in the one ``int32`` array a drain
    fetches: the fields a drain reads, each flattened, one after the
    other along the last axis (a mask as 0/1; a scalar is one entry),
    under the leading axes they share (none for a round, ``S`` for a
    chain's stacked rounds).  Written once, when the program that packs
    is traced (:func:`packed_round`); read at every drain."""

    __slots__ = ("type", "fields")

    def __init__(self):
        self.type = None  # the round's own output tuple
        # (offset, size, shape, is a mask) a field; None one left on the device
        self.fields: Optional[List[Optional[Tuple[int, int, tuple, bool]]]] = None

    def pack(self, out, lead: int, kept: Sequence[str]):
        """``out`` (a NamedTuple of traced arrays with ``lead`` leading
        axes in common) as ``(packed, rest)``: the one array, and ``out``
        with the fields ``kept`` names left as they are and None
        elsewhere."""
        import jax.numpy as jnp

        self.type, self.fields = type(out), []
        flat, offset = [], 0
        for name, leaf in zip(out._fields, out):
            if name in kept:
                self.fields.append(None)
                continue
            shape = leaf.shape[lead:]
            size = int(np.prod(shape, dtype=np.int64))
            self.fields.append((offset, size, shape, leaf.dtype == np.bool_))
            flat.append(leaf.astype(jnp.int32).reshape(leaf.shape[:lead] + (size,)))
            offset += size
        rest = self.type._make(
            leaf if field is None else None for leaf, field in zip(out, self.fields)
        )
        return jnp.concatenate(flat, axis=-1), rest

    def unpack(self, packed: np.ndarray):
        """The output tuple again, from the fetched array: numpy views
        of it (a mask a ``bool`` copy), None where the field stayed on
        the device."""
        lead = packed.shape[:-1]
        values = []
        for field in self.fields:
            if field is None:
                values.append(None)
                continue
            offset, size, shape, mask = field
            value = packed[..., offset:offset + size].reshape(lead + shape)
            values.append(value != 0 if mask else value)
        return self.type._make(values)


def packed_round(round_fn, specs, kept: Sequence[str], out_sharding):
    """``round_fn(state, *columns) -> (state, out)`` as ``(state, packed)
    -> (state, packed_out, rest)``, and the :class:`PackedOutput` that
    reads ``packed_out`` back: the columns ``specs`` names are unpacked
    from the one array by static slices (:func:`packed_columns`; a
    ``bool`` column from its 0/1) before the round, and what a drain
    reads of ``out`` is packed after it (``rest``: the fields ``kept``
    names, device leaves nobody fetches by default), all inside whatever
    ``jax.jit`` the caller puts around it.  Leading axes of ``packed``
    pass through to ``round_fn``'s columns and are taken to lead its
    outputs (a chain's ``lax.scan``).  ``out_sharding``: where the packed
    output is held to (replicated, as its fields are)."""
    layout = PackedOutput()

    def program(state, packed):
        import jax

        columns = [
            column != 0 if np.dtype(dtype) == np.bool_ else column
            for column, (_name, _shape, dtype, _fill) in zip(
                packed_columns(packed, specs), specs
            )
        ]
        state, out = round_fn(state, *columns)
        packed_out, rest = layout.pack(out, packed.ndim - 2, kept)
        return state, jax.lax.with_sharding_constraint(packed_out, out_sharding), rest

    return program, layout


class IngestRing:
    """K+1 pre-staged host staging slots, cycled round-robin.

    Each slot is one C-contiguous ``int32`` buffer (what a dispatch hands
    the device, one array) and the named columns of a round (the key /
    src / seq staging arrays; a ``bool`` column staged as 0/1) as views
    of it (:class:`StagedColumns`).  ``acquire()`` resets the next slot's
    columns to their fill values in place and returns them — no per-round
    allocation, and a slot is only revisited after ``slots`` more
    acquires, which the pipeline guarantees is after its round drained
    (rounds in flight <= depth < slots).
    """

    __slots__ = ("_slots", "_next")

    def __init__(
        self, slots: int, specs: Sequence[Tuple[str, tuple, Any, Any]]
    ):
        """``specs``: (name, shape, dtype, fill) per staging column."""
        assert slots >= 1
        self._slots = [StagedColumns(specs) for _ in range(slots)]
        self._next = 0

    @property
    def slots(self) -> int:
        return len(self._slots)

    def acquire(self) -> StagedColumns:
        """The next slot's columns, reset in place to their fill values
        (in spec order); the buffer they alias is their ``packed``."""
        staged = self._slots[self._next]
        self._next = (self._next + 1) % len(self._slots)
        staged.reset()
        return staged


class BoundedSubmitRing:
    """Bounded FIFO of pending submissions feeding a serving loop — the
    device runtime's admission edge (run/backpressure.py plane).

    What it holds is *runs*: the entries one push brought (the commands
    a session admitted from one socket read), in their order, with the
    push's one arrival time beside them.  What it counts is entries:
    ``len``, ``capacity``, ``depth_hwm`` and the refusal read commands,
    however they were pushed.  ``try_extend`` pushes a run, all of it or
    none where it would pass ``capacity`` (the caller replies with a
    typed Overloaded frame instead of queueing without bound), and
    ``take`` hands it back by slices; ``try_push`` / ``popleft`` are the
    one-entry forms (a run of one, a slice of one).  The depth
    high-watermark rides the ring for the metrics snapshot, and the
    admission edge that refuses a command tallies it on ``sheds`` (the
    ring only *checks* the bound — counting belongs to whoever owns the
    reply, so one shed is never counted twice).  ``capacity=None`` keeps
    the legacy unbounded behavior.
    """

    __slots__ = ("capacity", "depth_hwm", "sheds", "_runs", "_depth")

    def __init__(self, capacity: Optional[int] = None):
        assert capacity is None or capacity >= 1
        self.capacity = capacity
        self.depth_hwm = 0
        self.sheds = 0
        # (entries, arrival time) a run; the first may be the tail of
        # one a ``take`` split
        self._runs: Deque[Tuple[List[Any], float]] = deque()
        self._depth = 0

    def try_push(self, item: Any, at_ms: float = 0.0) -> bool:
        return self.try_extend([item], at_ms)

    def try_extend(self, items: List[Any], at_ms: float = 0.0) -> bool:
        """All of ``items`` in their order, as one run that arrived at
        ``at_ms`` (the ring keeps the list: the caller is done with it),
        or none of them where they would pass ``capacity``."""
        depth = self._depth + len(items)
        if self.capacity is not None and depth > self.capacity:
            return False
        if items:
            self._runs.append((items, at_ms))
            self._depth = depth
            if depth > self.depth_hwm:
                self.depth_hwm = depth
        return True

    def take(self, limit: int) -> Tuple[List[Any], float]:
        """The next slice and its arrival time: the first run whole
        where it has at most ``limit`` (>= 1) entries, else its first
        ``limit``, the rest staying first in the ring under the same
        arrival time.  The ring must not be empty."""
        run = self._runs[0]
        items, at_ms = run
        if len(items) <= limit:
            self._runs.popleft()
            self._depth -= len(items)
            return run
        self._runs[0] = (items[limit:], at_ms)
        self._depth -= limit
        return items[:limit], at_ms

    def popleft(self) -> Any:
        return self.take(1)[0][0]

    def __len__(self) -> int:
        return self._depth

    def __bool__(self) -> bool:
        return self._depth > 0

    def stats(self) -> Dict[str, float]:
        return {
            "depth": self._depth,
            "depth_hwm": self.depth_hwm,
            "capacity": self.capacity if self.capacity is not None else 0,
            "sheds": self.sheds,
        }


class PipelineCore:
    """Depth-K dispatch/drain pipelining plus the per-dispatch counters
    every device serving driver shares.

    Subclasses implement the halves of a dispatch, ``_assemble(batch) ->
    staged`` (host columns, registry) and ``_enqueue(staged) -> token``
    (async: must not block on device completion), and the tail of a
    drain, ``_execute(token, fetched) -> results``; :meth:`dispatch` and
    :meth:`drain` put the stage spans around them once for every driver.
    (A subclass may still override ``dispatch`` / ``drain`` whole, as the
    host-only test doubles do; it then records no ``assemble`` /
    ``enqueue`` / ``execute`` time.)  ``_pipeline_flush_needed`` gates
    dispatches that would rebase state an in-flight round still
    references (sequence/clock/gid windows) — the pipeline retires every
    outstanding round first.

    Required subclass attribute: ``batch_size`` (the compiled per-round
    row capacity, read by the occupancy counters) must be set before
    ``_init_pipeline``.  ``seq_epochs`` (window-advance tally) and
    ``slot_epochs`` (the leader round's slot-space rebases) are reported
    when present, 0 otherwise.
    """

    def _init_pipeline(self) -> None:
        self.pipeline_depth = DEFAULT_PIPELINE_DEPTH
        assert hasattr(self, "batch_size"), (
            "PipelineCore subclasses must set batch_size before "
            "_init_pipeline"
        )
        from fantoch_tpu.observability.device import StageRecorder

        self._ring: Optional[IngestRing] = None  # lazy staging ring
        # per-dispatch observability (observability/device.py):
        # dispatched_rows vs dispatched_capacity is the batch occupancy;
        # the stage recorder splits a round's host time (assemble /
        # enqueue / fetch / execute here, the loop's stages in
        # DeviceRuntime) — dispatch/drain/fetch wall-ms are reads of it
        self.stages = StageRecorder()
        self.dispatches = 0
        # arrays that crossed to the device and back for the dispatches:
        # those handed to ``device_put`` (the drivers count theirs) and
        # the leaves ``_fetch`` handed to ``device_get``; two a dispatch
        self.transfers = 0
        self.dispatched_rows = 0
        self.dispatched_capacity = 0
        self.pipelined_rounds = 0  # rounds dispatched over an in-flight one
        # dispatches made by ``serve`` calls that ran with the overlap on,
        # whether or not another round was in flight (the first half of a
        # closed loop after a quiet-ring retire is deferred all the same)
        self.overlapped_dispatches = 0
        self.chain_len = 1  # rounds the latest dispatch carried (gauge)
        # the round the spans on the stepping thread belong to: the
        # dispatch number while dispatching, the retired round's number
        # while draining (a pipelined drain is not the round dispatched)
        self._span_round = 0
        # the in-flight ring: dispatched-but-undrained (round id, token)
        # pairs, FIFO
        self._inflight: Deque[Tuple[int, Any]] = deque()
        # rounds dispatched and not yet entered drain — during a drain
        # this counts OTHER in-flight rounds (unlike has_outstanding,
        # which is False mid-flush even with round k+1 dispatched), so
        # rebase paths can assert nothing is in flight
        self._undrained = 0
        # like _undrained but in protocol ROUNDS (a chained token carries
        # S rounds per dispatch): the clock-window margins are per round
        self._undrained_rounds = 0
        # device busy/idle instrument: a busy window opens when a dispatch
        # leaves the host (device has work) and closes at the fetch that
        # retires the LAST in-flight round; span is first dispatch ->
        # last fetch.  idle = span - busy = wall the device sat waiting
        # on host assembly/emit — the number the pipeline exists to kill.
        # (the recorder's clock, ns)
        self._busy_t0: Optional[int] = None
        self._busy_ns = 0
        self._span_t0: Optional[int] = None
        self._span_end: Optional[int] = None

    @property
    def dispatch_wall_ms(self) -> float:
        """Host wall time inside dispatches (assembly + the jitted call
        returning)."""
        return self.stages.ms("assemble", "enqueue")

    @property
    def drain_wall_ms(self) -> float:
        """Host wall time inside drains: the blocking fetch, then the
        execution of what it brought."""
        return self.stages.ms("fetch", "execute")

    @property
    def fetch_wall_ms(self) -> float:
        """The blocking device->host wait inside drains."""
        return self.stages.ms("fetch")

    def _staging(self, *specs) -> StagedColumns:
        """The next pre-staged host staging slot for batch assembly (its
        columns, and as their ``packed`` the one buffer they alias):
        ``pipeline_depth + 1`` ring slots, so the buffer a
        still-in-flight round may alias zero-copy (the CPU backend) is
        never rewritten before that round drains."""
        slots = self.pipeline_depth + 1
        if self._ring is None or self._ring.slots < slots:
            self._ring = IngestRing(slots, specs)
        return self._ring.acquire()

    def reset_overlap_instrument(self) -> None:
        """Zero the busy/idle instrument (callers time a steady-state
        region after warm/compile rounds; requires nothing in flight so
        no busy window is open)."""
        assert self._undrained == 0, (
            "overlap-instrument reset with rounds in flight"
        )
        self._busy_t0 = self._span_t0 = self._span_end = None
        self._busy_ns = 0

    # --- the serving surface ---

    @property
    def has_outstanding(self) -> bool:
        """At least one dispatched-but-undrained pipelined round exists."""
        return bool(self._inflight)

    def serve(self, batches, overlap: bool = False) -> List[Any]:
        """Dispatch ``batches``, a round each, and return what was
        retired meanwhile, oldest round first.

        ``overlap=False``: everything in flight is retired first and each
        dispatch is drained at once, so the call returns its own rounds'
        results.  ``overlap=True``: dispatches are drained down to
        ``pipeline_depth`` only — results arrive up to that many
        dispatches late, in exchange for the device computing while the
        host assembles the next round and emits the last one's results —
        and ``flush_pipeline`` retires the tail.  Either way a dispatch
        that would rebase what a round in flight still refers to
        (``_pipeline_flush_needed``: once a sequence, clock, gid or slot
        window) retires every round in flight first."""
        results = [] if overlap else self.flush_pipeline()
        depth = self.pipeline_depth if overlap else 0
        for chain in self._dispatches(batches):
            rounds = len(chain)
            if self._inflight:
                # (a dispatch of several rounds is made only where no
                # rebase can land in it: ``_dispatches``)
                if rounds == 1 and self._pipeline_flush_needed(chain[0]):
                    results.extend(self.flush_pipeline())
                else:
                    self.pipelined_rounds += rounds
            # a round is named by its dispatch's number (1-based)
            round_id = self._span_round = self.dispatches + 1
            if self._span_t0 is None:
                self._span_t0 = self.stages.clock()
            tok = self._dispatch_chain(chain)
            t1 = self.stages.clock()
            self.dispatches += 1
            if overlap:
                self.overlapped_dispatches += 1
            self.dispatched_rows += sum(map(len, chain))
            self.dispatched_capacity += rounds * self.batch_size
            self.chain_len = rounds
            self._undrained += 1
            self._undrained_rounds += rounds
            if self._busy_t0 is None:
                # the device has work from the moment the dispatch call
                # returns (the submit is async); host assembly before it
                # counts as idle, which is the point of the instrument
                self._busy_t0 = t1
            self._inflight.append((round_id, tok))
            while len(self._inflight) > depth:
                results.extend(self._drain_tracked(self._inflight.popleft()))
        return results

    def step(self, batch) -> List[Any]:
        """One synchronous round."""
        return self.serve([batch])

    def _dispatches(self, batches) -> List[Sequence[Any]]:
        """How ``batches`` become dispatches, the one thing a driver
        says: the rounds of each dispatch, in order.  Here one dispatch a
        batch; a driver with a program of several rounds
        (NewtDeviceDriver) makes one of the whole chain."""
        return [(batch,) for batch in batches]

    def _dispatch_chain(self, chain):
        """One dispatch of the rounds ``_dispatches`` put together: here
        always the one."""
        (batch,) = chain
        return self.dispatch(batch)

    # --- the halves of a round, under their stage spans ---

    def dispatch(self, batch):
        """Assemble + enqueue one device round (async — does not block
        on device completion); returns the round token for ``drain``."""
        return self._dispatch_halves(self._assemble, self._enqueue, batch)

    def _dispatch_halves(self, assemble, enqueue, work):
        """``assemble``: staging slot, window checks, the round's columns,
        the registry.  ``enqueue``: the columns handed to jax and the jitted
        call returning."""
        with self.stages.span("assemble", self._span_round):
            staged = assemble(work)
        with self.stages.span("enqueue", self._span_round):
            return enqueue(staged)

    def drain(self, tok):
        """Fetch one token's outputs (ONE blocking device->host
        transfer) and execute what they resolved."""
        fetched = self._fetch(self._token_outputs(tok))
        with self.stages.span("execute", self._span_round):
            return self._execute(tok, fetched)

    def _token_outputs(self, tok):
        """The device arrays of a token (drivers whose token carries
        more than the step's outputs override)."""
        return tok

    def flush_pipeline(self) -> List[Any]:
        """Drain every outstanding pipelined round, oldest first."""
        results: List[Any] = []
        while self._inflight:
            results.extend(self._drain_tracked(self._inflight.popleft()))
        return results

    # --- tracked drain plumbing ---

    def _drain_tracked(self, tracked):
        round_id, tok = tracked
        # inside drain, _undrained counts OTHER in-flight rounds
        self._undrained -= 1
        self._undrained_rounds -= self._token_rounds(tok)
        self._span_round = round_id  # the round retired, not the one dispatching
        return self.drain(tok)

    def _token_rounds(self, tok) -> int:
        """Protocol rounds one dispatch's token carries: a driver whose
        dispatch may carry several has its token say."""
        return 1

    def _fetch(self, out):
        """The ONE blocking fetch of a dispatch's outputs.  ``device_get``
        issues an async copy for every leaf before blocking, but each
        leaf is still a transfer and a wait of its own (``transfers``
        counts them), so a driver hands it one leaf: the array its
        program packed what a drain reads into
        (:func:`packed_round`).  Also the busy/idle bookkeeping point:
        when this fetch retires the last in-flight round, the device goes
        idle until the next dispatch."""
        import jax

        self.transfers += len(jax.tree_util.tree_leaves(out))
        with self.stages.span("fetch", self._span_round) as span:
            out = jax.device_get(out)
        t1 = span.t1
        if self._undrained == 0 and self._busy_t0 is not None:
            self._busy_ns += t1 - self._busy_t0
            self._busy_t0 = None
        self._span_end = t1
        return out

    def _pipeline_flush_needed(self, batch) -> bool:
        """True when the upcoming dispatch may trigger a rebase that must
        not happen with rounds in flight; drivers extend with their
        window triggers."""
        return False

    # --- the counters (metrics snapshots / bench rows) ---

    def device_counters(self) -> Dict[str, float]:
        """Per-dispatch tallies for the metrics snapshot / bench rows:
        occupancy = dispatched_rows / dispatched_capacity; busy/span give
        ``device_idle_frac`` — the fraction of the serving span the
        device sat idle waiting on the host (the pipelined loop's whole
        job is driving it toward 0)."""
        now = self.stages.clock()
        busy_ms = self._busy_ns / 1e6
        span_ms = 0.0
        if self._span_t0 is not None:
            span_end = self._span_end
            if self._busy_t0 is not None:
                # rounds still in flight: close the open windows at `now`
                # for a consistent mid-run snapshot
                busy_ms += (now - self._busy_t0) / 1e6
                span_end = now
            if span_end is not None:
                span_ms = (span_end - self._span_t0) / 1e6
        idle_frac = (
            max(0.0, 1.0 - busy_ms / span_ms) if span_ms > 0 else 0.0
        )
        # occupancy: rows actually carried / rows the dispatched rounds
        # could carry — the adaptive ingest batcher's whole job is
        # driving this toward 1 under load
        fill_frac = (
            self.dispatched_rows / self.dispatched_capacity
            if self.dispatched_capacity > 0 else 0.0
        )
        return {
            "device_dispatches": self.dispatches,
            "device_transfers": self.transfers,
            "device_dispatched_rows": self.dispatched_rows,
            "device_batch_capacity": self.dispatched_capacity,
            "dispatch_fill_frac": round(fill_frac, 4),
            "serving_chain_len": self.chain_len,
            "device_dispatch_ms": round(self.dispatch_wall_ms, 3),
            "device_drain_ms": round(self.drain_wall_ms, 3),
            "device_fetch_ms": round(self.fetch_wall_ms, 3),
            "device_busy_ms": round(busy_ms, 3),
            "device_span_ms": round(span_ms, 3),
            "device_idle_frac": round(idle_frac, 4),
            "device_pipeline_depth": self.pipeline_depth,
            "device_pipelined_rounds": self.pipelined_rounds,
            "device_overlapped_dispatches": self.overlapped_dispatches,
            "device_seq_epochs": getattr(self, "seq_epochs", 0),
            "device_slot_epochs": getattr(self, "slot_epochs", 0),
        }
