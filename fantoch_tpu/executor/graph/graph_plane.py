"""Device-resident dependency backlog for the batched graph executor.

The host twin (:class:`~fantoch_tpu.executor.graph.batched.BatchedDependencyGraph`
with the plane off) keeps its backlog in host numpy columns and re-ships
the *entire* backlog through ``jnp.asarray`` on every resolve, then
blocks on the fetch.  This plane is the table/pred-plane move applied to
the graph executor — the last executor family still paying
upload-per-resolve (ROADMAP item 5's remainder): the dependency backlog
— src/seq/key columns plus the dep-slot matrix — lives ON DEVICE across
feeds as donated in-place state
(``ops/graph_resolve.resolve_graph_plane_step``), each executor feed is
ONE dispatch that installs the new rows, patches the ``MISSING`` cells
whose dots just committed (the waiter-index protocol of
``executor/pred_plane.py``), and re-resolves the whole pending window
with the same kernels the host-column path dispatches per flush
(``resolve_keyed_auto`` for single-key functional windows,
``resolve_general`` / ``resolve_general_resident`` otherwise).  Only the
emitted order comes back.

Residual protocol: a missing-blocked row (a dependency not committed
here yet) stays resident — its ``MISSING`` cells are patched when the
dep commits in a later feed (or resolves as a recovered noop), so
blocked rows never round-trip through host columns.

Host bookkeeping is COLUMN-NATIVE (the PR 4 arrays discipline): dots
are packed int64s, installs/emissions are vectorized numpy over the
feed, and the only per-item host work is one dict probe per dependency.
Slots are bump-allocated; when the window fills the plane compacts —
still-pending rows re-pack to the bottom (dep cells remapped through a
LUT, references to executed rows folding to ``TERMINAL``) in one
counted re-upload, with 3/4-capacity grow hysteresis so a few residual
rows cannot flap the compiled shape.  The full backlog state is also
HOST-MIRRORED (installs and patches are cheap numpy writes), so
compaction, the stuck-cycle host oracle, and the liveness watchdog
never fetch device state.

Pipelining: ``pipeline_depth`` K keeps up to K-1 dispatched rounds
un-fetched (the ``run/pipeline.py`` delivery-lag contract) so a serving
loop overlaps the next feed's host assembly with device compute; depth
1 (the default, and what executor pools use) is fully synchronous.
Host-side emission dedup makes drains idempotent, so the rare
stuck-cycle follow-up dispatch composes with in-flight rounds.

Buffer lifecycle — donation-safe uploads, lazy host-mirror
re-materialization after restore with exactly ONE counted re-upload,
pow2 capacity growth, per-dispatch counters — is the shared
:class:`~fantoch_tpu.executor.device_plane.DevicePlane` base.

Clock width: device dot sequences are int32; the plane refuses
sequences at or above ``2^31 - 1`` with the shared typed error.
"""

from __future__ import annotations

import time as _time
from collections import deque
from typing import Deque, Dict, List, Set, Tuple

import numpy as np

from fantoch_tpu.core.config import Config
from fantoch_tpu.core.ids import Dot, ProcessId, ShardId
from fantoch_tpu.core.metrics import Metrics
from fantoch_tpu.core.timing import SysTime
from fantoch_tpu.errors import DeviceCorruptionError, DeviceFailedError
from fantoch_tpu.executor.base import ExecutorMetricsKind
from fantoch_tpu.executor.device_plane import DevicePlane, next_pow2 as _pow2
from fantoch_tpu.executor.table_plane import ClockOverflowError
from fantoch_tpu.ops.frontier import DeviceFrontier, pack_dots
from fantoch_tpu.ops.graph_resolve import MISSING, TERMINAL

_INT32_MAX = (1 << 31) - 1
_SEQ_MASK = (1 << 32) - 1

class DeviceGraphPlane(DevicePlane):
    """Resident dependency backlog + one fused dispatch per executor
    feed.  Driven by :class:`BatchedDependencyGraph` behind
    ``Config.device_graph_plane`` (the host-column path is the oracle
    twin — per-key execution-order parity tested in
    tests/test_graph_plane.py)."""

    __slots__ = (
        "_process_id",
        "_shard_id",
        "_config",
        "_frontier",
        "_metrics",
        "_structure_threshold",
        "_width",
        "_next_slot",
        "_slot_of",
        "_slot_src",
        "_slot_seq",
        "_slot_key",
        "_slot_tms",
        "_slot_deps",
        "_slot_general",
        "_general_rows",
        "_exec_host",
        "_slot_cmd",
        "_waiters",
        "_waiter_since",
        "_patches",
        "_inflight",
        "_emitted",
        "pipeline_depth",
    )

    plane_name = "graph"

    def __init__(
        self,
        process_id: ProcessId,
        shard_id: ShardId,
        config: Config,
        frontier: DeviceFrontier,
        metrics: Metrics,
        *,
        structure_threshold: int = 4096,
        slot_capacity: int = 1024,
        width: int = 4,
    ):
        super().__init__(
            slot_capacity,
            stats={
                # per-dispatch tallies: new_rows/update_capacity is the
                # install-batch occupancy (padding waste), patched_cells
                # the waiter-index patches applied, residual_rows the
                # still-blocked window after the drain, kernel_ms the
                # dispatch->fetch wall; compactions counts window
                # re-packs (each is one counted re-upload)
                "new_rows": 0,
                "update_capacity": 0,
                "patched_cells": 0,
                "residual_rows": 0,
                "compactions": 0,
                "kernel_ms": 0.0,
            },
        )
        self._process_id = process_id
        self._shard_id = shard_id
        self._config = config
        # the graph's executed frontier and metrics are SHARED (one
        # executed set, one histogram registry — pickle preserves the
        # sharing within one executor snapshot)
        self._frontier = frontier
        self._metrics = metrics
        self._structure_threshold = structure_threshold
        self._width = _pow2(max(width, 1))
        self._next_slot = 0
        # packed dot -> slot, PENDING rows only (emission pops)
        self._slot_of: Dict[int, int] = {}
        # host mirrors of the resident columns (installs/patches are
        # cheap numpy writes, so compaction/oracle/watchdog never fetch)
        self._slot_src = np.zeros(self._cap, dtype=np.int64)
        self._slot_seq = np.zeros(self._cap, dtype=np.int64)
        self._slot_key = np.full(self._cap, -1, dtype=np.int32)
        self._slot_tms = np.zeros(self._cap, dtype=np.float64)
        self._slot_deps = np.full(
            (self._cap, self._width), TERMINAL, dtype=np.int32
        )
        # rows that disqualify the keyed kernel (multi-key, or >1 live
        # dep at install); the counter gates the per-dispatch mode
        self._slot_general = np.zeros(self._cap, dtype=bool)
        self._general_rows = 0
        self._exec_host = np.zeros(self._cap, dtype=bool)
        self._slot_cmd: Dict[int, object] = {}
        # missing packed dot -> [(slot, col), ...] cells awaiting it,
        # with first-registration time (the watchdog only nudges dots
        # missing past the pending threshold)
        self._waiters: Dict[int, List[Tuple[int, int]]] = {}
        self._waiter_since: Dict[int, float] = {}
        # dep patches buffered between dispatches (noop resolutions land
        # here; arrival patches are generated at feed time)
        self._patches: List[Tuple[int, int, int]] = []
        # in-flight dispatch tokens: (mode, step output, U, ucap, P,
        # time, t0) — up to pipeline_depth - 1 stay un-fetched
        self._inflight: Deque[tuple] = deque()
        # drained emissions awaiting the graph: (cmds, src, seq) chunks
        self._emitted: List[Tuple[list, np.ndarray, np.ndarray]] = []
        self.pipeline_depth = 1

    # --- feed surface (BatchedDependencyGraph drives this) ---

    @property
    def pending_count(self) -> int:
        """Resident rows still blocked (committed, not yet executed)."""
        return len(self._slot_of)

    @property
    def in_flight(self) -> int:
        return len(self._inflight)

    @property
    def has_patches(self) -> bool:
        return bool(self._patches)

    def reserve(self, capacity: int) -> None:
        """Pre-size the slot window (bench/serving loops: a capacity that
        covers the whole run keeps ``resident_uploads`` at exactly 1 —
        no compaction re-uploads).  Only before the first install."""
        assert self._next_slot == 0 and self._resident is None
        while self._cap < _pow2(capacity):
            self._grow_columns()

    def feed(
        self,
        dot_src: np.ndarray,  # int64[B]
        dot_seq: np.ndarray,  # int64[B]
        key: np.ndarray,  # int32[B] conflict-key hash (-1 = multi-key)
        tms: np.ndarray,  # float64[B] commit time (ms)
        dep_dots: np.ndarray,  # int64[B, W] packed dep dots, -1 pad
        cmds: List[object],
        time: SysTime,
    ) -> None:
        """Install one column feed and dispatch the resident resolve."""
        B = len(dot_src)
        if B == 0:
            return self.flush(time)
        if int(dot_seq.max()) >= _INT32_MAX:
            raise ClockOverflowError(
                "dot sequence >= 2^31 - 1: the device graph plane is "
                "31-bit windowed (disable device_graph_plane)"
            )
        now = float(time.millis()) if time is not None else 0.0
        self._make_room(B)
        packed = pack_dots(dot_src, dot_seq)
        packed_list = packed.tolist()
        slot_of = self._slot_of
        # exactly-once: a dot may be neither resident, nor executed, nor
        # repeated within the feed itself (the host twin's duplicate-dot
        # assert, extended across feeds)
        assert len(set(packed_list)) == B, "duplicate dot added"
        for pd in packed_list:
            assert pd not in slot_of, "duplicate dot added"
        assert not self._frontier.contains_batch(dot_src, dot_seq).any(), (
            "duplicate dot added"
        )

        # bump-allocate contiguous slots for the whole feed
        base = self._next_slot
        self._next_slot = base + B
        slots = np.arange(base, base + B, dtype=np.int64)
        slot_of.update(zip(packed_list, range(base, base + B)))
        self._slot_src[base : base + B] = dot_src
        self._slot_seq[base : base + B] = dot_seq
        self._slot_key[base : base + B] = key
        self._slot_tms[base : base + B] = tms
        self._exec_host[base : base + B] = False
        self._slot_cmd.update(zip(range(base, base + B), cmds))

        # --- dependency encode (vectorized; one dict probe per dep) ---
        valid = (dep_dots >= 0) & (dep_dots != packed[:, None])  # self-deps drop
        r_idx, c_src = np.nonzero(valid)
        if len(r_idx):
            v = dep_dots[r_idx, c_src]
            vals = np.empty(len(v), dtype=np.int64)
            miss_at: List[int] = []
            for e, pd in enumerate(v.tolist()):
                s = slot_of.get(pd)
                if s is not None:
                    vals[e] = s
                else:
                    miss_at.append(e)
            if miss_at:
                mp = np.asarray(miss_at, dtype=np.int64)
                mv = v[mp]
                # not in the window: executed -> TERMINAL, else MISSING
                # (one vectorized frontier probe for the whole feed)
                ex = self._frontier.contains_batch(
                    mv >> 32, mv & _SEQ_MASK
                )
                vals[mp] = np.where(ex, TERMINAL, MISSING)
            # already-satisfied cells (executed deps) encode to nothing:
            # only live cells occupy dep columns, so steady-state serving
            # feeds (most deps executed at install) never widen the window
            keep = vals != TERMINAL
            r_idx, v, vals = r_idx[keep], v[keep], vals[keep]
            live_cnt = np.bincount(r_idx, minlength=B)
            width_needed = int(live_cnt.max()) if len(r_idx) else 0
            self._ensure_width(max(width_needed, 1))
            u_deps = np.full((B, self._width), TERMINAL, dtype=np.int32)
            head = np.r_[True, r_idx[1:] != r_idx[:-1]] if len(r_idx) else (
                np.zeros(0, dtype=bool)
            )
            iota = np.arange(len(r_idx), dtype=np.int64)
            cols = iota - np.maximum.accumulate(np.where(head, iota, 0))
            u_deps[r_idx, cols] = vals
            for e in np.nonzero(vals == MISSING)[0].tolist():
                pd = int(v[e])
                w_slot, w_col = int(base + r_idx[e]), int(cols[e])
                self._waiters.setdefault(pd, []).append((w_slot, w_col))
                self._waiter_since.setdefault(pd, now)
        else:
            live_cnt = np.zeros(B, dtype=np.int64)
            self._ensure_width(1)
            u_deps = np.full((B, self._width), TERMINAL, dtype=np.int32)
        self._slot_deps[base : base + B] = u_deps
        gen = (key < 0) | (live_cnt > 1)
        self._slot_general[base : base + B] = gen
        self._general_rows += int(gen.sum())

        # the residual re-feed: earlier rows waiting on this feed's dots
        # get their MISSING cells patched to the new slots
        if self._waiters:
            for pd, slot in zip(packed_list, range(base, base + B)):
                cells = self._waiters.pop(pd, None)
                if cells is None:
                    continue
                self._waiter_since.pop(pd, None)
                for w_slot, w_col in cells:
                    self._patches.append((w_slot, w_col, slot))
                    self._slot_deps[w_slot, w_col] = slot

        self._dispatch(
            slots,
            u_deps,
            key.astype(np.int32, copy=False),
            dot_src.astype(np.int32),
            dot_seq.astype(np.int32),
            time,
        )

    def note_noop(self, source: int, sequence: int) -> None:
        """A recovery-committed noop: the dot counts as executed (the
        graph adds it to the shared frontier), and every cell waiting on
        it resolves to TERMINAL on the next dispatch."""
        pd = (int(source) << 32) | int(sequence)
        assert pd not in self._slot_of, "a noop dot has no resident slot"
        self._waiter_since.pop(pd, None)
        for w_slot, w_col in self._waiters.pop(pd, ()):
            self._patches.append((w_slot, w_col, TERMINAL))
            self._slot_deps[w_slot, w_col] = TERMINAL

    def flush(self, time: SysTime) -> None:
        """Dispatch any buffered patches (noop resolutions with no new
        feed) and drain per the pipeline depth (end-of-stream tails are
        ``drain_all`` / the graph's ``flush_plane_pipeline``)."""
        if self._patches:
            empty = np.empty(0, dtype=np.int64)
            self._dispatch(
                empty,
                np.empty((0, self._width), dtype=np.int32),
                empty.astype(np.int32),
                empty.astype(np.int32),
                empty.astype(np.int32),
                time,
            )
        while len(self._inflight) > max(self.pipeline_depth - 1, 0):
            self._drain_one()

    def drain_all(self) -> None:
        while self._inflight:
            self._drain_one()

    def take_emitted(self) -> List[Tuple[list, np.ndarray, np.ndarray]]:
        """Drained (cmds, src, seq) emission chunks in execution order
        since the last take (the graph routes them to the object drain
        or the order-arrays drain)."""
        out, self._emitted = self._emitted, []
        return out

    # --- the resident dispatch ---

    def _mode(self) -> str:
        """Single-key functional windows ride the sort-based keyed kernel
        (no exact-structure entry — the plane reports aggregate counters;
        the host-column twin keeps the CHAIN_SIZE path); multi-key /
        multi-dep windows ride ``resolve_general`` below the kernel-size
        gate (mutual cycles collapse on device, exact structure) and the
        resident peel-and-compact schedule above it."""
        if self._general_rows > 0:
            if self._cap <= self._structure_threshold:
                return "general"
            return "general_resident"
        return "keyed"

    def _dispatch(self, slots, u_deps, u_key, u_src, u_seq, time) -> None:
        patches, self._patches = self._patches, []
        U, P = len(slots), len(patches)
        if U == 0 and P == 0:
            return
        out, mode, t0, ucap = self._dispatch_raw(
            slots, u_deps, u_key, u_src, u_seq, patches, (), time=time
        )
        self._inflight.append((mode, out, U, ucap, P, time, t0))
        while len(self._inflight) > max(self.pipeline_depth - 1, 0):
            self._drain_one()

    def _pad_columns(self, slots, u_deps, u_key, u_src, u_seq, patches, marks):
        """The padded kernel columns for one dispatch — shared by the
        resident dispatch and the host twin's stuck follow-ups, so both
        feed the kernel bit-identical inputs."""
        cap = self._cap
        U, P, E = len(slots), len(patches), len(marks)
        # pad to pow2 FLOORS so the common serving shapes share compiled
        # programs: per-dispatch install/patch counts jitter, and every
        # distinct shape is a fresh XLA program (~minutes on small rigs)
        ucap = _pow2(max(U, 64))
        pcap = _pow2(max(P, 64))
        ecap = _pow2(max(E, 8))
        u_row = np.full(ucap, cap, dtype=np.int32)  # pad -> dropped
        u_dep = np.full((ucap, self._width), TERMINAL, dtype=np.int32)
        u_k = np.zeros(ucap, dtype=np.int32)
        u_s = np.zeros(ucap, dtype=np.int32)
        u_q = np.zeros(ucap, dtype=np.int32)
        if U:
            u_row[:U] = slots
            u_dep[:U] = u_deps
            u_k[:U] = u_key
            u_s[:U] = u_src
            u_q[:U] = u_seq
        p_row = np.full(pcap, cap, dtype=np.int32)  # pad -> dropped
        p_col = np.zeros(pcap, dtype=np.int32)
        p_val = np.zeros(pcap, dtype=np.int32)
        for i, (slot, col, val) in enumerate(patches):
            p_row[i], p_col[i], p_val[i] = slot, col, val
        e_row = np.full(ecap, cap, dtype=np.int32)  # pad -> dropped
        if E:
            e_row[:E] = marks
        return (u_row, u_dep, u_k, u_s, u_q, p_row, p_col, p_val, e_row), ucap

    def _dispatch_raw(
        self, slots, u_deps, u_key, u_src, u_seq, patches, marks, time=None
    ):
        import jax.numpy as jnp

        from fantoch_tpu.ops.graph_resolve import resolve_graph_plane_step

        cols, ucap = self._pad_columns(
            slots, u_deps, u_key, u_src, u_seq, patches, marks
        )
        mode = self._mode()
        # every dispatch — primary AND stuck follow-up — is logged with
        # its mode, so the twin replays the identical kernel sequence and
        # tracks the resident state bit-for-bit (armed-only no-op)
        self._twin_note((mode, time) + cols)
        t0 = _time.perf_counter()
        if self.degraded:
            # served from the twin at this round's drain (out=None token)
            return None, mode, t0, ucap
        try:
            fault = self._fault_check_pre()
            self._materialize()
            out = resolve_graph_plane_step(
                *self._resident,
                *(jnp.asarray(c) for c in cols),
                mode=mode,
            )
            self._resident = tuple(out[:6])
            if fault is not None:
                self._poison_resident(fault)
            return out, mode, t0, ucap
        except (DeviceFailedError, DeviceCorruptionError) as exc:
            # dispatch-time failure (injected hang/raise): the round — and
            # every in-flight round, whose device results are no longer
            # trusted — is served from the twin at its drain
            self._device_failure(exc)
            self._fail_inflight()
            self._note_degraded(t0)
            return None, mode, t0, ucap

    def _fail_inflight(self) -> None:
        """Invalidate the device results of every in-flight round after a
        failure: their rows replay from the twin log (emission dedup makes
        the replay exactly-once), the drains just count them."""
        if self._inflight:
            self._inflight = deque(
                (m, None, u, uc, p, tm, tt)
                for (m, _o, u, uc, p, tm, tt) in self._inflight
            )

    # --- host twin (accelerator fault tolerance; DevicePlane base) ---

    def _twin_replay(self, state, entry):
        """One logged dispatch replayed statelessly through the SAME
        kernel (fresh ``jnp.array`` uploads — the donation-safety rule),
        with host emission performed HERE: emission dedup
        (``_exec_host``) makes rounds the device already drained replay
        as no-ops, while in-flight rounds at pipeline depth K emit
        exactly once, in round order — the depth-K exactly-once replay.
        Degraded serving has no device follow-ups, so stuck residues
        resolve on the twin itself (healthy folds see them already
        emitted and skip — the device's own follow-up was logged)."""
        mode, time = entry[0], entry[1]
        state, fetched = self._twin_step(state, mode, entry[2:])
        order, newly, stuck, leader = fetched
        self._emit(order[newly[order]], leader, time)
        while stuck is not None:
            stuck_slots = np.nonzero(stuck & ~self._exec_host)[0]
            if not len(stuck_slots):
                break
            closed = self._close_stuck(stuck_slots)
            if not len(closed):
                break
            self._stuck_oracle(closed, time)
            empty = np.empty(0, dtype=np.int64)
            mcols, _ucap = self._pad_columns(
                empty, np.empty((0, self._width), np.int32),
                empty.astype(np.int32), empty.astype(np.int32),
                empty.astype(np.int32), (), closed,
            )
            state, fetched = self._twin_step(state, self._mode(), mcols)
            order, newly, stuck, leader = fetched
            self._emit(order[newly[order]], leader, time)
        return state, fetched

    def _twin_step(self, state, mode, cols):
        """One kernel run on host-owned twin state; returns the new
        state and the per-mode result columns, all host numpy."""
        import jax
        import jax.numpy as jnp

        from fantoch_tpu.ops.graph_resolve import resolve_graph_plane_step

        out = resolve_graph_plane_step(
            *(jnp.array(a) for a in state),
            *(jnp.asarray(c) for c in cols),
            mode=mode,
        )
        new_state = tuple(np.asarray(a) for a in jax.device_get(out[:6]))
        if mode == "keyed":
            order, newly = jax.device_get((out.order, out.newly))
            fetched = (np.asarray(order), np.asarray(newly), None, None)
        else:
            order, newly, stuck, leader = jax.device_get(
                (out.order, out.newly, out.stuck, out.leader)
            )
            fetched = (
                np.asarray(order),
                np.asarray(newly),
                np.asarray(stuck),
                np.asarray(leader) if mode == "general" else None,
            )
        return new_state, fetched

    def _fetch_result(self, mode: str, out):
        """One blocking transfer for a dispatch's small result columns
        (the backlog state itself never round-trips)."""
        import jax

        if mode == "keyed":
            order, newly = jax.device_get((out.order, out.newly))
            return np.asarray(order), np.asarray(newly), None, None
        order, newly, stuck, leader = jax.device_get(
            (out.order, out.newly, out.stuck, out.leader)
        )
        leader_np = np.asarray(leader) if mode == "general" else None
        return np.asarray(order), np.asarray(newly), np.asarray(stuck), leader_np

    def _drain_one(self) -> None:
        mode, out, U, ucap, P, time, t0 = self._inflight.popleft()
        if out is None:
            # the round is (or already was, by an earlier fold) served
            # bit-for-bit from the twin — emission dedup makes rounds an
            # earlier fold replayed pure no-ops here
            self._twin_fold()
            self._note_degraded(t0)
        else:
            try:
                order, newly, stuck, leader = self._fetch_result(mode, out)
                self._check_deadline(t0)
                live_stuck = stuck is not None and bool(
                    (stuck & ~self._exec_host).any()
                )
                if (
                    not self._inflight
                    and not live_stuck
                    and self._shadow_sampled()
                ):
                    # serve the round from the twin FIRST (the device
                    # emission below dedups to a no-op), then verify the
                    # device state against it — a corrupt ``newly`` never
                    # reaches the host bookkeeping.  Rounds with live
                    # stuck residues defer to the next sampled round (the
                    # follow-up dispatch below would race the compare).
                    self._twin_fold()
                    self._shadow_compare(self._fetch_state())
                self._emit(order[newly[order]], leader, time)
                # stuck residues (general modes: 3+-cycles the device
                # pass cannot collapse) finish on the host Tarjan oracle;
                # a follow-up dispatch marks them executed on device and
                # resolves dependents
                while stuck is not None:
                    stuck_slots = np.nonzero(stuck & ~self._exec_host)[0]
                    if not len(stuck_slots):
                        break
                    closed = self._close_stuck(stuck_slots)
                    if not len(closed):
                        break  # budget misclassification: wait for a later feed
                    self._stuck_oracle(closed, time)
                    empty = np.empty(0, dtype=np.int64)
                    out2, mode2, _t0b, _ucap2 = self._dispatch_raw(
                        empty, np.empty((0, self._width), np.int32),
                        empty.astype(np.int32), empty.astype(np.int32),
                        empty.astype(np.int32), (), closed, time=time,
                    )
                    if out2 is None:
                        # the follow-up itself hit the injected fault:
                        # its marks entry replays through the twin
                        self._twin_fold()
                        break
                    order, newly, stuck, leader = self._fetch_result(
                        mode2, out2
                    )
                    self._emit(order[newly[order]], leader, time)
            except (DeviceFailedError, DeviceCorruptionError) as exc:
                # serve this round — and everything still logged — from
                # the twin; in-flight device results are dropped
                self._twin_fold()
                self._device_failure(exc)
                self._fail_inflight()
                self._note_degraded(t0)
        self._count_dispatch(
            t0,
            new_rows=U,
            update_capacity=ucap,
            patched_cells=P,
            residual_rows=self.pending_count,
        )
        # cutback: once the fault window closed, ONE counted re-upload of
        # the folded twin state (no-op unless failed)
        self._maybe_rebuild()

    def _emit(self, slots: np.ndarray, leader, time) -> None:
        """Host bookkeeping for one drain's executed slots, in emission
        order.  Idempotent (already-executed slots are dropped) so the
        stuck-cycle follow-up composes with in-flight rounds."""
        slots = np.asarray(slots, dtype=np.int64)
        if len(slots):
            slots = slots[~self._exec_host[slots]]
        if not len(slots):
            return
        self._exec_host[slots] = True
        src = self._slot_src[slots]
        seq = self._slot_seq[slots]
        cmds = self._slot_cmd
        emitted_cmds = [cmds.pop(s) for s in slots.tolist()]
        slot_of = self._slot_of
        for pd in pack_dots(src, seq).tolist():
            del slot_of[pd]
        self._general_rows -= int(self._slot_general[slots].sum())
        self._frontier.add_batch(src, seq)
        if time is not None:
            now = float(time.millis())
            self._metrics.collect_many(
                ExecutorMetricsKind.EXECUTION_DELAY,
                np.maximum(now - self._slot_tms[slots], 0.0),
            )
        if leader is not None:
            # exact per-SCC structure (structure modes only — the same
            # gating as the host-column path's want_structure)
            leaders = leader[slots]
            sizes = np.diff(
                np.concatenate(
                    [[0], np.nonzero(np.diff(leaders))[0] + 1, [len(slots)]]
                )
            )
            self._metrics.collect_many(ExecutorMetricsKind.CHAIN_SIZE, sizes)
        self._emitted.append((emitted_cmds, src, seq))

    # --- stuck-cycle host oracle (slot space) ---

    def _folded_deps(self) -> np.ndarray:
        """The host mirror of the dep matrix with cells on executed
        slots folded to TERMINAL — what the device's resolve sees."""
        deps = self._slot_deps
        live = deps >= 0
        safe = np.clip(deps, 0, self._cap - 1)
        return np.where(live & self._exec_host[safe], TERMINAL, deps)

    def _close_stuck(self, stuck_slots: np.ndarray) -> np.ndarray:
        from fantoch_tpu.executor.graph.batched import _close_stuck_set

        return np.asarray(
            _close_stuck_set(stuck_slots, self._folded_deps(), ~self._exec_host)
        )

    def _stuck_oracle(self, slots: np.ndarray, time) -> None:
        """Host Tarjan over the (dep-closed) stuck residue, restricted to
        stuck members — the host-column path's python oracle in slot
        space (stuck residues are rare 3+-cycles; the mirrors make the
        subgraph free to build)."""
        from fantoch_tpu.executor.graph.deps_graph import DependencyGraph
        from fantoch_tpu.protocol.common.graph_deps import Dependency

        in_set = set(slots.tolist())
        oracle = DependencyGraph(self._process_id, self._shard_id, self._config)
        shards = frozenset({self._shard_id})
        row_of = {id(self._slot_cmd[int(s)]): int(s) for s in slots}
        emitted_rows: List[int] = []
        for s in slots.tolist():
            dot = Dot(int(self._slot_src[s]), int(self._slot_seq[s]))
            dep_list = [
                Dependency(
                    Dot(int(self._slot_src[t]), int(self._slot_seq[t])), shards
                )
                for t in self._slot_deps[s].tolist()
                if t in in_set
            ]
            oracle.handle_add(dot, self._slot_cmd[s], dep_list, time)
            for done in oracle.commands_to_execute():
                emitted_rows.append(row_of[id(done)])
        assert len(emitted_rows) == len(slots), (
            f"stuck residue not fully resolvable: "
            f"{len(emitted_rows)}/{len(slots)}"
        )
        chain_hist = oracle.metrics().get_collected(ExecutorMetricsKind.CHAIN_SIZE)
        if chain_hist is not None:
            from fantoch_tpu.core.metrics import Histogram

            self._metrics.collected.setdefault(
                ExecutorMetricsKind.CHAIN_SIZE, Histogram()
            ).merge(chain_hist)
        self._emit(np.asarray(emitted_rows, dtype=np.int64), None, time)

    # --- capacity management ---

    def _make_room(self, need: int) -> None:
        """Ensure ``need`` contiguous bump slots: grow while the pending
        window could not fit at 3/4 capacity (growing a LIVE window
        recompiles the step program — the hysteresis keeps a few residual
        rows from flapping the capacity), then compact (re-pack pending
        rows to the bottom — same compiled shape, one counted re-upload)
        when the bump pointer is exhausted anyway."""
        if (
            len(self._slot_of) + need > (3 * self._cap) // 4
            or self._next_slot + need > self._cap
        ):
            # both paths renumber or reshape: retire in-flight rounds
            self.drain_all()
        while len(self._slot_of) + need > (3 * self._cap) // 4:
            self._grow_columns()
        if self._next_slot + need > self._cap:
            self._compact()

    def _grow_columns(self) -> None:
        old_cap = self._cap
        self._grow()  # doubles _cap; re-pads resident state when live
        for name in ("_slot_src", "_slot_seq", "_slot_tms"):
            old = getattr(self, name)
            grown = np.zeros(self._cap, dtype=old.dtype)
            grown[:old_cap] = old
            setattr(self, name, grown)
        key = np.full(self._cap, -1, dtype=np.int32)
        key[:old_cap] = self._slot_key
        self._slot_key = key
        deps = np.full((self._cap, self._width), TERMINAL, dtype=np.int32)
        deps[:old_cap] = self._slot_deps
        self._slot_deps = deps
        for name in ("_slot_general", "_exec_host"):
            old = getattr(self, name)
            grown = np.zeros(self._cap, dtype=bool)
            grown[:old_cap] = old
            setattr(self, name, grown)

    def _ensure_width(self, width: int) -> None:
        if width <= self._width:
            return
        self.drain_all()
        if self._fault_armed and self._twin_log:
            # entries logged at the old width cannot replay against the
            # widened twin — fold them out first (emission dedup makes
            # the healthy-path replays no-ops)
            self._twin_fold()
        new_w = _pow2(width)
        deps = np.full((self._cap, new_w), TERMINAL, dtype=np.int32)
        deps[:, : self._width] = self._slot_deps
        self._slot_deps = deps
        self._width = new_w
        state = self._rebuild_state()
        if self._resident is not None:
            self._upload(state)
        elif self._host_mirror is not None:
            self._host_mirror = state
        if self._twin_state is not None:
            self._twin_resync(state)
        self.grows += 1

    def _compact(self) -> None:
        """Re-pack the pending window to the bottom of the slot space
        from the HOST MIRRORS (no device fetch): dep cells remap through
        a LUT, references to executed rows fold to TERMINAL, one counted
        re-upload."""
        assert not self._inflight
        if self._fault_armed and self._twin_log:
            # entries describe the pre-compaction slot layout: fold them
            # before the renumbering (healthy replays dedup to no-ops)
            self._twin_fold()
        cap = self._cap
        old = np.fromiter(self._slot_of.values(), np.int64, len(self._slot_of))
        old.sort()  # stable re-pack keeps slot order deterministic
        P = len(old)
        lut = np.full(cap, TERMINAL, dtype=np.int32)
        lut[old] = np.arange(P, dtype=np.int32)
        nd = self._slot_deps[old]
        live = nd >= 0
        safe = np.clip(nd, 0, cap - 1)
        nd = np.where(
            live,
            np.where(self._exec_host[safe], TERMINAL, lut[safe]),
            nd,
        ).astype(np.int32)
        # host columns follow the same re-pack
        self._slot_src[:P] = self._slot_src[old]
        self._slot_seq[:P] = self._slot_seq[old]
        self._slot_key[:P] = self._slot_key[old]
        self._slot_tms[:P] = self._slot_tms[old]
        self._slot_deps[:P] = nd
        self._slot_deps[P:] = TERMINAL
        self._slot_general[:P] = self._slot_general[old]
        self._slot_general[P:] = False
        self._general_rows = int(self._slot_general[:P].sum())
        self._exec_host[:] = False
        cmds = {int(lut[s]): self._slot_cmd[int(s)] for s in old.tolist()}
        self._slot_cmd.clear()
        self._slot_cmd.update(cmds)
        pend_pd = pack_dots(self._slot_src[:P], self._slot_seq[:P])
        self._slot_of.clear()
        self._slot_of.update(zip(pend_pd.tolist(), range(P)))
        remapped = {
            pd: [(int(lut[s]), c) for s, c in cells]
            for pd, cells in self._waiters.items()
        }
        self._waiters.clear()
        self._waiters.update(remapped)
        self._patches = [
            (int(lut[s]), c, int(lut[v]) if v >= 0 else v)
            for s, c, v in self._patches
        ]
        self._next_slot = P
        state = self._rebuild_state()
        if self.degraded:
            # no upload while failed over: the compacted window becomes
            # the new twin state; cutback re-uploads it (ONE upload)
            pass
        elif self._resident is not None or self._host_mirror is None:
            self._upload(state)
        else:
            self._host_mirror = state
        if self._twin_state is not None:
            self._twin_resync(state)
        self.stats["compactions"] += 1

    def _rebuild_state(self) -> Tuple[np.ndarray, ...]:
        """Full device state from the host mirrors at the current
        capacity/width (compaction, width growth, restore)."""
        cap = self._cap
        occ = np.zeros(cap, dtype=bool)
        occ[: self._next_slot] = True
        return (
            self._slot_deps.copy(),
            self._slot_key.copy(),
            self._slot_src.astype(np.int32),
            self._slot_seq.astype(np.int32),
            occ,
            self._exec_host.copy(),
        )

    # --- liveness watchdog (the BatchedDependencyGraph contract) ---

    def monitor_pending(self, time: SysTime):
        """Per-row liveness check over the host mirrors: old pending
        rows must be *transitively* missing-blocked (panic otherwise — a
        lost execution), rows blocked on missing deps past
        ``Config.executor_pending_fail_ms`` raise the typed stall, and
        the overdue missing dots are returned so the runner can nudge
        recovery.  A waiter dot found executed in the frontier is a lost
        wake and folds like an executed cell (its dependents then panic
        as pending-without-missing, exactly like the host twin)."""
        assert not self._inflight
        if not self._slot_of:
            return None
        from fantoch_tpu.executor.graph.indexes import (
            MONITOR_PENDING_THRESHOLD_MS,
        )

        now = float(time.millis())
        pend = np.fromiter(self._slot_of.values(), np.int64, len(self._slot_of))
        pending_for = now - self._slot_tms[pend]
        old_mask = pending_for >= MONITOR_PENDING_THRESHOLD_MS
        fail_ms = self._config.executor_pending_fail_ms
        ripe_mask = pending_for >= fail_ms if fail_ms is not None else None
        if not old_mask.any() and (ripe_mask is None or not ripe_mask.any()):
            return None
        # genuinely-missing frontier: waiter dots not executed; a waiter
        # dot IN the frontier is a lost wake — skipping it here leaves
        # its dependents without a missing set, so they trip the
        # pending-without-missing panic below
        row_missing: Dict[int, Set[Dot]] = {}
        if self._waiters:
            pds = np.fromiter(self._waiters.keys(), np.int64, len(self._waiters))
            executed = self._frontier.contains_batch(pds >> 32, pds & _SEQ_MASK)
            for pd, ex in zip(pds.tolist(), executed.tolist()):
                if ex:
                    continue
                dot = Dot(pd >> 32, pd & _SEQ_MASK)
                for slot, _col in self._waiters[pd]:
                    row_missing.setdefault(slot, set()).add(dot)
        cap = self._cap
        deps = self._folded_deps()
        direct = np.zeros(cap, dtype=bool)
        if row_missing:
            direct[np.fromiter(row_missing.keys(), np.int64)] = True
        nudge = {
            dot
            for slot in np.asarray(pend[old_mask]).tolist()
            for dot in row_missing.get(slot, ())
        }
        if ripe_mask is not None:
            stalled = pend[(direct[pend]) & ripe_mask]
            if len(stalled):
                from fantoch_tpu.errors import StalledExecutionError

                missing_map = {
                    Dot(int(self._slot_src[s]), int(self._slot_seq[s])):
                        row_missing[int(s)]
                    for s in stalled.tolist()[:8]
                }
                raise StalledExecutionError(
                    self._process_id,
                    missing_map,
                    int((now - self._slot_tms[stalled]).max()),
                    self._config.recovery_delay_ms,
                )
        # forward-propagate blockedness (MISSING cells whose dot is NOT
        # lost) to dependents; an old pending row left uncovered means an
        # execution was lost — panic naming the dots (host twin contract)
        blocked = ((deps == MISSING).any(axis=1)) & direct
        valid = deps >= 0
        safe = np.clip(deps, 0, cap - 1)
        old_slots = np.zeros(cap, dtype=bool)
        old_slots[pend[old_mask]] = True
        while True:
            uncovered = old_slots & ~blocked
            if not uncovered.any():
                return nudge
            grown = blocked | np.where(valid, blocked[safe], False).any(axis=1)
            if (grown == blocked).all():
                break
            blocked = grown
        dots = [
            Dot(int(self._slot_src[s]), int(self._slot_seq[s]))
            for s in np.nonzero(uncovered)[0][:8]
        ]
        raise AssertionError(
            f"p{self._process_id}: {int(uncovered.sum())} commands pending "
            f"without missing dependencies: {dots}"
        )

    # --- DevicePlane state hooks ---

    def _fresh_state(self):
        return (
            np.full((self._cap, self._width), TERMINAL, dtype=np.int32),
            np.full(self._cap, -1, dtype=np.int32),
            np.zeros(self._cap, dtype=np.int32),
            np.zeros(self._cap, dtype=np.int32),
            np.zeros(self._cap, dtype=bool),
            np.zeros(self._cap, dtype=bool),
        )

    def _pad_state(self, state, cap: int):
        deps, key, src, seq, occ, executed = state
        rows = min(len(key), cap)
        cols = min(deps.shape[1], self._width)
        out = [
            np.full((cap, self._width), TERMINAL, dtype=np.int32),
            np.full(cap, -1, dtype=np.int32),
            np.zeros(cap, dtype=np.int32),
            np.zeros(cap, dtype=np.int32),
            np.zeros(cap, dtype=bool),
            np.zeros(cap, dtype=bool),
        ]
        out[0][:rows, :cols] = deps[:rows, :cols]
        out[1][:rows] = key[:rows]
        out[2][:rows] = src[:rows]
        out[3][:rows] = seq[:rows]
        out[4][:rows] = occ[:rows]
        out[5][:rows] = executed[:rows]
        return tuple(out)

    # --- durability (in-flight rounds cannot survive a pickle) ---

    def __getstate__(self):
        self.drain_all()
        if self._fault_armed and self._twin_log:
            # fold so the pickled log is empty (entries hold live time
            # handles); post-drain replays dedup to no-op emissions
            self._twin_fold()
        return super().__getstate__()
