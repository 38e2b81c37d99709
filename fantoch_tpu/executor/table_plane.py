"""Device-resident votes-table plane for the Newt/Tempo commit path.

The host twin (executor/table.py) keeps one ``RangeEventSet`` per
(key, process) and rebuilds + re-uploads the frontier matrix for every
executor batch (on the chip: not measured).  This plane applies the move
of the graph executor: the ``(key_bucket x process)`` frontier matrix
lives ON DEVICE across batches (donated buffers,
``ops/table_ops.fused_votes_commit``), and each batch is one fused
dispatch doing vote-range coalescing (segment-max over sorted
``(key, by)`` runs), frontier update, and stability.

Exactness: a merged vote run that starts beyond a frontier gap cannot
advance the watermark; the kernel marks it *residual* and this class
buffers + re-feeds it with every later batch until the gap fills —
after which the frontier equals what the RangeEventSets would hold
(oracle-equivalence tested, tests/test_table_plane.py).

Buffer lifecycle (donation safety, lazy host-mirror re-materialization
with the single counted re-upload, pow2 growth, per-dispatch counters)
comes from the shared :class:`~fantoch_tpu.executor.device_plane.DevicePlane`
base — the same machinery the Caesar predecessors plane
(executor/pred_plane.py) rides.

Clock width: device clocks are int32.  The plane refuses clocks at or
above ``2^31 - 1`` with a typed error instead of silently wrapping —
real-time-micros clock bumps (``Config.newt_clock_bump_interval_ms``)
are rejected at config time (core/config.py).
"""

from __future__ import annotations

import time
from typing import Tuple

import numpy as np

from fantoch_tpu.errors import DeviceCorruptionError, DeviceFailedError
from fantoch_tpu.executor.device_plane import DevicePlane, next_pow2 as _pow2

_INT32_MAX = (1 << 31) - 1


class ClockOverflowError(ValueError):
    """A clock or vote endpoint exceeds the plane's 31-bit device window."""



class DeviceTablePlane(DevicePlane):
    """Resident vote-frontier state + fused commit dispatch per batch.

    ``commit_votes`` consumes vote columns (already bucketed) and returns
    the post-batch stable clock of every registered bucket; the frontier
    matrix never crosses the host boundary (donated in, donated out).
    """

    __slots__ = ("n", "threshold")

    plane_name = "table"

    def __init__(self, n: int, stability_threshold: int, key_buckets: int = 1024):
        assert stability_threshold <= n
        super().__init__(
            key_buckets,
            stats={
                # per-dispatch observability tallies (observability/
                # device.py): vote_rows/row_capacity is the batch
                # occupancy (padding waste), kernel_ms the blocking
                # dispatch+transfer wall time
                "vote_rows": 0,
                "row_capacity": 0,
                "residual_runs": 0,
                "kernel_ms": 0.0,
            },
        )
        self.n = n
        self.threshold = stability_threshold

    # --- DevicePlane state hooks (state = the 1-tuple frontier matrix) ---

    def _fresh_state(self) -> Tuple[np.ndarray, ...]:
        return (np.zeros((self._cap, self.n), dtype=np.int32),)

    def _pad_state(self, state, cap: int) -> Tuple[np.ndarray, ...]:
        (host,) = state
        padded = np.zeros((cap, self.n), dtype=np.int32)
        rows = min(len(host), cap)
        padded[:rows] = host[:rows]
        return (padded,)

    @property
    def _frontier(self):
        return self._resident[0] if self._resident is not None else None

    # --- host twin (accelerator fault tolerance; DevicePlane base) ---

    def _twin_replay(self, state, entry):
        """One logged commit dispatch replayed statelessly: the SAME
        fused kernel over a fresh XLA-owned copy of the twin frontier
        (``jnp.array`` — the donation-safety rule) plus the exact padded
        columns the resident dispatch consumed — outputs are bit-for-bit
        what a healthy device produced/would have produced."""
        import jax
        import jax.numpy as jnp

        from fantoch_tpu.ops.table_ops import fused_votes_commit

        pk, pb, ps, pe, pvalid = entry
        (frontier,) = state
        out = fused_votes_commit(
            jnp.array(frontier),
            jnp.asarray(pk),
            jnp.asarray(pb),
            jnp.asarray(ps),
            jnp.asarray(pe),
            jnp.asarray(pvalid),
            threshold=self.threshold,
        )
        fetched = jax.device_get(out)
        return (np.asarray(fetched[0]),), tuple(
            np.asarray(a) for a in fetched[1:]
        )

    # --- the fused commit dispatch ---

    def commit_votes(
        self,
        vkey: np.ndarray,  # int64[V] bucket ids (from ``bucket``)
        vby: np.ndarray,  # int64[V] process ids, 1-based (protocol ids)
        vstart: np.ndarray,  # int64[V]
        vend: np.ndarray,  # int64[V]
    ) -> np.ndarray:
        """Apply a batch of vote ranges; returns ``int64[key_count]``
        stable clocks (post-batch) for every registered bucket.  Residual
        (beyond-gap) runs are buffered internally and re-fed with the
        next batch."""
        if len(vend) and int(np.max(vend)) >= _INT32_MAX:
            raise ClockOverflowError(
                "vote endpoint >= 2^31 - 1: the device table plane is "
                "31-bit windowed (disable device_table_plane for "
                "real-time-micros clocks)"
            )
        # prepend buffered residuals so gap-filling batches coalesce with
        # the runs they unblock
        vkey, vby, vstart, vend = self._take_residuals(
            (vkey, vby, vstart, vend)
        )
        V = len(vkey)

        if V == 0:
            return self._stable_only()

        # pad the vote columns to pow2 so XLA compiles O(log) programs
        vcap = _pow2(V)
        pk = np.zeros(vcap, dtype=np.int32)
        pb = np.zeros(vcap, dtype=np.int32)
        ps = np.zeros(vcap, dtype=np.int32)
        pe = np.zeros(vcap, dtype=np.int32)
        pk[:V] = vkey
        pb[:V] = vby - 1  # protocol process ids are 1-based; columns 0-based
        ps[:V] = vstart
        pe[:V] = vend
        pvalid = np.zeros(vcap, dtype=bool)
        pvalid[:V] = True

        # the twin logs the exact padded columns BEFORE the dispatch, so
        # a failure mid-dispatch still replays it (armed-only no-op)
        self._twin_note((pk, pb, ps, pe, pvalid))
        t0 = time.perf_counter()
        stable, run_key, run_by, run_start, run_end, residual = (
            self._serve_commit(t0, pk, pb, ps, pe, pvalid)
        )
        res = np.flatnonzero(residual)
        self._count_dispatch(
            t0, vote_rows=V, row_capacity=vcap, residual_runs=len(res)
        )
        self._put_residuals(
            (
                run_key[res].astype(np.int64),
                (run_by[res] + 1).astype(np.int64),  # back to 1-based
                run_start[res].astype(np.int64),
                run_end[res].astype(np.int64),
            )
        )
        # cutback: once the fault window closed, ONE counted re-upload
        # of the folded twin state (no-op unless failed)
        self._maybe_rebuild()
        return stable.astype(np.int64)[: self.key_count]

    def _serve_commit(self, t0, pk, pb, ps, pe, pvalid):
        """One commit dispatch under the fault plane: the resident fused
        dispatch when healthy (guarded by the injector, the per-dispatch
        deadline, and the sampled shadow-check), the host twin bit-for-bit
        while failed over."""
        import jax
        import jax.numpy as jnp

        from fantoch_tpu.ops.table_ops import fused_votes_commit

        if self.degraded:
            outputs = self._twin_fold()
            self._note_degraded(t0)
            return outputs
        twin_out = None
        try:
            fault = self._fault_check_pre()
            self._materialize()
            out = fused_votes_commit(
                self._frontier,
                jnp.asarray(pk),
                jnp.asarray(pb),
                jnp.asarray(ps),
                jnp.asarray(pe),
                jnp.asarray(pvalid),
                threshold=self.threshold,
            )
            self._resident = (out[0],)
            if fault is not None:
                self._poison_resident(fault)
            # one blocking transfer for stability + the residual columns
            fetched = jax.device_get(out[1:])
            self._check_deadline(t0)
            if self._shadow_sampled():
                # the fold's outputs ARE this dispatch's bit-exact twin
                # outputs — kept so a corruption verdict can serve the
                # batch without re-replaying
                twin_out = self._twin_fold()
                self._shadow_compare(self._fetch_state())
            return tuple(np.asarray(a) for a in fetched)
        except (DeviceFailedError, DeviceCorruptionError) as exc:
            # serve THIS batch from the twin: either the shadow fold
            # above already produced its outputs, or the log still holds
            # the entry and one fold replays it
            outputs = twin_out if twin_out is not None else self._twin_fold()
            self._device_failure(exc)
            self._note_degraded(t0)
            return outputs

    def _stable_only(self):
        """The V == 0 path: stability unchanged — read it off the
        resident state (or the twin while failed over) with the plain
        non-donating kernel."""
        import jax
        import jax.numpy as jnp

        from fantoch_tpu.ops.table_ops import stable_clocks

        if self.degraded:
            t0 = time.perf_counter()
            self._twin_fold()
            stable = stable_clocks(
                jnp.asarray(self._twin_state[0]), threshold=self.threshold
            )
            result = np.asarray(jax.device_get(stable)).astype(np.int64)[
                : self.key_count
            ]
            self._note_degraded(t0)
            self._maybe_rebuild()
            return result
        self._materialize()
        stable = stable_clocks(self._frontier, threshold=self.threshold)
        return np.asarray(jax.device_get(stable)).astype(np.int64)[
            : self.key_count
        ]

    # --- introspection (tests / debugging) ---

    def frontiers(self) -> np.ndarray:
        """Host copy of the live ``int64[key_count, n]`` frontier matrix
        (a device round-trip; for tests and debugging only)."""
        if self._resident is None:
            if self.degraded and self._twin_state is not None:
                self._twin_fold()
                return self._twin_state[0][: self.key_count].astype(np.int64)
            if self._host_mirror is not None:
                return self._host_mirror[0][: self.key_count].astype(np.int64)
            return np.zeros((self.key_count, self.n), dtype=np.int64)
        return self._fetch_state()[0].astype(np.int64)[: self.key_count]
