"""Batched device kernels for the Newt/Tempo timestamp path.

The two hot loops of the table protocol/executor become array ops:

* ``batched_clock_proposal`` — the tensor twin of
  ``SequentialKeyClocks::proposal`` (fantoch_ps/src/protocol/common/table/
  clocks/keys/sequential.rs:36-47) for a batch of single-key commands:
  commands on the same key receive consecutive clocks continuing from the
  key's prior clock, each lower-bounded by its ``min_clock``.  Within one
  key group ordered j = 0..m-1::

      clock_j = max(min_j, clock_{j-1} + 1)
              = rank_j + max_{i <= j}(max(prior+1, min_i) - rank_i)

  a segmented max-scan of ``max(prior+1, min) - rank`` — one sort, one
  cummax, one scatter.  Vote ranges are born compressed: process p votes
  ``(prev_end + 1, clock_j)`` per command (votes.rs try_compress shapes).

* ``stable_clocks`` — the tensor twin of ``VotesTable::stable_clock``
  (fantoch_ps/src/executor/table/mod.rs:247-270) over all key tables at
  once: sort the per-process vote frontiers along the process axis and take
  the ``(n - threshold)``-th column.

Both are shape-static, fully jittable, and batch-friendly: one kernel
launch replaces B hash-map bumps / K BTree walks.

Clock width: device clocks are **31-bit windowed**.  Raw wall-clock micros
(Newt's real-time mode) overflow int32 after ~35 minutes, so callers must
rebase device clocks against a window floor before the kernel — the
natural floor is the GC'd stable clock the protocol already tracks, and
timestamps are only ever compared within a window (votes below the stable
floor are collected; proposals are bounded by floor + in-flight commands).
The host twins (table_clocks.py) use unbounded Python ints and need no
rebasing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from fantoch_tpu.core.compile_cache import register_program

_WINDOW_MAX = (1 << 31) - 1


def next_pow2(n: int) -> int:
    """Smallest power of two >= n — the shared padding schedule of every
    table-plane caller (key tables, vote columns, batch rows), so XLA
    compiles O(log) distinct programs as capacities grow."""
    p = 1
    while p < n:
        p *= 2
    return p


class ClockWindow:
    """31-bit device-clock window over unbounded host clocks.

    Owns the rebasing the module docstring demands of callers: host-side
    clocks are int64 (Newt's real-time mode uses wall-clock micros, which
    overflow int32 after ~35 minutes); device kernels see
    ``clock - floor`` as int32.  The floor advances monotonically with the
    protocol's GC'd stable clock — every *live* comparison happens above
    it, so subtracting it is order-preserving.

    ``advance`` returns the shift to apply to device-resident clock tables
    (see :func:`shift_table`); entries at or below the new floor clamp to
    0, which keeps proposal semantics (``max(prior + 1, min)``) because a
    floor-or-older prior constrains nothing above the floor.
    """

    __slots__ = ("_floor",)

    def __init__(self, floor: int = 0):
        assert floor >= 0
        self._floor = int(floor)

    @property
    def floor(self) -> int:
        return self._floor

    def rebase(self, values) -> np.ndarray:
        """Host int64 clocks -> int32 device clocks (values - floor).

        Zero stays zero (the \"no clock yet\" bottom), everything else must
        lie in (floor, floor + 2^31)."""
        values = np.asarray(values, dtype=np.int64)
        out = np.where(values == 0, 0, values - self._floor)
        # strict: a clock exactly at the floor would alias the bottom (0)
        assert (out[values != 0] > 0).all(), (
            f"clock at or below the window floor {self._floor}: "
            f"min {values.min()}"
        )
        assert (out <= _WINDOW_MAX).all(), (
            f"clock overflows the 31-bit window above floor {self._floor}: "
            f"max {values.max()} (advance the window)"
        )
        return out.astype(np.int32)

    def restore(self, device_values) -> np.ndarray:
        """Device int32 clocks -> host int64 clocks (values + floor)."""
        vals = np.asarray(device_values, dtype=np.int64)
        return np.where(vals == 0, 0, vals + self._floor)

    def advance(self, new_floor: int) -> int:
        """Move the floor forward (monotone); returns the int32 shift to
        subtract from device-resident clock tables."""
        new_floor = int(new_floor)
        assert new_floor >= self._floor, "window floor is monotone"
        shift = new_floor - self._floor
        assert shift <= _WINDOW_MAX
        self._floor = new_floor
        return shift


@jax.jit
def shift_table(table: jax.Array, shift) -> jax.Array:
    """Rebase a device-resident clock table after ``ClockWindow.advance``:
    entries at or below the new floor clamp to 0 (no constraint)."""
    return jnp.maximum(table - jnp.int32(shift), 0)


def _seg_max_combiner(a, b):
    """Associative combiner for segmented running max: keep the right
    operand's value unless both sides share a segment — no magic offsets,
    no overflow for any clock magnitude."""
    a_seg, a_val = a
    b_seg, b_val = b
    return b_seg, jnp.where(a_seg == b_seg, jnp.maximum(a_val, b_val), b_val)


def segmented_running_max(seg_id: jax.Array, values: jax.Array, axis: int = 0):
    """Running max of ``values`` within segments of equal ``seg_id`` along
    ``axis`` (segments must be contiguous along that axis).  The shared
    core of the proposal kernels here and the mesh-wide proposal of
    parallel/mesh_step.py; ``seg_id`` broadcasts against ``values``."""
    seg = jnp.broadcast_to(seg_id, values.shape)
    _, running = jax.lax.associative_scan(
        _seg_max_combiner, (seg, values), axis=axis
    )
    return running


def _proposal_core(
    prior: jax.Array,  # int32[K]
    key: jax.Array,  # int32[B]
    min_clock: jax.Array,  # int32[B]
):
    """Traceable body of :func:`batched_clock_proposal` — shared with the
    fused table-round kernels below, which inline it inside one dispatch."""
    batch = key.shape[0]
    idx = jnp.arange(batch, dtype=jnp.int32)

    # group commands by key, preserving batch order inside groups
    perm = jnp.argsort(key, stable=True).astype(jnp.int32)
    k_sorted = key[perm]
    seg_start = jnp.concatenate(
        [jnp.ones((1,), bool), k_sorted[1:] != k_sorted[:-1]]
    )
    # rank within the key group
    seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
    group_first = jnp.where(seg_start, idx, 0)
    group_first = jax.lax.associative_scan(jnp.maximum, group_first)
    rank = idx - group_first

    base = jnp.maximum(prior[k_sorted] + 1, min_clock[perm])  # max(prior+1, min)
    # segmented running max of (base - rank), resetting at segment starts
    running = segmented_running_max(seg_id, base - rank)
    clock_sorted = rank + running

    clock = jnp.zeros((batch,), jnp.int32).at[perm].set(clock_sorted)
    # voter's range start: previous clock on this key + 1
    prev_clock_sorted = jnp.where(
        seg_start, prior[k_sorted], jnp.roll(clock_sorted, 1)
    )
    vote_start = jnp.zeros((batch,), jnp.int32).at[perm].set(prev_clock_sorted + 1)

    new_prior = prior.at[key].max(clock)
    return clock, vote_start, new_prior


@jax.jit
def batched_clock_proposal(
    prior: jax.Array,  # int32[K] — key clock before the batch
    key: jax.Array,  # int32[B] — key bucket per command
    min_clock: jax.Array,  # int32[B] — proposal lower bound (0 if none)
):
    """Returns ``(clock[B], vote_start[B], new_prior[K])``.

    ``clock`` is the proposed timestamp per command; the voter's consumed
    range for command i is ``(vote_start[i], clock[i])``; ``new_prior`` is
    the key-clock table after the whole batch (== the last clock per key).
    Batch order is proposal order within each key (the worker's arrival
    order, as in the sequential reference).
    """
    return _proposal_core(prior, key, min_clock)


@functools.partial(jax.jit, donate_argnums=(0,))
def resident_clock_proposal(
    prior: jax.Array,  # int32[K], DONATED — stays device-resident
    key: jax.Array,
    min_clock: jax.Array,
):
    """:func:`batched_clock_proposal` with the key-clock table donated:
    callers thread ``new_prior`` into the next call and the table never
    crosses the host boundary between batches (the mesh_step donation
    pattern applied to the proposal plane)."""
    return _proposal_core(prior, key, min_clock)


@functools.partial(jax.jit, donate_argnums=(0,))
def resident_clock_bump(
    prior: jax.Array,  # int32[K], DONATED — stays device-resident
    idx: jax.Array,  # int32[M] — bumped buckets (pad rows use K-1)
    clock: jax.Array,  # int32[M] — bumped-to clock per bucket (pad: 0)
):
    """Fold host-side scalar clock bumps into the resident key-clock
    table WITHOUT dropping residency: a scatter-max of the bumped
    buckets' new clocks (bumps are monotone, so max == set here, and max
    keeps pad rows harmless).  This is what keeps live Newt's scalar
    detached-bumps between submit batches from degrading the proposal
    path to upload-per-batch: the table stays on device and only the
    O(bumps) columns cross the host boundary (the BENCH_DEV round-6
    "device-side bump kernel" note, shipped)."""
    return prior.at[idx].max(clock)


@functools.partial(jax.jit, static_argnames=("threshold",))
def stable_clocks(frontiers: jax.Array, *, threshold: int) -> jax.Array:
    """Stable clock per key: the ``(n - threshold)``-th smallest of the n
    per-process vote frontiers (``int32[K, n] -> int32[K]``)."""
    n = frontiers.shape[1]
    assert threshold <= n
    return jnp.sort(frontiers, axis=1)[:, n - threshold]


# ---------------------------------------------------------------------------
# Device-resident votes-table plane: the commit path as donated dispatches.
#
# The host twin of the vote state is one RangeEventSet per (key, process)
# (executor/table.py VotesTable._votes): sorted disjoint non-adjacent
# ranges whose *frontier* (largest contiguous voted prefix) feeds the
# stability order statistic.  On device the state is the frontier matrix
# ``int32[K, n]`` alone; a merged vote run that lands beyond a frontier
# gap cannot advance it and is returned to the caller as *residual* —
# the caller re-feeds residuals with the next batch, so once the gap
# fills the frontier catches up exactly as the RangeEventSet would.
# After interval-merging, runs per (key, process) are disjoint and
# non-adjacent, so AT MOST ONE run per group can extend the frontier in
# a batch (the next run starts > extended_end + 1 by construction) —
# which is what makes the update a single scatter-max, no iteration.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("threshold",), donate_argnums=(0,))
def fused_votes_commit(
    frontier: jax.Array,  # int32[K, n], DONATED — resident vote frontiers
    vkey: jax.Array,  # int32[V] — key bucket per vote range
    vby: jax.Array,  # int32[V] — voting process, 0-based column index
    vstart: jax.Array,  # int32[V]
    vend: jax.Array,  # int32[V]
    valid: jax.Array,  # bool[V] — pad rows False
    *,
    threshold: int,
):
    """One dispatch for the executor side of the table plane: coalesce
    vote ranges per (key, process), advance the resident frontiers, and
    compute every key's stable clock.

    Returns ``(new_frontier[K, n], stable[K], run_key[V], run_by[V],
    run_start[V], run_end[V], residual[V])``: the ``run_*`` columns hold
    the merged vote runs (one slot per run, invalid slots have
    ``residual`` False) and ``residual`` marks runs that start beyond
    the frontier gap — the caller buffers those and re-feeds them with
    the next batch (RangeEventSet semantics preserved across batches).
    """
    K, n = frontier.shape
    V = vkey.shape[0]
    int_min = jnp.iinfo(jnp.int32).min
    slot = jnp.arange(V, dtype=jnp.int32)

    # sort by (group, start); invalid rows get a shared out-of-range group
    gid = jnp.where(valid, vkey * n + vby, K * n)
    order = jnp.lexsort((vstart, gid)).astype(jnp.int32)
    g = gid[order]
    s = vstart[order]
    e = vend[order]
    valid_s = valid[order]

    # interval merge within each group: runs break where a start clears
    # the group's running max end by more than 1 (classic sorted-interval
    # merge, the host twin of handle_batch_arrays' numpy coalescing)
    grp_start = jnp.concatenate([jnp.ones((1,), bool), g[1:] != g[:-1]])
    run_max_end = segmented_running_max(g, e)
    prev_max = jnp.roll(run_max_end, 1)
    new_run = grp_start | (s > prev_max + 1)
    rid = jnp.cumsum(new_run.astype(jnp.int32)) - 1  # [V], non-decreasing

    # per-run columns: end = scatter-max, head position = scatter-max of
    # the (unique-per-run) head index, everything else gathers at head
    run_end = jnp.full((V,), int_min, jnp.int32).at[rid].max(e)
    run_head = jnp.zeros((V,), jnp.int32).at[rid].max(
        jnp.where(new_run, slot, 0)
    )
    num_runs = rid[V - 1] + 1
    run_exists = slot < num_runs
    run_valid = run_exists & valid_s[run_head]
    run_key = jnp.where(run_valid, vkey[order][run_head], 0)
    run_by = jnp.where(run_valid, vby[order][run_head], 0)
    run_start = s[run_head]

    # frontier update: a run extends iff it touches the contiguous prefix
    f0 = frontier[run_key, run_by]
    extends = run_valid & (run_start <= f0 + 1) & (run_end > f0)
    residual = run_valid & (run_start > f0 + 1) & (run_end > f0)
    new_frontier = frontier.at[run_key, run_by].max(
        jnp.where(extends, run_end, 0)
    )

    stable = jnp.sort(new_frontier, axis=1)[:, n - threshold]
    return new_frontier, stable, run_key, run_by, run_start, run_end, residual


register_program("votes_commit", fused_votes_commit)


def _fused_round_core(prior, frontier, key, min_clock, threshold, voters):
    """One full table round in-trace: proposal + contiguous vote
    application + stability.  The dense serving regime: the first
    ``voters`` processes vote every consumed range each round, so the
    per-key merged vote run is ``(prior + 1, new_prior)`` — contiguous
    with a voter's frontier iff that frontier already reached ``prior``.
    Voters with a gap (``gaps`` counts them) do NOT advance — callers
    fall back to the exact residual-tracking path when gaps appear."""
    K, n = frontier.shape
    clock, vote_start, new_prior = _proposal_core(prior, key, min_clock)
    touched = jnp.zeros((K,), bool).at[key].set(True)
    voter = jnp.arange(n, dtype=jnp.int32) < voters  # [n]
    contiguous = frontier >= prior[:, None]  # [K, n]
    lane = touched[:, None] & voter[None, :]
    new_frontier = jnp.where(
        lane & contiguous,
        jnp.maximum(frontier, new_prior[:, None]),
        frontier,
    )
    gaps = (lane & ~contiguous).sum().astype(jnp.int32)
    stable = jnp.sort(new_frontier, axis=1)[:, n - threshold]
    executable = clock <= stable[key]
    return new_prior, new_frontier, clock, vote_start, executable, gaps


@functools.partial(
    jax.jit, static_argnames=("threshold", "voters"), donate_argnums=(0, 1)
)
def fused_table_round(
    prior: jax.Array,  # int32[K], DONATED
    frontier: jax.Array,  # int32[K, n], DONATED
    key: jax.Array,  # int32[B]
    min_clock: jax.Array,  # int32[B]
    *,
    threshold: int,
    voters: int,
):
    """Proposal + vote coalescing + frontier update + stability as ONE
    donated dispatch (the full Newt commit round for a batch of
    single-key commands in the dense all-votes regime).  Returns
    ``(new_prior, new_frontier, clock[B], vote_start[B], executable[B],
    gaps[])``; callers must keep the last key bucket as a scratch/pad
    bucket (the BatchedKeyClocks convention) if they pad batches."""
    return _fused_round_core(prior, frontier, key, min_clock, threshold, voters)


register_program("table_round", fused_table_round)


@functools.partial(
    jax.jit, static_argnames=("threshold", "voters"), donate_argnums=(0, 1)
)
def fused_table_rounds(
    prior: jax.Array,  # int32[K], DONATED
    frontier: jax.Array,  # int32[K, n], DONATED
    keys: jax.Array,  # int32[S, B] — S chained batches
    min_clocks: jax.Array,  # int32[S, B]
    *,
    threshold: int,
    voters: int,
):
    """``lax.scan`` chain of :func:`fused_table_round`: S batches commit
    in ONE dispatch, amortizing the host round-trip the same way the
    graph bench's chained in-dispatch resolves do.  Returns
    ``(prior, frontier, clock[S, B], vote_start[S, B], executable[S, B],
    gaps[S])``."""

    def body(carry, xs):
        prior, frontier = carry
        key, mc = xs
        new_prior, new_frontier, clock, vote_start, executable, gaps = (
            _fused_round_core(prior, frontier, key, mc, threshold, voters)
        )
        return (new_prior, new_frontier), (clock, vote_start, executable, gaps)

    (prior, frontier), (clock, vote_start, executable, gaps) = jax.lax.scan(
        body, (prior, frontier), (keys, min_clocks)
    )
    return prior, frontier, clock, vote_start, executable, gaps
