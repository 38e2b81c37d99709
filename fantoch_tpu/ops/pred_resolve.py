"""Device kernel for Caesar's two-phase predecessor ordering.

Reference: fantoch_ps/src/executor/pred/mod.rs:132-186 — a committed
command executes after (phase 1) every dependency is committed and
(phase 2) every LOWER-clock dependency is executed.  Timestamps are
unique and totally ordered, so there are no cycles to collapse; the host
twin (fantoch_tpu/executor/pred.py) maintains the two phases as
per-vertex countdown counters fed by pending indexes.

The device formulation batches both countdowns: dependencies are an
``int32[B, W]`` slot matrix (row indices into the batch, ``TERMINAL`` for
already-executed/absent deps, ``MISSING`` for uncommitted ones), and one
``lax.while_loop`` executes the monotone fixpoint

    executable(v) = committed(v) and for every dep slot d of v:
                      d is TERMINAL, or executed(d), or clock(d) > clock(v)

— each iteration is one scatter-free vectorized pass (the countdown
decrements of the host twin become a masked ``all`` over the dep matrix),
and at least one clock-minimal executable vertex finalizes per iteration,
so ``B`` iterations bound the loop; the early-exit fires as soon as a
pass makes no progress (missing-blocked residue waits for a later batch).

Output order is (clock, dot)-sorted among the executed — exactly the
commit-timestamp order the PredecessorsExecutor promises for conflicts.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from fantoch_tpu.core.compile_cache import register_program
from fantoch_tpu.ops.graph_resolve import MISSING, TERMINAL


class PredResolution(NamedTuple):
    order: jax.Array  # int32[B] — executed rows first, (clock, dot) sorted
    executed: jax.Array  # bool[B]


@jax.jit
def resolve_pred(
    deps: jax.Array,  # int32[B, W] row indices / TERMINAL / MISSING
    clock: jax.Array,  # int32[B] — committed timestamp (unique with dot)
    dot_src: jax.Array,  # int32[B]
    dot_seq: jax.Array,  # int32[B]
    committed: jax.Array,  # bool[B] — False rows are pads / uncommitted
) -> PredResolution:
    batch, _width = deps.shape
    int_max = jnp.iinfo(jnp.int32).max
    safe = jnp.maximum(deps, 0)

    # phase 2's lower-clock comparison, precomputed per slot: a dep with a
    # HIGHER (clock, dot) never blocks (it executes after us)
    my_key = (clock, dot_src, dot_seq)
    dep_key = (clock[safe], dot_src[safe], dot_seq[safe])

    def lex_gt(a, b):
        """a > b on (clock, src, seq) triples, vectorized."""
        (ac, as_, aq), (bc, bs, bq) = a, b
        return (
            (ac > bc)
            | ((ac == bc) & (as_ > bs))
            | ((ac == bc) & (as_ == bs) & (aq > bq))
        )

    dep_higher = lex_gt(dep_key, tuple(k[:, None] for k in my_key))
    # a dep slot never blocks iff it is TERMINAL (already executed /
    # absent) or a COMMITTED dep with a higher (clock, dot) — phase 2
    # skips those.  An uncommitted dep's clock is meaningless (it may yet
    # commit lower), so MISSING and in-batch-uncommitted deps block
    # phase 1 outright.
    in_batch = deps >= 0
    dep_committed = in_batch & committed[safe]
    never_blocks = (deps == TERMINAL) | (dep_committed & dep_higher)

    def body(state):
        executed, _changed = state
        dep_ok = never_blocks | (dep_committed & executed[safe])
        new = committed & dep_ok.all(axis=1)
        changed = (new & ~executed).any()
        return new | executed, changed

    def cond(state):
        _executed, changed = state
        return changed

    executed0 = jnp.zeros((batch,), bool)
    first, changed0 = body((executed0, jnp.bool_(True)))
    executed, _ = jax.lax.while_loop(
        cond, body, (first, changed0)
    )
    sort_clock = jnp.where(executed, clock, int_max)
    order = jnp.lexsort((dot_seq, dot_src, sort_clock)).astype(jnp.int32)
    return PredResolution(order, executed)


# ---------------------------------------------------------------------------
# resident plane step (executor/pred_plane.DevicePredPlane)
# ---------------------------------------------------------------------------


class PredPlaneStep(NamedTuple):
    """One resident dispatch's output: the donated state back, plus which
    slots executed THIS dispatch.  Execution order among the newly
    executed is (clock, src) — computed HOST-side from the plane's slot
    columns (a dynamic-size host lexsort over the executed handful beats
    a full-capacity device sort every dispatch)."""

    deps: jax.Array  # int32[C, W] — resident slot matrix (donated through)
    clock: jax.Array  # int32[C]
    src: jax.Array  # int32[C]
    occ: jax.Array  # bool[C] — slot holds a committed command
    executed: jax.Array  # bool[C]
    newly: jax.Array  # bool[C] — executed by this dispatch


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def resolve_pred_plane_step(
    deps: jax.Array,  # int32[C, W] slot indices / TERMINAL / MISSING
    clock: jax.Array,  # int32[C] — committed timestamp seq
    src: jax.Array,  # int32[C] — timestamp process id (clock uniqueness)
    occ: jax.Array,  # bool[C]
    executed: jax.Array,  # bool[C]
    u_row: jax.Array,  # int32[U] — new slot ids (pad = C, dropped)
    u_deps: jax.Array,  # int32[U, W]
    u_clock: jax.Array,  # int32[U]
    u_src: jax.Array,  # int32[U]
    p_row: jax.Array,  # int32[P] — dep-patch cells (pad = C, dropped)
    p_col: jax.Array,  # int32[P]
    p_val: jax.Array,  # int32[P] — slot id or TERMINAL
) -> PredPlaneStep:
    """The resident twin of :func:`resolve_pred` (executor/pred_plane.py).

    The whole pending window lives ON DEVICE across dispatches: ``C``
    slots of (deps, clock, src) with occupancy and executed flags, all
    donated in-place.  Each dispatch (1) installs the batch's new rows,
    (2) re-points dep cells whose missing dot just committed (the
    residual re-feed: missing-blocked rows stay resident and wake when a
    later feed patches them — the pred-plane analog of the table plane's
    beyond-gap runs), then (3) runs the same monotone two-phase fixpoint
    as :func:`resolve_pred` over the *entire* resident window, so rows
    blocked across any number of earlier feeds execute the moment their
    chain completes.

    Slot recycling is host-owned: a freed slot is simply overwritten by a
    later ``u_row`` install (occ/executed/clock/deps all re-set), so no
    clear pass is needed — the host only frees a slot once nothing
    references it.
    """
    cap, _width = deps.shape

    # (1) new rows: full-row install (reused slots are fully overwritten)
    deps = deps.at[u_row].set(u_deps, mode="drop")
    clock = clock.at[u_row].set(u_clock, mode="drop")
    src = src.at[u_row].set(u_src, mode="drop")
    occ = occ.at[u_row].set(True, mode="drop")
    executed = executed.at[u_row].set(False, mode="drop")
    # (2) dep patches: MISSING cells whose dot just committed (or was
    # recovered as a noop -> TERMINAL)
    deps = deps.at[p_row, p_col].set(p_val, mode="drop")

    # (3) fixpoint: executable(v) = occ(v) and every dep slot is
    # TERMINAL, executed, or a committed dep with a higher (clock, src)
    # key (phase 2's lower-clock rule; MISSING always blocks phase 1)
    in_res = deps >= 0
    safe = jnp.maximum(deps, 0)
    dep_clock, dep_src = clock[safe], src[safe]
    my_clock, my_src = clock[:, None], src[:, None]
    dep_higher = (dep_clock > my_clock) | (
        (dep_clock == my_clock) & (dep_src > my_src)
    )
    never_blocks = (deps == TERMINAL) | (in_res & occ[safe] & dep_higher)
    executed0 = executed

    def body(state):
        done, _changed = state
        dep_ok = never_blocks | (in_res & done[safe])
        new = occ & dep_ok.all(axis=1)
        changed = (new & ~done).any()
        return new | done, changed

    def cond(state):
        _done, changed = state
        return changed

    first, changed0 = body((executed0, jnp.bool_(True)))
    done, _ = jax.lax.while_loop(cond, body, (first, changed0))

    newly = done & ~executed0
    return PredPlaneStep(deps, clock, src, occ, done, newly)


register_program("pred_plane_step", resolve_pred_plane_step)
register_program("pred_resolve", resolve_pred)
