"""Pallas-fused resolve kernels for the hot device-plane dispatches.

The BASELINE north star names a *Pallas kernel* for conflict detection +
order resolution.  XLA fuses elementwise work but materializes every
scatter/gather boundary to HBM — on a plane dispatch that is the install
scatter, the waiter-index patch, *and every iteration* of the dependency
fixpoint.  These kernels express each dispatch body as ONE gridless
whole-array ``pallas_call`` whose intermediates (the ``int32[C, W]``
dep-slot matrix, the dot's clock/src columns, the fixpoint's executable
mask) would stay VMEM-resident from the install through the last
fixpoint sweep.

**What the chip says** (TPU v5 lite, jax 0.9.0, ``interpret=False``,
C=4096 W=4, PR 21's probe): none of the four families lowers.
``pred_plane_step`` and ``graph_plane_step`` (keyed, general and
general_resident alike) are refused with *"Unimplemented primitive in
Pallas TPU lowering for KernelType.TC: scatter"*; ``votes_commit`` and
``table_round`` with *"...: sort"*.  They never reach Mosaic.  So the
composed XLA programs are the route on every backend, and this module
is an explicit opt-in: interpret mode on the CPU (the parity
instrument), a loud failure on the TPU.  Rewriting a kernel so that it
lowers, or deleting the module, is ROADMAP S4/D1.

Three kernel families, matching the three plane dispatches:

* :func:`pred_plane_step_pallas` — Caesar's resident window step
  (install new rows + dep-cell patches + the two-phase committed/
  lower-clock fixpoint) as one hand-written kernel body.
* :func:`graph_plane_step_pallas` — the EPaxos/Atlas backlog step
  (install + waiter-index patch + executed fold + mode-routed resolve).
  The resolve core is shared *by construction* with the composed path
  (``ops.graph_resolve.graph_plane_step_core``): the kernel body traces
  the identical program, so resolved/stuck/rank/order parity is exact.
* :func:`votes_commit_pallas` / :func:`table_round_pallas` — the fused
  table round (vote-range coalesce + frontier advance + stability order
  statistic as one kernel), sharing ``ops.table_ops`` cores the same
  way.

**Contract** (enforced by tests/test_pallas_resolve.py): bit-for-bit
equality with the composed kernels — same resolved/stuck/rank/order,
same residual-column protocol — and unchanged donation discipline: the
resident state aliases in-place through ``input_output_aliases`` under
the same ``donate_argnums`` the composed programs use, so
``resident_uploads == 1`` holds whichever route serves.

**Routing** (``Config.pallas_kernels`` > ``FANTOCH_PALLAS`` env > off):
the public ops symbols (``resolve_pred_plane_step``,
``resolve_graph_plane_step``, ``fused_votes_commit``,
``fused_table_round``) are routers that consult :func:`pallas_enabled`
per dispatch.  There is no fallback: a route that is on and does not
compile raises, and :func:`pallas_status` reports the route each family
was actually served by.
"""

from __future__ import annotations

import functools
import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from fantoch_tpu.ops.graph_resolve import (
    GraphPlaneStep,
    TERMINAL,
    graph_plane_step_core,
)
from fantoch_tpu.ops.pred_resolve import PredPlaneStep
from fantoch_tpu.ops.table_ops import _fused_round_core, _votes_commit_core

# ---------------------------------------------------------------------------
# routing: Config.pallas_kernels > FANTOCH_PALLAS env > off
# ---------------------------------------------------------------------------

_override: Optional[bool] = None
# dispatches served per family and route, counted by the router itself:
# what pallas_status() reports is what ran, not what was asked for
_served: Dict[str, Dict[str, int]] = {}


def set_pallas_kernels(enabled: Optional[bool]) -> None:
    """Process-global route override: ``True``/``False`` pin the route,
    ``None`` returns to env resolution.  Like the recompile counters
    this is process-global — co-hosted executors with conflicting
    configs share one route (last writer wins)."""
    global _override
    _override = enabled


def apply_pallas_config(config) -> None:
    """Executor-construction seam: fold ``Config.pallas_kernels`` into
    the route (an explicit config value beats the env var; ``None``
    leaves env resolution in place — the ``Config.device_graph_plane``
    precedence convention)."""
    value = getattr(config, "pallas_kernels", None)
    if value is not None:
        set_pallas_kernels(bool(value))


def pallas_enabled() -> bool:
    """Resolve the route for the next dispatch: explicit override
    (config) > ``FANTOCH_PALLAS`` env > off.  Off is the default on
    every backend because the composed programs are the route that
    compiles on every backend (module docstring)."""
    if _override is not None:
        return _override
    env = os.environ.get("FANTOCH_PALLAS")
    if env is not None and env != "":
        return env not in ("0", "false", "False", "off")
    return False


def _interpret() -> bool:
    """Interpret-mode switch: anything that is not a real TPU backend
    runs the kernel body through the Pallas interpreter (bit-for-bit
    the same ops, no Mosaic lowering)."""
    return jax.default_backend() != "tpu"


def pallas_status() -> Dict[str, object]:
    """Routing introspection for bench rows and the smokes: the route
    the next dispatch would take, and per family the dispatches each
    route has actually served in this process."""
    return {
        "enabled": pallas_enabled(),
        "interpret": _interpret(),
        "served": {family: dict(routes) for family, routes in _served.items()},
    }


def route_dispatch(family: str, pallas_fn, composed_fn, args, kwargs):
    """The per-dispatch router: the composed program when the route is
    off, the Pallas kernel when it is on.  Nothing is caught — a kernel
    that does not compile on this backend raises out of the dispatch."""
    route, fn = (
        ("pallas", pallas_fn) if pallas_enabled() else ("xla", composed_fn)
    )
    out = fn(*args, **kwargs)
    served = _served.setdefault(family, {})
    served[route] = served.get(route, 0) + 1
    return out


# ---------------------------------------------------------------------------
# pred plane: install + patch + two-phase fixpoint, hand-written
# ---------------------------------------------------------------------------


def _pred_step_kernel(
    deps_ref, clock_ref, src_ref, occ_ref, exec_ref,
    u_row_ref, u_deps_ref, u_clock_ref, u_src_ref,
    p_row_ref, p_col_ref, p_val_ref,
    o_deps_ref, o_clock_ref, o_src_ref, o_occ_ref, o_exec_ref, o_newly_ref,
):
    """The fused pred-plane dispatch body.  All refs are whole-window
    VMEM blocks; the five state refs alias their outputs in place
    (``input_output_aliases``), so the window never leaves the core
    between the install scatter and the last fixpoint sweep.

    The math is the composed ``resolve_pred_plane_step`` body verbatim
    (ops/pred_resolve.py): (1) full-row install, (2) dep-cell patches,
    (3) the monotone two-phase fixpoint — ``executable(v) = occ(v) and
    every dep slot TERMINAL / executed / committed-with-higher-(clock,
    src)``, iterated to no-change.  Identical deterministic recurrence
    => bit-for-bit identical outputs (the parity contract)."""
    deps = deps_ref[...]
    clock = clock_ref[...]
    src = src_ref[...]
    occ = occ_ref[...]
    executed0 = exec_ref[...]
    u_row = u_row_ref[...]

    # (1) install new rows (pad rows carry row == C and drop)
    deps = deps.at[u_row].set(u_deps_ref[...], mode="drop")
    clock = clock.at[u_row].set(u_clock_ref[...], mode="drop")
    src = src.at[u_row].set(u_src_ref[...], mode="drop")
    occ = occ.at[u_row].set(True, mode="drop")
    executed0 = executed0.at[u_row].set(False, mode="drop")
    # (2) dep patches (missing dots that just committed / noop TERMINAL)
    deps = deps.at[p_row_ref[...], p_col_ref[...]].set(
        p_val_ref[...], mode="drop"
    )

    # (3) two-phase fixpoint over the whole resident window
    in_res = deps >= 0
    safe = jnp.maximum(deps, 0)
    dep_clock, dep_src = clock[safe], src[safe]
    dep_higher = (dep_clock > clock[:, None]) | (
        (dep_clock == clock[:, None]) & (dep_src > src[:, None])
    )
    never_blocks = (deps == TERMINAL) | (in_res & occ[safe] & dep_higher)

    def body(state):
        done, _changed = state
        dep_ok = never_blocks | (in_res & done[safe])
        new = occ & dep_ok.all(axis=1)
        changed = (new & ~done).any()
        return new | done, changed

    first, changed0 = body((executed0, jnp.bool_(True)))
    done, _ = jax.lax.while_loop(lambda s: s[1], body, (first, changed0))

    o_deps_ref[...] = deps
    o_clock_ref[...] = clock
    o_src_ref[...] = src
    o_occ_ref[...] = occ
    o_exec_ref[...] = done
    o_newly_ref[...] = done & ~executed0


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def pred_plane_step_pallas(
    deps, clock, src, occ, executed,
    u_row, u_deps, u_clock, u_src, p_row, p_col, p_val,
) -> PredPlaneStep:
    """Pallas twin of ``resolve_pred_plane_step``: same signature, same
    donation set, same :class:`PredPlaneStep` out — the resident tuple
    aliases in place via ``input_output_aliases`` so donation semantics
    match the composed jit exactly."""
    from jax.experimental import pallas as pl

    cap, width = deps.shape
    out = pl.pallas_call(
        _pred_step_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((cap, width), deps.dtype),
            jax.ShapeDtypeStruct((cap,), clock.dtype),
            jax.ShapeDtypeStruct((cap,), src.dtype),
            jax.ShapeDtypeStruct((cap,), jnp.bool_),
            jax.ShapeDtypeStruct((cap,), jnp.bool_),
            jax.ShapeDtypeStruct((cap,), jnp.bool_),
        ],
        input_output_aliases={0: 0, 1: 1, 2: 2, 3: 3, 4: 4},
        interpret=_interpret(),
    )(deps, clock, src, occ, executed,
      u_row, u_deps, u_clock, u_src, p_row, p_col, p_val)
    return PredPlaneStep(*out)


# ---------------------------------------------------------------------------
# graph plane: install + patch + executed fold + mode-routed resolve
# ---------------------------------------------------------------------------


def _graph_step_kernel(
    deps_ref, key_ref, src_ref, seq_ref, occ_ref, exec_ref,
    u_row_ref, u_deps_ref, u_key_ref, u_src_ref, u_seq_ref,
    p_row_ref, p_col_ref, p_val_ref, e_row_ref,
    o_deps_ref, o_key_ref, o_src_ref, o_seq_ref, o_occ_ref, o_exec_ref,
    o_order_ref, o_newly_ref, o_stuck_ref, o_leader_ref,
    *, mode: str,
):
    """The fused graph-plane dispatch body: loads the whole backlog into
    VMEM values and traces ``graph_plane_step_core`` — the exact
    composed program — over them, so parity is by construction and the
    prologue scatters, the keyed compression, and the resolve fixpoint
    share one on-core program (no HBM round-trip at the scatter
    boundaries XLA would materialize)."""
    out = graph_plane_step_core(
        deps_ref[...], key_ref[...], src_ref[...], seq_ref[...],
        occ_ref[...], exec_ref[...],
        u_row_ref[...], u_deps_ref[...], u_key_ref[...], u_src_ref[...],
        u_seq_ref[...],
        p_row_ref[...], p_col_ref[...], p_val_ref[...], e_row_ref[...],
        mode=mode,
    )
    o_deps_ref[...] = out.deps
    o_key_ref[...] = out.key
    o_src_ref[...] = out.src
    o_seq_ref[...] = out.seq
    o_occ_ref[...] = out.occ
    o_exec_ref[...] = out.executed
    o_order_ref[...] = out.order
    o_newly_ref[...] = out.newly
    o_stuck_ref[...] = out.stuck
    o_leader_ref[...] = out.leader


@functools.partial(
    jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5), static_argnames=("mode",)
)
def graph_plane_step_pallas(
    deps, key, src, seq, occ, executed,
    u_row, u_deps, u_key, u_src, u_seq,
    p_row, p_col, p_val, e_row,
    *, mode: str,
) -> GraphPlaneStep:
    """Pallas twin of ``resolve_graph_plane_step``: same signature,
    donation set and :class:`GraphPlaneStep` out, resident columns
    aliased in place."""
    from jax.experimental import pallas as pl

    cap, width = deps.shape
    i32 = deps.dtype
    out = pl.pallas_call(
        functools.partial(_graph_step_kernel, mode=mode),
        out_shape=[
            jax.ShapeDtypeStruct((cap, width), i32),
            jax.ShapeDtypeStruct((cap,), i32),
            jax.ShapeDtypeStruct((cap,), i32),
            jax.ShapeDtypeStruct((cap,), i32),
            jax.ShapeDtypeStruct((cap,), jnp.bool_),
            jax.ShapeDtypeStruct((cap,), jnp.bool_),
            jax.ShapeDtypeStruct((cap,), i32),
            jax.ShapeDtypeStruct((cap,), jnp.bool_),
            jax.ShapeDtypeStruct((cap,), jnp.bool_),
            jax.ShapeDtypeStruct((cap,), i32),
        ],
        input_output_aliases={0: 0, 1: 1, 2: 2, 3: 3, 4: 4, 5: 5},
        interpret=_interpret(),
    )(deps, key, src, seq, occ, executed,
      u_row, u_deps, u_key, u_src, u_seq, p_row, p_col, p_val, e_row)
    return GraphPlaneStep(*out)


# ---------------------------------------------------------------------------
# table plane: vote-range coalesce + frontier + stability as one kernel
# ---------------------------------------------------------------------------


def _votes_commit_kernel(
    frontier_ref, vkey_ref, vby_ref, vstart_ref, vend_ref, valid_ref,
    o_frontier_ref, o_stable_ref, o_rkey_ref, o_rby_ref, o_rstart_ref,
    o_rend_ref, o_residual_ref,
    *, threshold: int,
):
    """The fused table commit body: interval coalesce per (key, process)
    + frontier scatter-max + the stability order statistic, traced from
    the shared ``_votes_commit_core`` over VMEM-resident values —
    including the residual classification (beyond-gap runs return to the
    caller exactly as the composed kernel returns them)."""
    out = _votes_commit_core(
        frontier_ref[...], vkey_ref[...], vby_ref[...], vstart_ref[...],
        vend_ref[...], valid_ref[...], threshold=threshold,
    )
    (o_frontier_ref[...], o_stable_ref[...], o_rkey_ref[...],
     o_rby_ref[...], o_rstart_ref[...], o_rend_ref[...],
     o_residual_ref[...]) = out


@functools.partial(jax.jit, static_argnames=("threshold",), donate_argnums=(0,))
def votes_commit_pallas(frontier, vkey, vby, vstart, vend, valid, *, threshold):
    """Pallas twin of ``fused_votes_commit``: same signature, same
    donated frontier (aliased in place), same 7-tuple out including the
    residual columns."""
    from jax.experimental import pallas as pl

    K, n = frontier.shape
    V = vkey.shape[0]
    i32 = frontier.dtype
    return tuple(
        pl.pallas_call(
            functools.partial(_votes_commit_kernel, threshold=threshold),
            out_shape=[
                jax.ShapeDtypeStruct((K, n), i32),
                jax.ShapeDtypeStruct((K,), i32),
                jax.ShapeDtypeStruct((V,), i32),
                jax.ShapeDtypeStruct((V,), i32),
                jax.ShapeDtypeStruct((V,), i32),
                jax.ShapeDtypeStruct((V,), i32),
                jax.ShapeDtypeStruct((V,), jnp.bool_),
            ],
            input_output_aliases={0: 0},
            interpret=_interpret(),
        )(frontier, vkey, vby, vstart, vend, valid)
    )


def _table_round_kernel(
    prior_ref, frontier_ref, key_ref, min_clock_ref,
    o_prior_ref, o_frontier_ref, o_clock_ref, o_vstart_ref, o_exec_ref,
    o_gaps_ref,
    *, threshold: int, voters: int,
):
    """The fused dense table round (proposal + contiguous votes +
    stability), traced from ``_fused_round_core`` over VMEM values."""
    out = _fused_round_core(
        prior_ref[...], frontier_ref[...], key_ref[...], min_clock_ref[...],
        threshold, voters,
    )
    (o_prior_ref[...], o_frontier_ref[...], o_clock_ref[...],
     o_vstart_ref[...], o_exec_ref[...]) = out[:5]
    o_gaps_ref[...] = out[5][None]


@functools.partial(
    jax.jit, static_argnames=("threshold", "voters"), donate_argnums=(0, 1)
)
def table_round_pallas(prior, frontier, key, min_clock, *, threshold, voters):
    """Pallas twin of ``fused_table_round`` (same signature/donation;
    the scalar ``gaps`` comes back shaped ``[1]`` inside the kernel and
    is squeezed here so the 6-tuple matches the composed out)."""
    from jax.experimental import pallas as pl

    K = prior.shape[0]
    n = frontier.shape[1]
    B = key.shape[0]
    i32 = prior.dtype
    out = pl.pallas_call(
        functools.partial(
            _table_round_kernel, threshold=threshold, voters=voters
        ),
        out_shape=[
            jax.ShapeDtypeStruct((K,), i32),
            jax.ShapeDtypeStruct((K, n), i32),
            jax.ShapeDtypeStruct((B,), i32),
            jax.ShapeDtypeStruct((B,), i32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            jax.ShapeDtypeStruct((1,), i32),
        ],
        input_output_aliases={0: 0, 1: 1},
        interpret=_interpret(),
    )(prior, frontier, key, min_clock)
    return out[0], out[1], out[2], out[3], out[4], out[5][0]


# the Pallas twins join the compiled-identity audit alongside their
# composed counterparts: a canonicalized sweep holds EITHER route to one
# compile per program
from fantoch_tpu.core.compile_cache import register_program  # noqa: E402

register_program("pred_plane_step_pallas", pred_plane_step_pallas)
register_program("graph_plane_step_pallas", graph_plane_step_pallas)
register_program("votes_commit_pallas", votes_commit_pallas)
register_program("table_round_pallas", table_round_pallas)
