"""Batched dependency-graph resolution on TPU — the north-star kernel.

Replaces the reference's serial Tarjan walk
(fantoch_ps/src/executor/graph/tarjan.rs:99-319) with a data-parallel
resolver over a batch of committed commands.  The output contract is the
one the reference's correctness argument actually needs (see
fantoch/src/executor/monitor.rs and the sim_test agreement check
fantoch_ps/src/protocol/mod.rs:924-1010):

  * members of one SCC execute contiguously, ordered by dot
    (tarjan.rs:15 — ``SCC = BTreeSet<Dot>``);
  * if SCC A depends on SCC B, then B executes before A (topological
    order of the condensation);
  * independent SCCs may execute in any order (they share no keys, since
    conflicting commands are always linked by dependencies), so only
    *local* topological validity is required — no cross-process rank
    agreement.

Representation (device arrays over a batch of B command slots):

  * ``dep[B]`` (functional path) or ``deps[B, D]`` (general path): batch
    index of each dependency after pruning, with sentinels
    ``TERMINAL = -1`` (no dependency / dependency already executed) and
    ``MISSING = -2`` (dependency not yet committed here — the vertex and
    everything that reaches it stays unresolved, mirroring the pending
    index in fantoch_ps/src/executor/graph/index.rs:146).
  * dots are carried as ``(dot_src[B], dot_seq[B])`` int32 pairs for the
    intra-SCC sort.

Why a functional fast path: with the reference's sequential ``KeyDeps``
(fantoch_ps/src/protocol/common/graph/deps/keys/sequential.rs:8-11) each
command picks up exactly one dependency per key — the latest.  A batch of
single-key commands therefore forms a *functional graph* (out-degree <= 1)
whose weakly-connected components are rho-shapes: cycles can only sit at
the oldest end of a chain (a mid-chain cycle would need out-degree 2).
Functional graphs admit an **exact O(log B)** resolution with pointer
doubling:

  1. doubling with distance accumulation ranks every chain (list ranking);
  2. min-id accumulation along the jumped path identifies each cycle's
     leader exactly (a 2^L >= 2B hop walk from any non-terminating vertex
     wraps its cycle completely);
  3. a binary-closure scatter from the leaders marks cycle membership;
  4. a second doubling pass ranks the vertices that flow into cycles.

Everything is gathers/scatters/min/max over int32[B] — no data-dependent
shapes, fully jittable, MXU-free but HBM-friendly.  The general
(multi-key, out-degree D) path uses affine-max pointer doubling with a
relaxation floor; the rare residue it cannot finish (3+-cycles) is
reported via ``stuck`` so the caller can hand those vertices to the host
Tarjan oracle (executor/graph/deps_graph.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from fantoch_tpu.core.compile_cache import register_program

TERMINAL = -1  # dependency executed / absent (pruned)
MISSING = -2  # dependency not committed here yet: blocks resolution

# rank assigned to unresolved vertices so they sort after all resolved ones
_UNRESOLVED_RANK = jnp.iinfo(jnp.int32).max


class Resolution(NamedTuple):
    """Result of one batched resolve.

    ``order`` is a permutation of batch indices: resolved vertices first in
    execution order, unresolved vertices at the tail (use ``resolved`` to
    cut).  ``rank``/``leader`` expose the condensation structure for tests.
    """

    order: jax.Array  # int32[B] permutation
    resolved: jax.Array  # bool[B]
    rank: jax.Array  # int32[B] topological level (condensation)
    leader: jax.Array  # int32[B] SCC leader (batch index)
    on_cycle: jax.Array  # bool[B]


def _num_doubling_steps(batch: int) -> int:
    """Steps so that 2^L >= 2*batch: a walk of 2^L hops from any vertex of a
    non-terminating component has fully wrapped its cycle at least once."""
    steps = 1
    while (1 << steps) < 2 * max(batch, 2):
        steps += 1
    return steps


def _doubling_core(dep: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Pointer-doubling resolution core: (resolved, rank, leader, on_cycle).

    Exact for any out-degree-<=1 graph; O(log B) rounds of B-wide gathers.
    Shared by ``resolve_functional`` (full batch) and the keyed path's
    residual finish (small compacted batch, where the gathers are cheap).
    """
    batch = dep.shape[0]
    idx = jnp.arange(batch, dtype=jnp.int32)
    steps = _num_doubling_steps(batch)

    is_term = dep == TERMINAL
    is_miss = dep == MISSING
    absorbing = is_term | is_miss

    # self-absorbing pointers: terminals/missing point at themselves with
    # zero step cost, so doubling past them is a no-op.
    jump = jnp.where(absorbing, idx, dep)
    # min id over the true path p^1..p^(2^t); init = id of first hop
    acc = jnp.where(absorbing, jnp.int32(batch), jump)

    jumps_log = []  # p^(2^t) for the closure scatter below
    for _ in range(steps):
        jumps_log.append(jump)
        acc = jnp.minimum(acc, acc[jump])
        jump = jump[jump]

    end = jump  # endpoint after 2^steps hops
    end_term = is_term[end]
    end_miss = is_miss[end]
    nonterminating = ~(end_term | end_miss)

    # --- cycles: every non-terminating walk has wrapped its cycle, so the
    # path-min at the endpoint is exactly the cycle's smallest id.
    cyc_leader = acc[end]
    # seeds: the leaders themselves are cycle members by construction
    on_cycle = nonterminating & (idx == cyc_leader)
    # binary closure along p: orbit of each leader = its whole cycle (p maps
    # cycle members to cycle members, so marks cannot leak off the cycle).
    for hop in jumps_log:
        contrib = jnp.zeros_like(on_cycle).at[hop].max(on_cycle)
        on_cycle = on_cycle | (contrib & nonterminating)

    # --- second doubling pass: rank = distance to a terminal or to the
    # cycle boundary (cycle members themselves sit at rank 0 of their
    # component, which is all local topological validity requires).
    absorbing2 = absorbing | on_cycle
    jump2 = jnp.where(absorbing2, idx, dep)
    dist2 = jnp.where(absorbing2, 0, 1).astype(jnp.int32)
    for _ in range(steps):
        dist2 = dist2 + dist2[jump2]
        jump2 = jump2[jump2]

    resolved = jnp.where(on_cycle, True, is_term[jump2] | on_cycle[jump2])
    rank = jnp.where(resolved, dist2, _UNRESOLVED_RANK).astype(jnp.int32)
    leader = jnp.where(on_cycle, cyc_leader, idx).astype(jnp.int32)
    return resolved, rank, leader, on_cycle


@functools.partial(jax.jit, static_argnames=("return_order",))
def resolve_functional(
    dep: jax.Array,  # int32[B] — single dependency (TERMINAL/MISSING sentinels)
    dot_src: jax.Array,  # int32[B]
    dot_seq: jax.Array,  # int32[B]
    *,
    return_order: bool = True,
) -> Resolution:
    """Exact batched resolution of an out-degree-<=1 dependency graph."""
    resolved, rank, leader, on_cycle = _doubling_core(dep)
    if not return_order:
        order = jnp.arange(dep.shape[0], dtype=jnp.int32)
    else:
        order = _order_from_ranks(rank, leader, dot_src, dot_seq)
    return Resolution(order, resolved, rank, leader, on_cycle)


class KeyedResolution(NamedTuple):
    """Result of one keyed batched resolve (``resolve_functional_keyed``).

    ``order``/``resolved``/``rank``/``leader``/``on_cycle`` as in
    ``Resolution`` when ``return_structure=True``.  With
    ``return_structure=False`` (the latency-critical entry) ``resolved`` is
    a *permutation* of the true per-vertex flags — valid for reductions
    (``all``/``sum``) but not for indexing — and rank/leader/on_cycle are
    zeros; use ``n_resolved`` for counting.  ``overflow`` means the
    residual exceeded ``residual_size`` and the result must be discarded
    (the caller falls back to ``resolve_functional``).
    """

    order: jax.Array  # int32[B]
    resolved: jax.Array  # bool[B]
    rank: jax.Array  # int32[B]
    leader: jax.Array  # int32[B]
    on_cycle: jax.Array  # bool[B]
    n_resolved: jax.Array  # int32 scalar
    overflow: jax.Array  # bool scalar


def _residual_size_for(batch: int) -> int:
    """Default residual capacity: whole batch when small (tests — never
    overflow), B/64 when large (cycles + cross-replica chain inversions are
    a thin slice of real traffic; overflow falls back to full doubling)."""
    cap = batch if batch <= 4096 else max(4096, batch // 64)
    return _pow2_at_least(cap)


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@functools.partial(jax.jit, static_argnames=("residual_size", "return_structure"))
def resolve_functional_keyed(
    key: jax.Array,  # int32[B] — conflict-key hash per command (perf hint)
    dep: jax.Array,  # int32[B]
    dot_src: jax.Array,  # int32[B]
    dot_seq: jax.Array,  # int32[B]
    *,
    residual_size: int,
    return_structure: bool = True,
) -> KeyedResolution:
    """Sort-based exact resolution of an out-degree-<=1 dependency graph.

    The north-star kernel (SURVEY §7 stage 4; VERDICT r2 item 1).  Replaces
    O(log B) rounds of B-wide random gathers (~6.6 ms each on TPU v5e at
    B=1M — the 894 ms of round 2) with a handful of B-wide *sorts*
    (~0.4-2 ms each) plus small-residual doubling:

      1. stable-sort the batch by key hash: each key's commands become one
         contiguous run in batch-arrival order;
      2. verify every in-run link: position p is *chain-verified* when its
         dep is exactly the previous in-run vertex and the run head's dep
         is TERMINAL.  For graphs produced by sequential KeyDeps in arrival
         order (the dominant shape — the file docstring's rho argument),
         every link verifies and the run position IS the rank;
      3. everything downstream of the first unverified link in a run (cycle
         heads, cross-replica chain inversions, missing-blocked suffixes)
         is compacted into a ``residual_size`` buffer and finished exactly
         by ``_doubling_core`` at residual scale, where gathers are cheap;
         deps that point back into a verified prefix fold to TERMINAL —
         sound because the whole prefix of that run is emitted first;
      4. residual vertices are re-emitted at their run's tail positions
         ((rank, SCC leader, dot) order within the run), and one final sort
         by (unresolved, emit position) yields ``order``.

    Exactness does not depend on the key hint: any link the sort order
    cannot verify lands in the residual and is resolved by doubling, so
    hash collisions and adversarial inputs only cost performance (worst
    case ``overflow`` → caller reruns via ``resolve_functional``).  The
    only structural requirement is the functional one (out-degree <= 1)
    plus deps linking same-key vertices (guaranteed: deps are conflicts —
    fantoch_ps/src/protocol/common/graph/deps/keys/sequential.rs:8-11);
    cross-key deps would break run locality and must go through
    ``resolve_general``.
    """
    batch = dep.shape[0]
    res_n = min(residual_size, batch)
    idx = jnp.arange(batch, dtype=jnp.int32)
    p_iota = idx

    # --- 1. one stable sort groups runs in arrival order
    k_s, pos_s, dep_s = jax.lax.sort(
        (key.astype(jnp.int32), idx, dep), num_keys=1, is_stable=True
    )

    # --- 2. link verification + prefix ranking (elementwise + cummax)
    head = jnp.concatenate([jnp.ones((1,), bool), k_s[1:] != k_s[:-1]])
    prev_pos = jnp.roll(pos_s, 1)  # head rows never read it
    ok = jnp.where(head, dep_s == TERMINAL, dep_s == prev_pos)
    run_start = jax.lax.cummax(jnp.where(head, p_iota, 0))
    lastbad = jax.lax.cummax(jnp.where(~ok, p_iota, -1))
    chain_ok = lastbad < run_start  # no unverified link in [run_start, p]
    rank_fast = p_iota - run_start

    cflag = chain_ok.astype(jnp.int32)
    n_residual = batch - cflag.sum()
    overflow = n_residual > res_n

    def _residual_path(structure: bool):
        """Compact + doubling + emit (stages 3-4).  Returns
        (order, unres_b) and, when ``structure``, per-vertex
        (rank_b, leader_b, cyc_b) in sorted space."""
        # --- 3. compact the residual (stable by cflag keeps run order)
        _, p_r_full = jax.lax.sort((cflag, p_iota), num_keys=1, is_stable=True)
        p_r = p_r_full[:res_n]  # sorted-space position of each residual row
        r_iota = jnp.arange(res_n, dtype=jnp.int32)
        valid_r = r_iota < n_residual
        # small gathers (res_n rows) pull the rest of the residual view
        rpos = pos_s[p_r]  # original batch index
        rdep = dep_s[p_r]
        rrs = jnp.where(valid_r, run_start[p_r], jnp.iinfo(jnp.int32).max)
        rsrc = dot_src[rpos]
        rseq = dot_seq[rpos]

        # remap deps to residual-local slots; deps leaving the residual (into
        # a verified prefix or already executed) fold to TERMINAL — the whole
        # prefix of the run is emitted before any residual member of it
        remap = jnp.full((batch,), TERMINAL, dtype=jnp.int32)
        remap = remap.at[jnp.where(valid_r, rpos, batch)].set(r_iota, mode="drop")
        rdep_local = jnp.where(
            rdep >= 0, remap[jnp.clip(rdep, 0, batch - 1)], rdep
        )
        rdep_local = jnp.where(valid_r, rdep_local, TERMINAL)

        # residual groups (per run) in p order: first residual row of a run
        # sits exactly at the run's first unverified position.  In p_r order
        # rrs is already sorted (run_start is monotone in p and compaction
        # is stable), so the emit sort below keeps every group's block at
        # the same offsets — per-group constants like firstbad carry over
        # elementwise without riding the sort.
        g_head = jnp.concatenate([jnp.ones((1,), bool), rrs[1:] != rrs[:-1]])
        firstbad = jax.lax.cummax(jnp.where(g_head, p_r, 0))

        # --- exact finish at residual scale
        l_resolved, l_rank, l_leader, l_on_cycle = _doubling_core(rdep_local)

        # emit order within each run's residual tail: resolved first, then
        # (rank, SCC leader, dot) — SCC members contiguous and dot-sorted
        l_unres = (~l_resolved).astype(jnp.int32)
        operands = [
            rrs,
            l_unres,
            l_rank,
            l_leader,
            rsrc,
            rseq,
            p_r,
            l_resolved.astype(jnp.int32),
        ]
        if structure:
            operands += [
                jnp.where(valid_r, l_rank, 0),
                rpos[jnp.clip(l_leader, 0, res_n - 1)],  # leader as orig index
                l_on_cycle.astype(jnp.int32),
            ]
        sorted_ops = jax.lax.sort(tuple(operands), num_keys=6, is_stable=True)
        e_p_r, e_res = sorted_ops[6], sorted_ops[7]
        emit_local = r_iota - jax.lax.cummax(jnp.where(g_head, r_iota, 0))
        target_r = firstbad + emit_local
        # invalid rows sank to the emit-sort tail (rrs=max) = exactly ~valid_r

        # --- 4. scatter residual emit data back over the batch, final sort
        # by one packed key: (unresolved << 30) | target position
        sc_idx = jnp.where(valid_r, e_p_r, batch)
        tgt_b = p_iota.at[sc_idx].set(target_r, mode="drop")
        unres_b = (~chain_ok).at[sc_idx].set(e_res == 0, mode="drop")
        packed = jnp.where(unres_b, jnp.int32(1) << 30, 0) | tgt_b
        _, order = jax.lax.sort((packed, pos_s), num_keys=1, is_stable=True)
        if not structure:
            return order, unres_b

        e_rank2, e_leader2, e_cyc = sorted_ops[8], sorted_ops[9], sorted_ops[10]
        rank_b = jnp.where(chain_ok, rank_fast, _UNRESOLVED_RANK)
        rank_b = rank_b.at[sc_idx].set(
            jnp.where(e_res == 1, firstbad - rrs + e_rank2, _UNRESOLVED_RANK),
            mode="drop",
        )
        leader_b = pos_s.at[sc_idx].set(e_leader2, mode="drop")
        cyc_b = jnp.zeros((batch,), jnp.int32).at[sc_idx].set(e_cyc, mode="drop")
        return order, unres_b, rank_b, leader_b, cyc_b

    if not return_structure:
        # latency-critical entry: when every link chain-verified (the
        # dominant shape — deps produced by latest-per-key KeyDeps in
        # arrival order) the run position IS the rank and the grouped order
        # is already the execution order; skip compaction + doubling + emit,
        # which at residual scale are pure op-launch overhead (~10 ms of the
        # round-2 kernel's 17 ms — scripts/profile_resolve.py).
        order, unres_b = jax.lax.cond(
            n_residual == 0,
            lambda: (pos_s, jnp.zeros((batch,), bool)),
            lambda: _residual_path(False),
        )
        n_resolved = (batch - unres_b.sum()).astype(jnp.int32)
        zeros = jnp.zeros((batch,), jnp.int32)
        return KeyedResolution(
            order, ~unres_b, zeros, zeros, zeros.astype(bool), n_resolved, overflow
        )

    order, unres_b, rank_b, leader_b, cyc_b = _residual_path(True)
    n_resolved = (batch - unres_b.sum()).astype(jnp.int32)

    # realign per-vertex structure to original batch order (one more sort)
    aligned = jax.lax.sort(
        (
            pos_s,
            (~unres_b).astype(jnp.int32),
            rank_b,
            leader_b,
            cyc_b,
        ),
        num_keys=1,
        is_stable=True,
    )
    _, a_res, a_rank, a_leader, a_cyc = aligned
    return KeyedResolution(
        order,
        a_res == 1,
        a_rank,
        a_leader,
        a_cyc.astype(bool),
        n_resolved,
        overflow,
    )


def _order_from_ranks(rank, leader, dot_src, dot_seq) -> jax.Array:
    """Execution order: (rank, SCC leader, dot) lexicographic.

    Same-SCC members share (rank, leader) and are therefore contiguous and
    dot-sorted (the reference's BTreeSet<Dot> order, tarjan.rs:15).  The
    rank key makes every SCC follow all SCCs it depends on.  Unresolved
    vertices carry rank INT32_MAX and sink to the tail.
    """
    return jnp.lexsort((dot_seq, dot_src, leader, rank)).astype(jnp.int32)


def resolve_keyed_auto(
    key: jax.Array,
    dep: jax.Array,
    dot_src: jax.Array,
    dot_seq: jax.Array,
    *,
    return_structure: bool = True,
) -> KeyedResolution:
    """Host wrapper over ``resolve_functional_keyed``: picks the default
    residual capacity and falls back to the exact full-batch doubling path
    if the residual overflows (one host sync either way — the caller
    fetches results right after)."""
    batch = dep.shape[0]
    res = resolve_functional_keyed(
        key,
        dep,
        dot_src,
        dot_seq,
        residual_size=_residual_size_for(batch),
        return_structure=return_structure,
    )
    if bool(res.overflow):
        full = resolve_functional(dep, dot_src, dot_seq)
        return KeyedResolution(
            full.order,
            full.resolved,
            full.rank,
            full.leader,
            full.on_cycle,
            full.resolved.sum().astype(jnp.int32),
            jnp.bool_(False),
        )
    return res


# ---------------------------------------------------------------------------
# general path: out-degree up to D (multi-key commands)
# ---------------------------------------------------------------------------


class GeneralResolution(NamedTuple):
    # jax.Array from the jitted resolvers; host np.ndarray from the
    # host-orchestrated resolve_general_staged (both index identically)
    order: jax.Array  # int32[B]
    resolved: jax.Array  # bool[B]
    rank: jax.Array  # int32[B]
    leader: jax.Array  # int32[B]
    stuck: jax.Array  # bool[B] — not resolved and not missing-blocked:
    # cycles the device pass could not collapse (or, from the components
    # pass, rows its residual could not hold); host oracle finishes them.
    # the components pass alone: the passes its two loops made
    iters: jax.Array | None = None  # int32[]


def components_residual(batch: int) -> int:
    """The residual a round of ``batch`` working rows gives
    :func:`resolve_general`'s components pass: a quarter of the rows (the
    rows of a round that wait for another row of it are a tenth at the
    benchmark's shapes), the whole of a small working set."""
    return min(batch, max(256, batch // 4))


@functools.partial(jax.jit, static_argnames=("max_iters", "residual"))
def resolve_general(
    deps: jax.Array,  # int32[B, D]
    dot_src: jax.Array,
    dot_seq: jax.Array,
    *,
    max_iters: int = 0,  # 0 -> auto: 4 * log2(B) + 8
    residual: int = 0,  # > 0: the components pass, over that many rows
) -> GeneralResolution:
    """Batched resolution for out-degree-D graphs.

    What the served path calls: the round with one coordinator
    (``parallel/mesh_step.protocol_step`` at key width 2 and above) builds
    dependencies that point backward only, and takes the arrival pass
    below, the iterative pass where a shard under its write quorum leaves
    ``MISSING`` rows; the round with a coordinator at every site builds
    edges both ways, across keys and shards, and asks for the **components
    pass** (``residual`` > 0, :func:`_resolve_general_components`): exact
    strongly connected components and their order for every row that
    reaches no ``MISSING`` one, in a number of passes bounded by
    ``log2(residual) + 1``, ``stuck`` only where more than ``residual``
    rows wait for another row of the batch.

    The iterative pass, affine-max pointer doubling: each dependency slot of vertex v is a
    constraint ``rank[v] >= max(floor, add + rank[target])``.  A slot whose
    target has finalized folds into the floor; a slot whose target has
    exactly one live slot composes through it (chain doubling); any live
    target always contributes its current floor (monotone relaxation), so
    progress never stalls on merge vertices — worst case degrades to
    frontier peeling, typical per-key-chain graphs finish in O(log depth).

    SCCs whose vertices are connected by *mutual* edges (the dominant
    shape: k concurrent conflicting proposals that all saw each other,
    k = 2 being two replicas racing) are collapsed exactly by a
    mutual-edge connected-components pre-pass.  Cycles with no mutual
    edges (delivery orders where conflict visibility is strictly
    one-directional around a ring) surface as ``stuck`` for the host
    Tarjan oracle to finish — they cannot deadlock or spin the device
    pass: floors/adds saturate at the batch size, after which the loop
    settles and the budget check exits early.
    """
    batch, width = deps.shape
    idx = jnp.arange(batch, dtype=jnp.int32)
    if max_iters == 0:
        max_iters = 4 * _num_doubling_steps(batch) + 8

    # self-dependencies are semantic no-ops (a command never waits on
    # itself); prune them up front like the host oracle (tarjan.py:129) —
    # left in, they'd read as unfinishable frozen slots in the iterative
    # pass and falsely disqualify the backward fast path
    deps = jnp.where(deps == idx[:, None], TERMINAL, deps)
    if residual:
        return _resolve_general_components(deps, dot_src, dot_seq, residual)

    # --- fast path: every dependency points backward in batch order and
    # nothing is missing.  This is the dominant executor shape (deps are
    # latest-per-key at commit time, appended in commit order), and it
    # makes batch order itself a topological order: backward-only edges
    # cannot form cycles, so every SCC is a singleton and emitting in
    # arrival order satisfies the per-key dependency contract.  The
    # iterative machinery below costs O(critical-path alternations) rounds
    # of B-wide gathers — measured 6.7 s at B=262k, D=4 on deep chains —
    # while this check is one elementwise pass.
    backward_only = jnp.where(deps >= 0, deps < idx[:, None], True).all()
    fast = backward_only & ~(deps == MISSING).any()

    def _fast_arrival():
        ones = jnp.ones((batch,), bool)
        return idx, ones, idx, idx, jnp.zeros((batch,), bool)

    def _iterative():
        return _resolve_general_iterative(deps, dot_src, dot_seq, max_iters)

    return GeneralResolution(*jax.lax.cond(fast, _fast_arrival, _iterative))


@functools.partial(jax.jit, static_argnames=("run_to_fixpoint",))
def _peel_stage(tgt, floor, miss, final, rank, *, run_to_fixpoint: bool):
    """One stage of the staged peeler: frontier peeling (absorption only,
    one dependency level per round) until progress stops or — unless
    ``run_to_fixpoint`` — the live set halves, at which point the caller
    compacts and re-dispatches at half size, so total work tracks the
    frontier-size integral (sum of per-level live counts), not B x depth."""
    half = jnp.int32(max(tgt.shape[0] // 2, 1))

    def body(state):
        tgt, floor, miss, final, rank, _changed = state
        live = tgt >= 0
        safe = jnp.where(live, tgt, 0)
        t_final = final[safe]
        t_miss = miss[safe]
        fold = live & t_final
        new_floor = jnp.maximum(
            floor, jnp.where(fold, rank[safe] + 1, 0).max(axis=-1)
        )
        new_tgt = jnp.where(fold, jnp.int32(TERMINAL), tgt)
        new_miss = miss | (live & t_miss).any(axis=-1)
        open_slots = (new_tgt >= 0).sum(axis=-1)
        newly_final = ~final & ~new_miss & (open_slots == 0)
        new_rank = jnp.where(newly_final, new_floor, rank)
        new_final = final | newly_final
        changed = newly_final.any() | (new_miss != miss).any()
        return new_tgt, new_floor, new_miss, new_final, new_rank, changed

    def cond(state):
        _tgt, _floor, miss, final, _rank, changed = state
        if run_to_fixpoint:
            return changed
        return changed & ((~final & ~miss).sum() > half)

    state = (tgt, floor, miss, final, rank, jnp.bool_(True))
    tgt, floor, miss, final, rank, changed = jax.lax.while_loop(
        cond, body, state
    )
    return tgt, floor, miss, final, rank, changed


def resolve_general_staged(
    deps,  # int32[B, W] numpy or jax — TERMINAL/MISSING sentinels
    dot_src,
    dot_seq,
    *,
    min_size: int = 4096,
) -> GeneralResolution:
    """Exact DAG resolution with frontier-size-proportional cost.

    The in-jit ``resolve_general`` budget pays O(B x W) per round for a
    fixed ~4 log B rounds — deep alternating-chain graphs (measured
    critical path 2187 at 262k x 4) blow through it with most rows
    unresolved (VERDICT r3 weak #3).  This host-orchestrated variant peels
    dependency levels with a jitted while_loop per *stage*, compacting the
    live rows to half capacity between stages: each level's cost is the
    current live count, so the total is the frontier-size integral
    (sum over vertices of their depth terms), at ~log(B / min_size) extra
    compiles + host syncs.

    Cycles never peel: they survive every stage and return as ``stuck``
    (leader = self; the host Tarjan oracle finishes them, as with
    ``resolve_general``).  Missing-blocked rows and their dependents come
    back unresolved and not stuck.

    No executor calls it: the in-dispatch resolvers (``resolve_general``,
    ``resolve_general_resident``, ``resolve_keyed_auto``) are the route.
    It stays as the oracle that tests/test_ops_resolve.py holds
    ``resolve_general_resident`` to."""
    import numpy as np

    deps = np.asarray(deps, dtype=np.int32)
    batch, width = deps.shape
    idx32 = np.arange(batch, dtype=np.int32)
    # self-deps are semantic no-ops (tarjan.py:129)
    deps = np.where(deps == idx32[:, None], TERMINAL, deps)

    # stage-local state starts as the full batch; rows with a MISSING
    # sentinel are missing-blocked from the outset (and their dependents
    # catch it through propagation in the peel rounds)
    orig = idx32.copy()  # stage row -> original row
    tgt = deps.copy()
    floor = np.zeros(batch, np.int32)
    miss = (deps == MISSING).any(axis=1)
    final = np.zeros(batch, bool)
    rank_local = np.zeros(batch, np.int32)

    # full-batch outputs, filled in as rows finalize
    out_rank = np.full(batch, _UNRESOLVED_RANK, np.int32)
    out_final = np.zeros(batch, bool)
    out_miss = np.zeros(batch, bool)

    prev_live = None
    while True:
        size = _pow2_at_least(max(len(orig), 1))
        pad = size - len(orig)
        if pad:
            tgt = np.concatenate(
                [tgt, np.full((pad, width), TERMINAL, np.int32)]
            )
            floor = np.concatenate([floor, np.zeros(pad, np.int32)])
            miss = np.concatenate([miss, np.zeros(pad, bool)])
            final = np.concatenate([final, np.ones(pad, bool)])  # inert
            rank_local = np.concatenate([rank_local, np.zeros(pad, np.int32)])
        j_out = _peel_stage(
            jnp.asarray(tgt), jnp.asarray(floor), jnp.asarray(miss),
            jnp.asarray(final), jnp.asarray(rank_local),
            run_to_fixpoint=size <= min_size,
        )
        # one blocking transfer for the stage's whole output (device_get
        # issues async copies for every leaf before blocking)
        tgt, floor, miss, final, rank_local = jax.device_get(j_out[:5])
        tgt, floor, miss, final, rank_local = (
            tgt[: len(orig)], floor[: len(orig)], miss[: len(orig)],
            final[: len(orig)], rank_local[: len(orig)],
        )

        # publish finalized / missing rows
        out_final[orig[final]] = True
        out_rank[orig[final]] = rank_local[final]
        out_miss[orig[miss]] = True

        live = ~final & ~miss
        n_live = int(live.sum())
        if n_live == 0 or size <= min_size:
            # done, or the terminal stage ran to its fixpoint: any
            # survivor is cycle-blocked and returns as stuck
            break
        if prev_live is not None and n_live >= prev_live:
            # a larger-than-terminal stage hit a fixpoint with no progress:
            # everything left is cycle-blocked — stop instead of spinning
            break
        prev_live = n_live

        # compact to the live rows; fold deps on finalized/missing rows
        keep = np.nonzero(live)[0].astype(np.int32)
        remap = np.full(len(orig), TERMINAL, np.int32)
        remap[keep] = np.arange(len(keep), dtype=np.int32)
        new_tgt = tgt[keep]
        valid = new_tgt >= 0
        t_rows = np.where(valid, new_tgt, 0)
        t_final = final[t_rows] & valid
        t_miss = miss[t_rows] & valid
        new_floor = np.maximum(
            floor[keep],
            np.where(t_final, rank_local[t_rows] + 1, 0).max(axis=1),
        )
        new_miss = t_miss.any(axis=1)
        folded = np.where(
            valid & t_final, TERMINAL, np.where(valid, remap[t_rows], new_tgt)
        )
        orig = orig[keep]
        tgt = folded.astype(np.int32)
        floor = new_floor.astype(np.int32)
        miss = new_miss
        final = np.zeros(len(orig), bool)
        rank_local = np.zeros(len(orig), np.int32)

    stuck_np = ~out_final & ~out_miss
    order = np.lexsort(
        (
            np.asarray(dot_seq),
            np.asarray(dot_src),
            idx32,
            np.where(out_final, out_rank, _UNRESOLVED_RANK),
        )
    ).astype(np.int32)
    # host numpy, deliberately: this variant is host-orchestrated and its
    # consumers read the results on host — bouncing them through the device
    # would cost an upload plus a fetch round trip per field
    return GeneralResolution(
        order,
        out_final,
        np.where(out_final, out_rank, _UNRESOLVED_RANK),
        idx32,
        stuck_np,
    )


# slot sentinel internal to resolve_general_resident's compaction: a dep
# whose target was cut at a fixpoint compaction (permanently stuck live
# rows past the stage capacity) — only ever created after the publish
# gate closed, so it is never read into a published result
_FROZEN = -3


def _resident_schedule(batch: int, min_size: int) -> Tuple[int, ...]:
    """Static pow2 halving schedule from the padded batch down to the
    terminal stage size (inclusive)."""
    sizes = []
    size = _pow2_at_least(max(batch, 1))
    floor_size = _pow2_at_least(max(min_size, 1))
    while size > floor_size:
        sizes.append(size)
        size //= 2
    sizes.append(size)
    return tuple(sizes)


@functools.partial(jax.jit, static_argnames=("min_size",))
def resolve_general_resident(
    deps: jax.Array,  # int32[B, W] — TERMINAL/MISSING sentinels
    dot_src: jax.Array,
    dot_seq: jax.Array,
    *,
    min_size: int = 4096,
) -> GeneralResolution:
    """``resolve_general_staged`` with the state kept DEVICE-RESIDENT
    between stages: the whole peel-and-compact schedule — frontier
    peeling until the live set halves, device-side compaction to half
    capacity, repeat down to ``min_size``, terminal fixpoint — runs as
    ONE jitted dispatch with no host round-trips.

    The host-orchestrated variant pays a full state fetch + re-upload
    per stage; this one costs a single dispatch + one result fetch, so
    the adversarial fallback (``bench.py general_fallback_*``) is
    slope-timeable and serves from the accelerator like every other
    in-dispatch resolver.

    Semantics are the staged peeler's exactly (parity-tested): DAG rows
    finalize with frontier-proportional total cost, missing-blocked rows
    and their dependents come back unresolved-not-stuck, cycles never
    peel and return ``stuck`` for the host Tarjan oracle.  The one
    divergence-shaped corner — a fixpoint reached while the live set
    still exceeds the next stage's capacity — closes the publish gate:
    results are already final at a fixpoint, so later stages (whose cut
    rows would dangle) cannot corrupt them.
    """
    batch, width = deps.shape
    idx = jnp.arange(batch, dtype=jnp.int32)
    # self-deps are semantic no-ops (tarjan.py:129)
    deps = jnp.where(deps == idx[:, None], TERMINAL, deps)

    # full-batch outputs, scatter-published as stages finalize rows
    out_final = jnp.zeros((batch,), bool)
    out_miss = jnp.zeros((batch,), bool)
    out_rank = jnp.full((batch,), _UNRESOLVED_RANK, jnp.int32)

    schedule = _resident_schedule(batch, min_size)
    size0 = schedule[0]
    pad = size0 - batch
    iota0 = jnp.arange(size0, dtype=jnp.int32)
    tgt = jnp.full((size0, width), TERMINAL, jnp.int32).at[:batch].set(deps)
    floor = jnp.zeros((size0,), jnp.int32)
    miss = jnp.zeros((size0,), bool).at[:batch].set((deps == MISSING).any(axis=1))
    final = iota0 >= batch  # pads are inert
    rank = jnp.zeros((size0,), jnp.int32)
    orig = jnp.where(iota0 < batch, iota0, jnp.int32(batch))  # pad -> dropped

    dead = jnp.bool_(False)  # publish gate (see docstring)
    for size in schedule:
        tgt, floor, miss, final, rank, _changed = _peel_stage(
            tgt, floor, miss, final, rank,
            run_to_fixpoint=size <= min_size,
        )
        pub_final = out_final.at[orig].set(final, mode="drop")
        pub_miss = out_miss.at[orig].set(miss, mode="drop")
        pub_rank = out_rank.at[orig].set(
            jnp.where(final, rank, _UNRESOLVED_RANK), mode="drop"
        )
        out_final = jnp.where(dead, out_final, pub_final)
        out_miss = jnp.where(dead, out_miss, pub_miss)
        out_rank = jnp.where(dead, out_rank, pub_rank)
        if size <= min_size:
            break  # terminal stage ran to its fixpoint

        # --- device-side compaction to half capacity ---
        half = size // 2
        live = ~final & ~miss
        # a fixpoint with live > half means every survivor is
        # permanently blocked: results above are final — close the gate
        # (cut rows may dangle below, but nothing publishes past here)
        dead = dead | (live.sum() > half)
        iota = jnp.arange(size, dtype=jnp.int32)
        _, perm = jax.lax.sort(
            ((~live).astype(jnp.int32), iota), num_keys=1, is_stable=True
        )
        keep = perm[:half]
        remap = (
            jnp.full((size,), _FROZEN, jnp.int32)
            .at[keep]
            .set(jnp.arange(half, dtype=jnp.int32))
        )
        tgt_k = tgt[keep]
        valid = tgt_k >= 0
        t_rows = jnp.where(valid, tgt_k, 0)
        t_final = final[t_rows] & valid
        t_miss = miss[t_rows] & valid
        floor = jnp.maximum(
            floor[keep],
            jnp.where(t_final, rank[t_rows] + 1, 0).max(axis=1),
        )
        miss = miss[keep] | t_miss.any(axis=1)
        tgt = jnp.where(
            t_final, jnp.int32(TERMINAL), jnp.where(valid, remap[t_rows], tgt_k)
        )
        final = final[keep]
        rank = rank[keep]
        orig = orig[keep]

    stuck = ~out_final & ~out_miss
    order = jnp.lexsort(
        (
            dot_seq,
            dot_src,
            idx,
            jnp.where(out_final, out_rank, _UNRESOLVED_RANK),
        )
    ).astype(jnp.int32)
    return GeneralResolution(order, out_final, out_rank, idx, stuck)


# ---------------------------------------------------------------------------
# resident graph-plane step (executor/graph/graph_plane.DeviceGraphPlane)
# ---------------------------------------------------------------------------


class GraphPlaneStep(NamedTuple):
    """One resident dispatch's output: the donated backlog state back,
    plus the emitted order.  Only the small per-slot result columns
    (order/newly/stuck/leader) are fetched by the host — the backlog
    state itself never round-trips."""

    deps: jax.Array  # int32[C, W] — resident dep-slot matrix (donated)
    key: jax.Array  # int32[C] conflict-key hash (-1 = multi-key)
    src: jax.Array  # int32[C]
    seq: jax.Array  # int32[C]
    occ: jax.Array  # bool[C] — slot holds a committed command
    executed: jax.Array  # bool[C]
    order: jax.Array  # int32[C] permutation; emitted = order rows w/ newly
    newly: jax.Array  # bool[C] — executed by this dispatch
    stuck: jax.Array  # bool[C] — general modes: cycles for the host oracle
    leader: jax.Array  # int32[C] — structure modes: SCC leader (CHAIN_SIZE)


@functools.partial(
    jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5), static_argnames=("mode",)
)
def resolve_graph_plane_step(
    deps: jax.Array,  # int32[C, W] slot indices / TERMINAL / MISSING
    key: jax.Array,  # int32[C]
    src: jax.Array,  # int32[C]
    seq: jax.Array,  # int32[C]
    occ: jax.Array,  # bool[C]
    executed: jax.Array,  # bool[C]
    u_row: jax.Array,  # int32[U] — new slot ids (pad = C, dropped)
    u_deps: jax.Array,  # int32[U, W]
    u_key: jax.Array,  # int32[U]
    u_src: jax.Array,  # int32[U]
    u_seq: jax.Array,  # int32[U]
    p_row: jax.Array,  # int32[P] — dep-patch cells (pad = C, dropped)
    p_col: jax.Array,  # int32[P]
    p_val: jax.Array,  # int32[P] — slot id or TERMINAL
    e_row: jax.Array,  # int32[E] — host-oracle executed marks (pad = C)
    *,
    mode: str,  # "keyed" | "general" | "general_resident"
) -> GraphPlaneStep:
    """The resident twin of ``BatchedDependencyGraph._resolve_backlog``
    (executor/graph/graph_plane.py).

    The whole dependency backlog lives ON DEVICE across feeds: ``C``
    slots of (deps, key, src, seq) with occupancy and executed flags,
    all donated in-place.  Each dispatch (1) installs the feed's new
    rows, (2) re-points MISSING dep cells whose dot just committed (the
    waiter-index residual protocol: missing-blocked rows stay resident
    and wake when a later feed patches them), (3) applies host-oracle
    executed marks (stuck-cycle residues the host Tarjan finished), then
    (4) resolves the *entire* pending window with the same kernels the
    host-column path dispatches per flush — ``resolve_keyed_auto``'s
    sort-based kernel for single-key functional windows,
    ``resolve_general`` (small, exact structure) or
    ``resolve_general_resident`` (large, peel-and-compact) otherwise —
    folding dep cells that point at executed slots to TERMINAL first.

    Non-pending slots (free, or executed-but-not-yet-compacted) are
    masked inert: private pad keys + TERMINAL deps make them resolve as
    singleton runs, and the host drops them via ``newly``.  Slot
    recycling is host-owned (compaction re-packs pending rows and
    re-uploads once).
    """
    cap, _width = deps.shape
    idx = jnp.arange(cap, dtype=jnp.int32)

    # (1) new rows: full-row install (reused slots fully overwritten)
    deps = deps.at[u_row].set(u_deps, mode="drop")
    key = key.at[u_row].set(u_key, mode="drop")
    src = src.at[u_row].set(u_src, mode="drop")
    seq = seq.at[u_row].set(u_seq, mode="drop")
    occ = occ.at[u_row].set(True, mode="drop")
    executed = executed.at[u_row].set(False, mode="drop")
    # (2) dep patches: MISSING cells whose dot just committed (or was
    # recovered as a noop -> TERMINAL)
    deps = deps.at[p_row, p_col].set(p_val, mode="drop")
    # (3) host-oracle executed marks (stuck residues finished on host)
    executed = executed.at[e_row].set(True, mode="drop")

    pending = occ & ~executed
    cell_live = deps >= 0
    safe = jnp.clip(deps, 0, cap - 1)
    # fold deps on executed slots to TERMINAL; mask non-pending rows inert
    dmat = jnp.where(cell_live & executed[safe], jnp.int32(TERMINAL), deps)
    dmat = jnp.where(pending[:, None], dmat, jnp.int32(TERMINAL))

    zeros_i = jnp.zeros((cap,), jnp.int32)
    if mode == "keyed":
        # single-dep column: the first live cell, else MISSING if any cell
        # is missing, else TERMINAL (the host-column path's compression)
        live = dmat >= 0
        has_live = live.any(axis=1)
        first = jnp.argmax(live, axis=1)
        col = jnp.take_along_axis(dmat, first[:, None], axis=1)[:, 0]
        col = jnp.where(
            has_live,
            col,
            jnp.where((dmat == MISSING).any(axis=1), MISSING, TERMINAL),
        ).astype(jnp.int32)
        # distinct private keys park every non-pending slot in its own
        # singleton run (one shared key would flood the residual)
        pk = jnp.where(pending, key, jnp.iinfo(jnp.int32).max - idx)
        # a SMALL residual, deliberately: the plane's window is mostly
        # chain-verified rows plus a thin blocked residue, and the
        # residual finish (doubling + closure scatters) is the dispatch's
        # dominant cost when sized to the window; overflow falls back to
        # exact full-window doubling in-dispatch.  No structure entry:
        # the plane reports aggregate counters, not exact CHAIN_SIZE
        # (the host-column twin keeps the exact-structure path)
        residual_size = _pow2_at_least(max(64, cap // 16))
        res = resolve_functional_keyed(
            pk, col, src, seq,
            residual_size=min(residual_size, cap),
            return_structure=False,
        )

        def _kept():
            # per-vertex resolved from the order permutation (resolved
            # rows sort first): position-in-order < n_resolved
            pos = zeros_i.at[res.order].set(idx)
            return res.order, pos < res.n_resolved

        if residual_size >= cap:
            order, resolved_v = _kept()
        else:

            def _overflowed():
                # residual overflow: rerun via exact full-window doubling
                # (the resolve_keyed_auto fallback, in-dispatch)
                full = resolve_functional(col, src, seq)
                return full.order, full.resolved

            order, resolved_v = jax.lax.cond(res.overflow, _overflowed, _kept)
        stuck = jnp.zeros((cap,), bool)  # functional cycles resolve exactly
        leader = zeros_i
    elif mode == "general":
        res = resolve_general(dmat, src, seq)
        order, resolved_v = res.order, res.resolved
        stuck = res.stuck & pending
        leader = res.leader
    else:
        assert mode == "general_resident", mode
        res = resolve_general_resident(dmat, src, seq)
        order, resolved_v = res.order, res.resolved
        stuck = res.stuck & pending
        leader = res.leader

    newly = resolved_v & pending
    executed = executed | newly
    return GraphPlaneStep(
        deps, key, src, seq, occ, executed, order, newly, stuck, leader
    )


register_program("graph_plane_step", resolve_graph_plane_step)


def _resolve_general_iterative(deps, dot_src, dot_seq, max_iters):
    """The exact fallback: mutual-edge SCC collapse + affine-max doubling
    (see resolve_general).  Returns the GeneralResolution fields."""
    batch, width = deps.shape
    idx = jnp.arange(batch, dtype=jnp.int32)

    # --- mutual-edge SCC collapse: v and u mutually dependent -> same SCC,
    # and so is the whole connected component of the (undirected) mutual-
    # edge graph.  leader = min id of the component, found by min-label
    # propagation over mutual neighbours with pointer jumping; intra-
    # component edges are pruned and inbound edges retargeted.
    tgt = deps  # int32[B, D]
    valid = tgt >= 0
    safe_tgt = jnp.where(valid, tgt, 0)
    # reverse test: does any slot of target point back at v?
    back = (tgt[safe_tgt] == idx[:, None, None]).any(axis=-1) & valid
    leader = idx
    for _ in range(_num_doubling_steps(batch)):
        # min over mutual neighbours' leaders, then pointer jump
        nbr_min = jnp.where(back, leader[safe_tgt], jnp.int32(batch)).min(axis=-1)
        leader = jnp.minimum(leader, nbr_min)
        leader = jnp.minimum(leader, leader[leader])

    # rewrite deps through leaders; drop intra-SCC edges
    tgt = jnp.where(valid, leader[safe_tgt], tgt)
    tgt = jnp.where(valid & (tgt == leader[:, None]), TERMINAL, tgt)
    # non-leaders hand their external deps to... they keep them: every
    # member's constraints apply to the SCC; members share the leader's
    # rank at the end, so fold member floors via a segment-max on leader.

    is_miss = tgt == MISSING
    add = jnp.where(tgt >= 0, 1, 0).astype(jnp.int32)
    floor = jnp.zeros((batch, width), dtype=jnp.int32)
    missing_blocked = is_miss.any(axis=-1)

    member_count = jnp.zeros(batch, jnp.int32).at[leader].add(1)

    def body(state):
        it, tgt, add, floor, missing_blocked, _changed = state
        # a slot that composed all the way around a 3+-cycle points at its
        # own vertex: frozen — excluded from folding, absorption and
        # composition so the loop settles and the budget exits early; the
        # vertex stays live and surfaces as ``stuck``.
        frozen = tgt == idx[:, None]
        live = (tgt >= 0) & ~frozen
        safe = jnp.where(live, tgt, 0)
        n_live = live.sum(axis=-1)  # live slots per vertex row
        vfloor = floor.max(axis=-1)  # row lower bound

        # SCC-aggregate view (live targets are always leaders): a slot on a
        # multi-member SCC must fold the *aggregate* rank and wait for all
        # members, or dependents would undercut 1 + scc_rank.
        agg_floor = jnp.zeros(batch, jnp.int32).at[leader].max(vfloor)
        agg_live = jnp.zeros(batch, jnp.int32).at[leader].add(n_live)
        agg_miss = jnp.zeros(batch, bool).at[leader].max(missing_blocked)
        agg_frozen = jnp.zeros(batch, bool).at[leader].max(frozen.any(axis=-1))
        agg_final = (agg_live == 0) & ~agg_miss & ~agg_frozen

        t_final = agg_final[safe]
        t_miss = agg_miss[safe]
        t_vfloor = agg_floor[safe]

        # (a) finalized target SCC: fold into floor, close the slot
        new_floor = jnp.where(live & t_final, jnp.maximum(floor, add + t_vfloor), floor)
        new_tgt = jnp.where(live & t_final, TERMINAL, tgt)
        new_add = add

        # (b) missing-blocked target: vertex becomes missing-blocked
        new_missing = missing_blocked | (live & t_miss).any(axis=-1)

        # (c) live target: always absorb its floor (relaxation)...
        still = live & ~t_final & ~t_miss
        new_floor = jnp.where(still, jnp.maximum(new_floor, add + t_vfloor), new_floor)
        # ...and compose through singleton-SCC targets with one live slot
        # (chain doubling); stop composing once ``add`` saturates — a legit
        # chain has < batch hops, so only unwrapped cycles ever get there.
        single = (
            still
            & (agg_live[safe] == 1)
            & (member_count[safe] == 1)
            & (add < jnp.int32(batch))
        )
        # compose through the target's single live slot.  Precompute each
        # vertex's (first-live-slot target, add) as [B] columns so the
        # per-slot lookup is a [B, D] gather — the naive formulation
        # ``((tgt >= 0) & ~frozen)[safe]`` materializes [B, D, D]
        # (VERDICT r2 weak #7: 256M elements per iteration at B=1M, D=16).
        live_slot = jnp.argmax(live, axis=-1)[..., None]  # [B, 1]
        comp_tgt = jnp.take_along_axis(tgt, live_slot, axis=-1)[..., 0]  # [B]
        comp_add = jnp.take_along_axis(add, live_slot, axis=-1)[..., 0]  # [B]
        new_tgt = jnp.where(single, comp_tgt[safe], new_tgt)
        new_add = jnp.where(single, add + comp_add[safe], new_add)
        # a composition that lands on the vertex itself wrapped a cycle the
        # mutual-edge pass missed; it becomes ``frozen`` next iteration

        # saturate: legitimate ranks/hop-counts are < batch, so capping at
        # batch only affects un-collapsible cycles — whose floors would
        # otherwise grow (and overflow) forever, keeping ``changed`` true
        # for the whole budget instead of settling in O(log batch) rounds.
        new_floor = jnp.minimum(new_floor, jnp.int32(batch))
        new_add = jnp.minimum(new_add, jnp.int32(batch))

        changed = (
            (new_tgt != tgt).any() | (new_floor != floor).any() | (new_missing != missing_blocked).any()
        )
        return it + 1, new_tgt, new_add, new_floor, new_missing, changed

    def cond(state):
        it, _tgt, _add, _floor, _miss, changed = state
        return (it < max_iters) & changed

    state = (jnp.int32(0), tgt, add, floor, missing_blocked, jnp.bool_(True))
    _, tgt, add, floor, missing_blocked, _ = jax.lax.while_loop(cond, body, state)

    live = tgt >= 0
    final = (live.sum(axis=-1) == 0) & ~missing_blocked
    vrank = floor.max(axis=-1)

    # fold SCC members onto their leader: shared rank = max member rank
    scc_rank = jnp.zeros(batch, jnp.int32).at[leader].max(jnp.where(final, vrank, 0))
    scc_final = jnp.ones(batch, bool).at[leader].min(final)
    scc_missing = jnp.zeros(batch, bool).at[leader].max(missing_blocked)
    resolved = scc_final[leader] & ~scc_missing[leader]
    rank = jnp.where(resolved, scc_rank[leader], _UNRESOLVED_RANK).astype(jnp.int32)
    stuck = ~resolved & ~(missing_blocked | scc_missing[leader])

    order = _order_from_ranks(rank, leader, dot_src, dot_seq)
    return order, resolved, rank, leader, stuck


def _reaches(deps, marked):
    """``(reaches, passes)``: the rows that are ``marked`` or reach a marked
    row over ``deps`` (indices; negative: none), found by spreading the mark
    against the edges to a fixpoint, one hop a pass; no pass where no row
    is marked."""
    live = deps >= 0
    safe = jnp.where(live, deps, 0)

    def spread(state):
        reaches, _, passes = state
        wider = reaches | (live & reaches[safe]).any(axis=-1)
        return wider, (wider != reaches).any(), passes + 1

    reaches, _, passes = jax.lax.while_loop(
        lambda state: state[1], spread, (marked, marked.any(), jnp.int32(0))
    )
    return reaches, passes


def _resolve_general_components(deps, dot_src, dot_seq, residual):
    """Exact components of a graph whose edges point either way
    (:func:`resolve_general` with ``residual``; self-dependencies pruned).

    A row is *blocked* while it reaches a row with a ``MISSING`` slot:
    found by spreading the mark against the edges to a fixpoint, one hop a
    pass, a loop that does not run where no slot is ``MISSING``.  Every
    other row is resolved or ``stuck``, so a caller that hands the stuck
    rows to the host's Tarjan strands nothing.  Of those rows, the ones
    with no dependency left run first, in batch order: whatever depends on
    them comes after.  The rows that do wait for another (a tenth of a
    round at the benchmark's shapes) are compacted into ``residual``
    slots, in batch order, and their reachability is closed there as a
    dense 0/1 matrix squared on the MXU until it stops changing: at most
    ``ceil(log2(residual))`` squarings, fewer where the longest path is
    short (each doubles the path length covered).  Two rows are one
    component iff each reaches the other; its leader is its first row; a
    component that depends on another reaches strictly more rows, so
    ``(rows reached, leader, dot)`` is an execution order with components
    contiguous and in dot order (``tarjan.rs:15``).  Where more than
    ``residual`` rows wait, all of them come back ``stuck``: they depend
    on resolved rows and on each other only, and nothing resolved depends
    on them.  ``iters`` counts the passes of both loops."""
    batch, width = deps.shape
    size = min(residual, batch)
    idx = jnp.arange(batch, dtype=jnp.int32)
    live = deps >= 0
    safe = jnp.where(live, deps, 0)

    blocked, passes = _reaches(deps, (deps == MISSING).any(axis=-1))
    waits = ~blocked & live.any(axis=-1)  # for a row that runs this round too
    waiting = waits.sum().astype(jnp.int32)
    overflow = waiting > size

    # the waiting rows, compacted in batch order; an edge to a row that
    # does not wait is dropped (that row runs first)
    _, rows = jax.lax.sort(
        ((~waits).astype(jnp.int32), idx), num_keys=1, is_stable=True
    )
    rows = rows[:size]
    local = jnp.arange(size, dtype=jnp.int32)
    held = local < waiting
    slot_of = jnp.full((batch,), -1, jnp.int32).at[
        jnp.where(held, rows, batch)
    ].set(local, mode="drop")
    edges = jnp.where(live[rows] & held[:, None], slot_of[safe[rows]], -1)  # [size, D]
    reach = local[:, None] == local[None, :]
    for d in range(width):
        reach = reach | (edges[:, d, None] == local[None, :])

    def square(state):
        reach, _, squarings = state
        ones = reach.astype(jnp.bfloat16)  # 0/1, summed in float32: exact
        wider = jnp.dot(ones, ones, preferred_element_type=jnp.float32) > 0
        return wider, (wider != reach).any(), squarings + 1

    bound = max(1, (size - 1).bit_length())
    reach, _, squarings = jax.lax.while_loop(
        lambda state: state[1] & (state[2] < bound),
        square, (reach, waiting > 0, jnp.int32(0)),
    )
    reached = reach.sum(axis=-1).astype(jnp.int32)  # itself among them
    first = jnp.argmax(reach & reach.T, axis=-1).astype(jnp.int32)  # leader's slot

    placed = jnp.where(held & ~overflow, rows, batch)
    stuck = waits & overflow
    resolved = ~blocked & ~stuck
    rank = jnp.where(resolved, 0, _UNRESOLVED_RANK).astype(jnp.int32).at[placed].set(
        reached, mode="drop"
    )
    leader = idx.at[placed].set(rows[first], mode="drop")
    order = _order_from_ranks(rank, leader, dot_src, dot_seq)
    return GeneralResolution(order, resolved, rank, leader, stuck, passes + squarings)


# ---------------------------------------------------------------------------
# one key a command: the components of a key's run
# ---------------------------------------------------------------------------


class KeyRunResolution(NamedTuple):
    """Result of :func:`resolve_key_runs`, by sorted position."""

    order: jax.Array  # int32[W] positions: the resolved ones first, in execution order
    resolved: jax.Array  # bool[W] — executes this round, at its place in ``order``
    finish: jax.Array  # bool[W] — executes this round, at the place the host finds
    scc_rows: jax.Array  # int32[] — resolved rows in a component of several
    scc_count: jax.Array  # int32[] — components of several rows
    scc_rows_max: jax.Array  # int32[] — the largest of them
    iters: jax.Array  # int32[] — passes: this one, and the blocked set's


def resolve_key_runs(
    deps: jax.Array,  # int32[W, D] sorted positions; TERMINAL for none
    head: jax.Array,  # bool[W] — the position begins a key's run
    valid: jax.Array,  # bool[W]
    committed: jax.Array,  # bool[W]
    dot_src: jax.Array,  # int32[W]
    dot_seq: jax.Array,  # int32[W]
) -> KeyRunResolution:
    """The strongly connected components of a working set of one-key
    commands, and their order, where the rows stand sorted by key (each
    key's rows one contiguous run) and every dependency of a row is a row
    of its own run (a command conflicts on its one key) or ``TERMINAL``
    (executed already).  Dependencies may point either way along the run:
    replicas that saw a round's commands in different orders report
    different predecessors, and the union of what they reported has cycles.

    A row is *blocked* while it reaches a row that is valid and not
    committed (the resolvers' ``MISSING``): found by spreading the mark
    against the edges to a fixpoint, a loop that does not run where every
    valid row is committed.  The other valid rows execute this round.
    Among them a cycle needs an edge that points forward, and lies inside
    the union of the spans of such edges that touch: so the runs cut into
    *intervals* (a running maximum of the forward reach), every component
    lies inside one, and an edge between two intervals points back.  An
    interval in which every row but the first has an edge to the row just
    before it is one component: the first row reaches the interval's end
    over the forward spans and everything walks back down to it.  That is
    the shape concurrent writes leave (each quorum member reports the
    latest command it saw before this one, and with three members one of
    them saw the row just before).  Such an interval executes in dot order
    (``tarjan.rs:15``), the intervals of a run in position order, runs in
    any order: one sort by (interval, dot).

    A run that holds an interval which is not such a chain (reads that
    commute leave some) is not cut further here: its executable rows are
    marked ``finish``, and the caller hands them, with their
    dependencies, to the host's Tarjan (``executor/graph/deps_graph.py``)
    in the same round.  They execute this round either way: every row a
    ``finish`` row reaches is executable too."""
    work = deps.shape[0]
    pos = jnp.arange(work, dtype=jnp.int32)
    int_max = jnp.iinfo(jnp.int32).max
    live = deps >= 0
    run_start = jax.lax.cummax(jnp.where(head, pos, 0))

    # blocked: reaches an uncommitted row (no pass where every valid row
    # is committed)
    blocked, passes = _reaches(deps, valid & ~committed)
    runs = valid & committed & ~blocked  # executes this round

    def before(x):  # the value one position earlier
        return jnp.concatenate([jnp.full((1,), -1, jnp.int32), x[:-1]])

    # intervals: a row begins one iff no executable row before it reaches
    # it or past it (a reach never leaves its run, and runs stand in
    # position order, so one running maximum serves every run)
    reach = jnp.where(runs, jnp.maximum(jnp.where(live, deps, -1).max(axis=-1), pos), -1)
    begins = runs & (before(jax.lax.cummax(reach)) < pos)
    start = jax.lax.cummax(jnp.where(begins, pos, -1))
    # a chain: every executable row that begins no interval has an edge to
    # the executable row just before it
    last_run = before(jax.lax.cummax(jnp.where(runs, pos, -1)))
    chained = ~runs | begins | (deps == last_run[:, None]).any(axis=-1)
    unchained = jnp.zeros((work,), bool).at[run_start].max(~chained)[run_start]
    finish = runs & unchained
    resolved = runs & ~unchained

    order = jnp.lexsort(
        (dot_seq, dot_src, jnp.where(resolved, start, int_max))
    ).astype(jnp.int32)
    size = jnp.zeros((work,), jnp.int32).at[
        jnp.where(resolved, start, work)
    ].add(1, mode="drop")
    several = jnp.where(size > 1, size, 0)
    return KeyRunResolution(
        order, resolved, finish,
        several.sum().astype(jnp.int32),
        (size > 1).sum().astype(jnp.int32),
        several.max().astype(jnp.int32),
        passes + 1,
    )
