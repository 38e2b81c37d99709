"""Pallas-vs-composed parity suite (ops/pallas_resolve.py).

The routing contract is EXACT: for every input — permuted DAGs, cycles,
missing deps, residual seams, non-pow2 caps — the Pallas route must
return bit-for-bit the composed route's outputs (same resolved/stuck/
rank/order, same residual-column protocol), under the same donation
discipline (``resident_uploads == 1`` at the executor level).  On the
CPU pin the kernels run in Pallas interpret mode, so this suite proves
the contract on every push (scripts/run_device_stripped.py re-runs the
suite with ``FANTOCH_PALLAS=1`` forced through the executor stack).  On
the TPU the kernels do not lower (ops/pallas_resolve.py).

Every test forces the route explicitly (``set_pallas_kernels``) so the
suite is independent of the default (off).
"""

import contextlib
import random

import numpy as np
import pytest

import jax.numpy as jnp

from fantoch_tpu.ops import pallas_resolve as pallas_resolve
from fantoch_tpu.ops.graph_resolve import (
    MISSING,
    TERMINAL,
    resolve_graph_plane_step,
    resolve_graph_plane_step_xla,
)
from fantoch_tpu.ops.pred_resolve import (
    resolve_pred_plane_step,
    resolve_pred_plane_step_xla,
)
from fantoch_tpu.ops.table_ops import (
    fused_table_round,
    fused_table_round_xla,
    fused_votes_commit,
    fused_votes_commit_xla,
)


@contextlib.contextmanager
def forced_pallas(enabled=True):
    pallas_resolve.set_pallas_kernels(enabled)
    try:
        yield
    finally:
        pallas_resolve.set_pallas_kernels(None)


def _assert_tuples_equal(got, want, fields=None):
    names = fields or range(len(tuple(want)))
    for name, g, w in zip(names, tuple(got), tuple(want)):
        assert np.array_equal(np.asarray(g), np.asarray(w)), name


# ---------------------------------------------------------------------------
# pred plane step
# ---------------------------------------------------------------------------


def _pred_feed(rng, cap, width, n_installed):
    """One random dispatch feed: installs new rows (deps may point at
    already-installed rows, be TERMINAL, or MISSING), plus patches that
    re-point MISSING cells of earlier rows (the residual wake seam)."""
    U, P = 6, 6
    u_row = np.full((U,), cap, np.int32)
    u_deps = np.full((U, width), TERMINAL, np.int32)
    u_clock = np.zeros((U,), np.int32)
    u_src = np.zeros((U,), np.int32)
    installs = min(rng.randrange(1, U + 1), cap - n_installed)
    for i in range(max(installs, 0)):
        row = n_installed + i
        u_row[i] = row
        u_clock[i] = rng.randrange(1, 1000)
        u_src[i] = rng.randrange(1, 4)
        for w in range(rng.randrange(0, width + 1)):
            u_deps[i, w] = rng.choice(
                [TERMINAL, MISSING, rng.randrange(0, max(row, 1))]
            )
    p_row = np.full((P,), cap, np.int32)
    p_col = np.zeros((P,), np.int32)
    p_val = np.full((P,), TERMINAL, np.int32)
    for j in range(rng.randrange(0, P)):
        if n_installed == 0:
            break
        p_row[j] = rng.randrange(0, n_installed)
        p_col[j] = rng.randrange(0, width)
        p_val[j] = rng.choice([TERMINAL, rng.randrange(0, n_installed)])
    return (
        (u_row, u_deps, u_clock, u_src, p_row, p_col, p_val),
        n_installed + max(installs, 0),
    )


def test_pred_plane_step_parity_multi_dispatch():
    """Bit-for-bit PredPlaneStep parity across random multi-dispatch
    sequences, each route threading its OWN resident state (so donation
    runs on both sides) — installs, MISSING-cell patches waking earlier
    rows, and the two-phase fixpoint all inside the window."""
    rng = random.Random(11)
    for _trial in range(4):
        cap, width = 24, 4
        state_p = state_x = None

        def fresh():
            return (
                jnp.full((cap, width), TERMINAL, jnp.int32),
                jnp.zeros((cap,), jnp.int32),
                jnp.zeros((cap,), jnp.int32),
                jnp.zeros((cap,), jnp.bool_),
                jnp.zeros((cap,), jnp.bool_),
            )

        state_p, state_x = fresh(), fresh()
        installed = 0
        for _round in range(5):
            feed, installed = _pred_feed(rng, cap, width, installed)
            feed_j = tuple(jnp.asarray(a) for a in feed)
            with forced_pallas(True):
                out_p = resolve_pred_plane_step(*state_p, *feed_j)
            with forced_pallas(False):
                out_x = resolve_pred_plane_step(*state_x, *feed_j)
            _assert_tuples_equal(out_p, out_x, out_p._fields)
            state_p = tuple(out_p[:5])
            state_x = tuple(out_x[:5])


# ---------------------------------------------------------------------------
# graph plane step
# ---------------------------------------------------------------------------


def _graph_feed(rng, cap, width, n_installed, *, with_cycle):
    U, P, E = 6, 4, 3
    u_row = np.full((U,), cap, np.int32)
    u_deps = np.full((U, width), TERMINAL, np.int32)
    u_key = np.zeros((U,), np.int32)
    u_src = np.zeros((U,), np.int32)
    u_seq = np.zeros((U,), np.int32)
    installs = min(rng.randrange(1, U + 1), cap - n_installed)
    for i in range(max(installs, 0)):
        row = n_installed + i
        u_row[i] = row
        u_key[i] = rng.randrange(0, 4)
        u_src[i] = rng.randrange(1, 4)
        u_seq[i] = row + 1
        for w in range(rng.randrange(0, width + 1)):
            u_deps[i, w] = rng.choice(
                [TERMINAL, MISSING, rng.randrange(0, max(row, 1))]
            )
    if with_cycle and installs >= 2:
        # a deliberate 2-cycle between the first two fresh rows: the
        # general modes must flag both stuck identically on both routes
        a, b = n_installed, n_installed + 1
        u_deps[0, 0] = b
        u_deps[1, 0] = a
    p_row = np.full((P,), cap, np.int32)
    p_col = np.zeros((P,), np.int32)
    p_val = np.full((P,), TERMINAL, np.int32)
    for j in range(rng.randrange(0, P)):
        if n_installed == 0:
            break
        p_row[j] = rng.randrange(0, n_installed)
        p_col[j] = rng.randrange(0, width)
        p_val[j] = rng.choice([TERMINAL, rng.randrange(0, n_installed)])
    e_row = np.full((E,), cap, np.int32)
    if n_installed and rng.random() < 0.5:
        e_row[0] = rng.randrange(0, n_installed)
    return (
        (u_row, u_deps, u_key, u_src, u_seq, p_row, p_col, p_val, e_row),
        n_installed + max(installs, 0),
    )


@pytest.mark.parametrize("mode", ["keyed", "general", "general_resident"])
@pytest.mark.parametrize("cap", [32, 48])  # 48: the non-pow2 corner
def test_graph_plane_step_parity_modes(mode, cap):
    """Bit-for-bit GraphPlaneStep parity in all three modes over random
    permuted-DAG feeds with cycles, missing deps, host-oracle executed
    marks, and a non-pow2 capacity (the keyed residual publish-gate
    corner: residual_size derives from cap)."""
    rng = random.Random(hash((mode, cap)) & 0xFFFF)
    width = 4

    def fresh():
        return (
            jnp.full((cap, width), TERMINAL, jnp.int32),
            jnp.zeros((cap,), jnp.int32),
            jnp.zeros((cap,), jnp.int32),
            jnp.zeros((cap,), jnp.int32),
            jnp.zeros((cap,), jnp.bool_),
            jnp.zeros((cap,), jnp.bool_),
        )

    state_p, state_x = fresh(), fresh()
    installed = 0
    for round_i in range(4):
        feed, installed = _graph_feed(
            rng, cap, width, installed, with_cycle=(round_i == 1)
        )
        feed_j = tuple(jnp.asarray(a) for a in feed)
        with forced_pallas(True):
            out_p = resolve_graph_plane_step(*state_p, *feed_j, mode=mode)
        with forced_pallas(False):
            out_x = resolve_graph_plane_step(*state_x, *feed_j, mode=mode)
        _assert_tuples_equal(out_p, out_x, out_p._fields)
        state_p = tuple(out_p[:6])
        state_x = tuple(out_x[:6])


# ---------------------------------------------------------------------------
# table plane
# ---------------------------------------------------------------------------


def test_votes_commit_parity_residual_seam():
    """Bit-for-bit 7-tuple parity (including run_*/residual columns)
    over random vote batches with beyond-gap runs, each route threading
    its own resident frontier."""
    rng = random.Random(23)
    K, n, V = 16, 3, 16
    f_p = jnp.zeros((K, n), jnp.int32)
    f_x = jnp.zeros((K, n), jnp.int32)
    for _round in range(6):
        vkey = np.array([rng.randrange(0, K) for _ in range(V)], np.int32)
        vby = np.array([rng.randrange(0, n) for _ in range(V)], np.int32)
        vstart = np.array([rng.randrange(1, 12) for _ in range(V)], np.int32)
        vend = vstart + np.array(
            [rng.randrange(0, 4) for _ in range(V)], np.int32
        )
        valid = np.array([rng.random() < 0.85 for _ in range(V)], bool)
        feed = tuple(
            jnp.asarray(a) for a in (vkey, vby, vstart, vend, valid)
        )
        with forced_pallas(True):
            out_p = fused_votes_commit(f_p, *feed, threshold=2)
        with forced_pallas(False):
            out_x = fused_votes_commit(f_x, *feed, threshold=2)
        _assert_tuples_equal(
            out_p, out_x,
            ["frontier", "stable", "run_key", "run_by", "run_start",
             "run_end", "residual"],
        )
        f_p, f_x = out_p[0], out_x[0]


def test_table_round_parity_chain():
    """Bit-for-bit parity of the fused dense round across a chain of
    rounds threading donated prior/frontier through both routes."""
    rng = random.Random(31)
    K, n, B = 16, 3, 8
    pr_p, fr_p = jnp.zeros((K,), jnp.int32), jnp.zeros((K, n), jnp.int32)
    pr_x, fr_x = jnp.zeros((K,), jnp.int32), jnp.zeros((K, n), jnp.int32)
    for _round in range(6):
        key = np.array([rng.randrange(0, K - 1) for _ in range(B)], np.int32)
        mc = np.array([rng.randrange(0, 8) for _ in range(B)], np.int32)
        feed = (jnp.asarray(key), jnp.asarray(mc))
        with forced_pallas(True):
            out_p = fused_table_round(pr_p, fr_p, *feed, threshold=2, voters=2)
        with forced_pallas(False):
            out_x = fused_table_round(pr_x, fr_x, *feed, threshold=2, voters=2)
        _assert_tuples_equal(
            out_p, out_x,
            ["prior", "frontier", "clock", "vote_start", "executable",
             "gaps"],
        )
        pr_p, fr_p = out_p[0], out_p[1]
        pr_x, fr_x = out_x[0], out_x[1]


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


def test_route_resolution_precedence(monkeypatch):
    """Config override beats the env var beats the default, which is
    off on every backend (the composed programs are the route that
    compiles everywhere)."""
    monkeypatch.delenv("FANTOCH_PALLAS", raising=False)
    pallas_resolve.set_pallas_kernels(None)
    assert pallas_resolve.pallas_enabled() is False  # the default
    monkeypatch.setenv("FANTOCH_PALLAS", "1")
    assert pallas_resolve.pallas_enabled() is True
    monkeypatch.setenv("FANTOCH_PALLAS", "0")
    assert pallas_resolve.pallas_enabled() is False
    try:
        pallas_resolve.set_pallas_kernels(True)
        assert pallas_resolve.pallas_enabled() is True  # config beats env
    finally:
        pallas_resolve.set_pallas_kernels(None)


def test_apply_pallas_config():
    """The executor-construction seam folds Config.pallas_kernels into
    the route; None leaves the resolution chain untouched."""
    from fantoch_tpu.core.config import Config

    try:
        pallas_resolve.apply_pallas_config(Config(3, 1))
        assert pallas_resolve._override is None
        pallas_resolve.apply_pallas_config(Config(3, 1, pallas_kernels=True))
        assert pallas_resolve.pallas_enabled() is True
        pallas_resolve.apply_pallas_config(Config(3, 1, pallas_kernels=False))
        assert pallas_resolve.pallas_enabled() is False
    finally:
        pallas_resolve.set_pallas_kernels(None)


def test_route_on_and_kernel_raising_propagates():
    """There is no fallback: with the route on, a kernel that fails to
    compile raises out of the dispatch and the composed program is not
    tried in its place; pallas_status counts only what was served."""
    calls = {"pallas": 0, "composed": 0}

    def bad_kernel(x):
        calls["pallas"] += 1
        raise NotImplementedError(
            "Unimplemented primitive in Pallas TPU lowering: scatter"
        )

    def composed(x):
        calls["composed"] += 1
        return x + 1

    pallas_resolve._served.pop("_test_family", None)
    try:
        with forced_pallas(True):
            with pytest.raises(NotImplementedError, match="scatter"):
                pallas_resolve.route_dispatch(
                    "_test_family", bad_kernel, composed, (1,), {}
                )
        assert calls == {"pallas": 1, "composed": 0}
        assert "_test_family" not in pallas_resolve.pallas_status()["served"]
        with forced_pallas(False):
            assert pallas_resolve.route_dispatch(
                "_test_family", bad_kernel, composed, (1,), {}
            ) == 2
        assert pallas_resolve.pallas_status()["served"]["_test_family"] == {
            "xla": 1
        }
    finally:
        pallas_resolve._served.pop("_test_family", None)


# ---------------------------------------------------------------------------
# executor-level routing: the planes serve identically on either route,
# with the donation discipline intact (resident_uploads == 1)
# ---------------------------------------------------------------------------


def test_pred_executor_parity_and_single_upload_under_pallas():
    """DevicePredPlane serving through the Pallas route matches the
    composed-route plane (results, per-key order, and upload count —
    the donation contract survives the kernel swap)."""
    from tests.test_pred_plane import (
        _conflict_workload,
        _plane_executor,
        _assert_parity,
    )

    rng = random.Random(7)
    infos = _conflict_workload(rng, count=40)
    with forced_pallas(True):
        ex_pallas = _plane_executor()
        for info in infos:
            ex_pallas.handle(info, None)
        uploads_pallas = ex_pallas._plane.resident_uploads
    with forced_pallas(False):
        ex_composed = _plane_executor()
        for info in infos:
            ex_composed.handle(info, None)
        uploads_composed = ex_composed._plane.resident_uploads
    # identical upload count: capacity growth re-uploads are workload-
    # driven and count the same on either route — the Pallas kernels add
    # ZERO extra uploads (donation discipline unchanged)
    assert uploads_pallas == uploads_composed
    # route-vs-route parity (to_clients_iter drains, so one comparison):
    # the Pallas-routed executor against the composed-routed one
    _assert_parity(ex_pallas, ex_composed)


def test_pred_executor_steady_state_single_upload_under_pallas():
    """A workload inside the initial window: exactly ONE resident upload
    on the Pallas route (the ISSUE's steady-state contract)."""
    from tests.test_pred_plane import _conflict_workload, _plane_executor

    rng = random.Random(3)
    infos = _conflict_workload(rng, count=8, keys=("Ka", "Kb"))
    with forced_pallas(True):
        ex = _plane_executor()
        ex.handle_batch(infos, None)
        assert ex._plane.resident_uploads == 1


def test_table_plane_parity_under_pallas():
    """DeviceTablePlane commit dispatches agree bit-for-bit between the
    two routes, residual re-feeds included, with one resident upload."""
    from fantoch_tpu.executor.table_plane import DeviceTablePlane

    def drive(enabled):
        with forced_pallas(enabled):
            plane = DeviceTablePlane(3, stability_threshold=2, key_buckets=8)
            for k in range(6):
                plane.bucket(f"k{k}")
            r = random.Random(99)
            stables = []
            for _round in range(6):
                vk, vb, vs, ve = [], [], [], []
                for _ in range(8):
                    vk.append(r.randrange(0, 6))
                    vb.append(r.randrange(1, 4))
                    s = r.randrange(1, 12)
                    vs.append(s)
                    ve.append(s + r.randrange(0, 4))
                stables.append(
                    plane.commit_votes(
                        np.array(vk, np.int64), np.array(vb, np.int64),
                        np.array(vs, np.int64), np.array(ve, np.int64),
                    )
                )
            return plane, stables

    plane_p, outs_p = drive(True)
    plane_x, outs_x = drive(False)
    for got, want in zip(outs_p, outs_x):
        assert np.array_equal(got, want)
    assert np.array_equal(plane_p.frontiers(), plane_x.frontiers())
    assert plane_p.resident_uploads == plane_x.resident_uploads == 1
