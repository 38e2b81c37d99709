"""The device Caesar round with a coordinator at every site
(``mesh_step.caesar_protocol_step(sites=n)``) against the plain reference of
the mechanism (``tests/caesar_sites_reference.py``): seeded random rounds at a
small size on the CPU, n = 3, 5, 7 (and 4 and 9, whose rings leave out more
than one site or none), a hot key and zipf keys, one to seven sites, every
replica live, replicas lagging, under the write quorum, several rounds in a row
so that carried rows and learnt clocks are exercised.  Clocks, commit and
fast-path flags, what executed and in which order, what is carried, the five
tallies and the table at the end: equal, exactly.  And the at-shape case the
chip runs (``chiprun -- python3 -c "from tests.test_caesar_sites_reference
import ...``; the verify skill has the line)."""

import jax
import numpy as np
import pytest

from fantoch_tpu.parallel import mesh_step
from tests import caesar_reference
from tests.caesar_sites_reference import (
    PAD, TALLIES, Carried, CaesarSitesReference, quorum_sizes, ring)
# the Tempo sites round's seeded rounds, meshes and sizes: one generator, two families
from tests.test_tempo_sites_reference import (
    BATCH, BUCKETS, CAPACITY, SITE_BASE, at_shape_rounds, batches, forced_mesh)


def assert_round_equal(out, want, at):
    executed = np.asarray(out.executed)
    for name, got, expected in (
        ("clock", out.clock, want.clock), ("committed", out.committed, want.committed),
        ("fast_path", out.fast_path, want.fast_path), ("executed", executed, want.executed),
        ("order", np.asarray(out.order)[: int(executed.sum())], want.order),
        ("slow_paths", out.slow_paths, want.slow_paths), ("pending", out.pending, want.pending),
        ("pend_dropped", out.pend_dropped, want.dropped),
        ("watermark", out.watermark, want.watermark),
        ("tallies", out.tallies, [want.tallies[name] for name in TALLIES] + [want.wait_passes]),
    ):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(expected),
                                      err_msg=f"{name}, round {at}")


def assert_state_equal(state, reference, buckets=BUCKETS):
    table = np.asarray(state.key_clock)
    for r in range(reference.n):
        want = np.zeros(buckets, np.int64)
        for key, clock in reference.clock[r].items():
            want[key] = clock
        np.testing.assert_array_equal(table[r], want, err_msg=f"replica {r}")
    carried = [(k[0], s, q, c) for k, s, q, c in zip(
        np.asarray(state.pend_key).tolist(), np.asarray(state.pend_src).tolist(),
        np.asarray(state.pend_seq).tolist(), np.asarray(state.pend_clock).tolist())
        if k[0] != PAD]
    assert carried == [tuple(cmd) for cmd in reference.pending]


def run_against_reference(n, sites, seed, keys="hot", rounds=4, live_replicas=None, mesh=None,
                          capacity=CAPACITY):
    mesh = forced_mesh() if mesh is None else mesh
    state = mesh_step.init_caesar_state(mesh, n, key_buckets=BUCKETS, pending_capacity=capacity)
    program = mesh_step.jit_caesar_step(
        mesh, num_replicas=n, live_replicas=live_replicas, sites=n, site_base=SITE_BASE)
    reference = CaesarSitesReference(n, capacity, SITE_BASE, live_replicas)
    rng = np.random.default_rng([seed, n, sites])
    seen = {"executed": 0, "slow": 0, "fast": 0, "dropped": 0, "carried": 0, "proposed_anew": 0,
            "waited_and_fast": 0, "passes": 0, **dict.fromkeys(TALLIES, 0)}
    for at in range(rounds):
        key, src, seq = batches(rng, 1, n, sites, keys)
        state, out = program(state, key[0], src[0], seq[0])
        assert isinstance(out, mesh_step.CaesarSiteStepOutput)
        want = reference.round(key[0], src[0], seq[0])
        assert_round_equal(out, want, at)
        seen["executed"] += len(want.order)
        seen["slow"] += want.slow_paths
        seen["fast"] += sum(want.fast_path)
        seen["dropped"] += want.dropped
        seen["carried"] += want.pending
        seen["proposed_anew"] += sum(cmd.clock < 0 for cmd in reference.pending)
        seen["waited_and_fast"] += sum(
            want.fast_path[w] and "waited" in said.values() for w, said in want.answers.items())
        seen["passes"] = max(seen["passes"], want.wait_passes)
        for name, count in want.tallies.items():
            seen[name] += count
    assert_state_equal(state, reference)
    return seen, reference


@pytest.mark.parametrize("keys", ("hot", "zipf"))
@pytest.mark.parametrize("sites", (1, 2, 3, 7))
@pytest.mark.parametrize("n", (3, 5, 7))
def test_the_round_equals_the_reference_with_every_replica_live(n, sites, keys):
    sites = min(sites, n)
    seen, reference = run_against_reference(n, sites, seed=59 + sites, keys=keys)
    assert seen["executed"] > 100 and not reference.pending  # all live: nothing is held
    assert seen["slow"] + seen["fast"] == seen["executed"]
    if sites == 1 or n == 3:  # one view, or a ring that is everyone: nothing is retried
        assert seen["slow"] == seen["reject_acks"] == seen["retry_clock_lift"] == 0
    if sites == 1:  # ... and with one view nobody has met a higher timestamp
        assert seen["wait_acks"] == 0 and seen["passes"] == 1
    else:
        assert seen["wait_rows"] > 0 and seen["waited_and_fast"] > 0
    if n > 3 and sites > 2 and keys == "hot":
        assert seen["slow"] > 0 and seen["retry_clock_lift"] >= seen["slow"]
        assert seen["passes"] > 1


@pytest.mark.parametrize("n, sites", [(4, 4), (9, 9), (9, 4)])
def test_the_round_equals_the_reference_at_other_rings(n, sites):
    """n = 4: a ring of four, everyone; n = 9: rings of seven, two sites
    outside each."""
    seen, _ = run_against_reference(n, sites, seed=4, rounds=5)
    assert (seen["slow"] > 0) == (n == 9)


@pytest.mark.parametrize("n, live, held", [
    (7, 6, "nothing"),  # one replica lags: its commands are numbered low and retried
    (7, 4, "nothing"),  # the write quorum of four still commits every retry
    (7, 3, "uncommitted"),  # under it a retried row stays uncommitted and is proposed anew
    (5, 3, "nothing"), (5, 2, "uncommitted"), (3, 1, "nothing"),
])
def test_the_round_equals_the_reference_with_replicas_lagging(n, live, held):
    capacity = 8 * BATCH if held == "uncommitted" else CAPACITY
    seen, reference = run_against_reference(
        n, n, seed=29, rounds=6, live_replicas=live, capacity=capacity)
    if held == "nothing":
        assert seen["executed"] > 100 and not reference.pending
        assert n == 3 or seen["reject_acks"] > 0
    else:  # (the tallies are of the rows committed: the fast ones, whom nobody rejected)
        assert seen["slow"] > 0 and seen["proposed_anew"] > 20 and seen["dropped"] == 0
        assert any(cmd.clock < 0 for cmd in reference.pending)
        assert seen["reject_acks"] == 0 < seen["fast"]


def test_a_carried_committed_row_above_a_lagging_coordinators_proposal_is_not_ignored():
    """No round leaves a committed row behind an uncommitted one (every retry
    clock lies above every proposal of its key), so the buffer is seeded by
    hand: key 0 holds two rows committed at 50 and 60 by sources 2 and 3, the
    six live replicas know 60 there, and replica 6 lags at 0.  Site 6's
    commands on the key are numbered from 1: every live member of their rings
    has the carried rows above them and rejects; site 0's are numbered from 61
    and, standing earlier, are what site 6's retried ones have to outbid."""
    n, capacity = 7, 8
    mesh = forced_mesh()
    state = mesh_step.init_caesar_state(mesh, n, key_buckets=BUCKETS, pending_capacity=capacity)
    carried = [(0, SITE_BASE + 1, 900, 50), (0, SITE_BASE + 2, 900, 60)]
    columns = [np.array([row[c] for row in carried] + [-1] * (capacity - 2), np.int32)
               for c in range(4)]
    table = np.zeros((n, BUCKETS), np.int32)
    table[:6, 0] = 60
    state = jax.device_put(
        mesh_step.CaesarMeshState(table, columns[0][:, None], *columns[1:]),
        jax.tree_util.tree_map(lambda leaf: leaf.sharding, state))
    reference = CaesarSitesReference(n, capacity, SITE_BASE, live_replicas=6)
    reference.pending = [Carried(*row) for row in carried]
    for r in range(6):
        reference.clock[r][0] = 60
    program = mesh_step.jit_caesar_step(
        mesh, num_replicas=n, live_replicas=6, sites=n, site_base=SITE_BASE)
    key = np.zeros(6, np.int32)
    src = np.array([1, 7, 1, 7, 2, 7], np.int32) + SITE_BASE - 1
    seq = np.array([1, 1, 2, 2, 1, 3], np.int32)
    state, out = program(state, key, src, seq)
    want = reference.round(key, src, seq)
    assert_round_equal(out, want, 0)
    lagging = [capacity + row for row in (1, 3, 5)]
    assert [want.proposed[w] for w in lagging] == [1, 2, 3]
    for w in lagging:  # ring {6, 0, 1, 2, 3, 4}: the five live members reject
        assert sorted(want.answers[w].values()) == ["ok"] + ["rejected"] * 5
    assert want.tallies["reject_acks"] == 15 and want.slow_paths == 3
    assert min(want.clock[w] for w in lagging) > 61
    assert_state_equal(state, reference)


def test_a_short_buffer_drops_what_it_cannot_carry():
    seen, reference = run_against_reference(5, 5, seed=2, rounds=6, live_replicas=2, capacity=2)
    assert seen["dropped"] > 0 and seen["carried"] > 0


def test_the_round_equals_the_reference_with_a_replica_a_device():
    """Seven replica rows over seven devices along ``replica``: the clocks at
    the round's keys are gathered along the axis, the answers are every
    device's own copy, and each scatters into the row it holds; and a batch
    split over four devices."""
    seen, _ = run_against_reference(7, 7, seed=3, mesh=forced_mesh(7, 1))
    assert seen["executed"] > 100 and seen["slow"] > 0
    seen, _ = run_against_reference(5, 5, seed=3, mesh=forced_mesh(1, 4), live_replicas=4)
    assert seen["executed"] > 100 and seen["slow"] > 0


def test_no_sites_is_the_parents_round():
    """``sites=None`` traces the round as it was: the jitted program is built
    without the two arguments, its output is ``CaesarStepOutput``, nothing of
    the wait condition is in what it lowers to, and it is the one-coordinator
    reference's round (``tests/caesar_reference.py``), which the program with a
    coordinator at every site is not: there every replica numbers every
    command and the quorum is the first rows."""
    mesh = forced_mesh()
    program = mesh_step.jit_caesar_step(mesh, num_replicas=5)
    assert set(program.__wrapped__.keywords) == {"mesh", "num_replicas", "live_replicas"}
    sited = mesh_step.jit_caesar_step(mesh, num_replicas=5, sites=5, site_base=SITE_BASE)
    assert {"sites", "site_base"} <= set(sited.__wrapped__.keywords)
    state = mesh_step.init_caesar_state(mesh, 5, key_buckets=BUCKETS, pending_capacity=CAPACITY)
    rng = np.random.default_rng(59)
    key, src, seq = batches(rng, 1, 5, 5)
    lowered = program.lower(state, key[0], src[0], seq[0]).as_text(debug_info=True)
    assert "caesar_wait" not in lowered and "caesar_retry" not in lowered
    lowered = sited.lower(state, key[0], src[0], seq[0]).as_text(debug_info=True)
    assert "caesar_wait" in lowered and "caesar_retry" in lowered
    reference = caesar_reference.Reference(5, CAPACITY)
    for at in range(4):
        key, src, seq = batches(rng, 1, 5, 5)
        state, out = program(state, key[0], src[0], seq[0])
        assert type(out) is mesh_step.CaesarStepOutput
        rows = [w for w in range(BATCH) if key[0, w] != PAD]
        want = reference.round([
            caesar_reference.Command(int(src[0, w]), int(seq[0, w]), (str(key[0, w]),), "v")
            for w in rows])
        clocks = np.asarray(out.clock)[CAPACITY:]
        assert [int(clocks[w]) for w in rows] == [
            want.verdicts[(int(src[0, w]), int(seq[0, w]))].clock for w in rows]
        assert int(out.slow_paths) == want.slow_paths == 0  # one view: no row is slow
    with pytest.raises(AssertionError, match="one key a command, one shard"):
        mesh_step.caesar_protocol_step(
            mesh_step.init_caesar_state(mesh, 5, key_buckets=BUCKETS, pending_capacity=8,
                                        key_width=2),
            np.stack([key[0], key[0]], axis=1), src[0], seq[0], mesh=mesh, sites=5)
    with pytest.raises(AssertionError, match="a site a replica"):
        mesh_step.caesar_protocol_step(state, key[0], src[0], seq[0], mesh=mesh, sites=3)


def test_the_reference_by_hand_two_sites_one_key():
    """n = 5 (fast quorum 4, write quorum 3): site 0 submits ``a`` then ``c``
    and site 1 submits ``b`` on key 9 of a fresh store, arriving ``a, b, c``.
    Each coordinator numbers its own: ``a`` 1, ``c`` 2, ``b`` 1, so by (T0, dot)
    ``a`` (1, source 1) < ``b`` (1, source 2) < ``c`` (2, source 1).  The views:
    replica 0 has ``a, c, b``; replica 1 ``b, a, c``; replicas 2, 3 and 4
    ``a, b, c``.

    ``c`` is the highest: nobody has met a higher one, its ring {0, 1, 2, 3}
    says ok, it is fast at 2 and depends on what its ring had met below it:
    ``a`` and ``b``.  ``b``'s ring is {1, 2, 3, 4}: none of them had met ``c``
    before ``b``, all say ok at once, fast at 1; its dependencies are ``a``,
    which replicas 2, 3 and 4 met first.  Replica 0, outside the ring, had
    ``c`` before ``b``; nobody asks it.  ``a``'s ring is {0, 1, 2, 3}: replica
    1 met ``b``, which is higher, before ``a``, and waits; ``b`` depends on
    ``a``, so it says ok.  ``a`` is fast at 1.  One ack waited, none rejected,
    and the answer hung on no verdict: one pass.  All execute, ``a, b, c``."""
    assert quorum_sizes(5) == (4, 3) and ring(1, 5, 4) == [1, 2, 3, 4]
    reference = CaesarSitesReference(5, 2, SITE_BASE)
    got = reference.round([9, 9, 9], [1, 2, 1], [1, 1, 2])
    a, b, c = 2, 3, 4  # after the two pending slots
    assert got.proposed == {a: 1, b: 1, c: 2}
    assert got.answers[c] == dict.fromkeys((0, 1, 2, 3), "ok")
    assert got.answers[b] == dict.fromkeys((1, 2, 3, 4), "ok")
    assert got.answers[a] == {0: "ok", 1: "waited", 2: "ok", 3: "ok"}
    assert got.clock[a:] == [1, 1, 2] and got.fast_path[a:] == [True] * 3
    assert got.order == [a, b, c] and got.slow_paths == 0
    assert got.tallies == {"wait_rows": 1, "wait_acks": 1, "reject_acks": 0,
                           "retry_clock_lift": 0}
    assert got.wait_passes == 1
    assert all(reference.clock[r] == {9: 2} for r in range(5))


def test_the_reference_by_hand_a_retry():
    """n = 5 again, a fresh store, key 9: site 1 submits ``y1, y2`` and site 0
    submits ``x``, arriving ``y1, y2, x``.  ``y1`` 1, ``y2`` 2, ``x``
    1: ``x`` (1, source 1) < ``y1`` (1, source 2) < ``y2`` (2, source 2).
    Site 0 is outside site 1's ring {1, 2, 3, 4}, and every member of that
    ring met ``y1`` and ``y2`` before ``x``, so neither depends on ``x``;
    both are fast (nothing above them was met first by their ring).  ``x``'s
    ring is {0, 1, 2, 3}: replica 0 met its own ``x`` first and says ok;
    replicas 1, 2 and 3 met ``y1`` and ``y2`` first, wait for them, and
    reject.  Each knows 2 on the key and counter-proposes 3; ``x`` is retried
    at 3 and commits (five live): lift 2, three rejections, three acks that
    waited, and its answers hung on fast verdicts: two passes.  Order:
    ``y1, y2, x``."""
    reference = CaesarSitesReference(5, 2, SITE_BASE)
    got = reference.round([9, 9, 9], [2, 2, 1], [1, 2, 1])
    y1, y2, x = 2, 3, 4
    assert got.proposed == {y1: 1, y2: 2, x: 1}
    assert got.answers[x] == {0: "ok", 1: "rejected", 2: "rejected", 3: "rejected"}
    assert got.fast_path[y1:] == [True, True, False] and got.clock[y1:] == [1, 2, 3]
    assert got.order == [y1, y2, x] and got.slow_paths == 1
    assert got.tallies == {"wait_rows": 1, "wait_acks": 3, "reject_acks": 3,
                           "retry_clock_lift": 2}
    assert got.wait_passes == 2
    assert [reference.clock[r] for r in range(5)] == [{9: 3}] * 5


def retried_by_the_shortcut(n, site, place, stamp, fast):
    """ISSUE 59's arithmetic for rings of ``n - 1``: ``x`` is retried iff a
    fast ``y`` of the next site on the ring stands earlier in working order and
    has the higher ``(T0, dot)``."""
    return {x for x in site if any(
        site[y] == (site[x] + 1) % n and place[y] < place[x] and stamp[y] > stamp[x] and fast[y]
        for y in site)}


@pytest.mark.parametrize("n", (5, 7))
def test_rings_of_all_but_one_come_down_to_the_next_site(n):
    """The general rule against the shortcut, on random rounds of one fresh
    store each (every replica live, nothing carried: the shortcut's case)."""
    assert quorum_sizes(n)[0] == n - 1
    rng = np.random.default_rng(n)
    retried = 0
    for _ in range(20):
        reference = CaesarSitesReference(n, 0, SITE_BASE)
        for _ in range(2):
            key, src, seq = batches(rng, 1, n, n, "hot", batch=60, buckets=3)
            got = reference.round(key[0], src[0], seq[0])
            rows = list(got.proposed)
            for bucket in set(key[0][rows].tolist()):
                on = [w for w in rows if key[0, w] == bucket]
                want = retried_by_the_shortcut(
                    n, {w: (int(src[0, w]) - SITE_BASE) % n for w in on}, {w: w for w in on},
                    {w: (got.proposed[w], int(src[0, w]), int(seq[0, w])) for w in on},
                    {w: got.fast_path[w] for w in on})
                assert want == {w for w in on if not got.fast_path[w]}
                retried += len(want)
    assert retried > 50


def test_rounds_at_the_cells_shape_agree_with_the_reference(rounds=6, small=True):
    """The at-shape comparison (n = 7, 1,048,576 buckets, batch and pending
    4096, the cell's keys, seven sites in turn) through ``CaesarDeviceDriver``'s
    own program; under pytest a small shape on the CPU, by hand on the chip the
    cell's."""
    from fantoch_tpu.run.device_runner import CaesarDeviceDriver
    from fantoch_tpu.run.pipeline import StagedColumns

    buckets, batch = (256, 96) if small else (1048576, 4096)
    driver = CaesarDeviceDriver(7, batch_size=batch, key_buckets=buckets,
                                pending_capacity=batch, site_base=SITE_BASE)
    driver.precompile_chains([1])
    driver.register_site(3)
    reference = CaesarSitesReference(7, batch, SITE_BASE)
    rng = np.random.default_rng(2**31 + 59)
    key, src, seq = at_shape_rounds(
        rng, rounds, clients=2 * batch, batch=batch, buckets=buckets, n=7)
    slow = fast = waited = passes = 0
    for at in range(rounds):
        program, sharding, layout = driver._program()
        # the columns as a dispatch stages them: views of the one array that goes up
        staged = StagedColumns(driver._column_specs())
        for view, column in zip(staged, (key[at][:, None], src[at], seq[at])):
            view[...] = column
        driver._state, packed, _rest = program(
            driver._state, jax.device_put(staged.packed, sharding))
        out = layout.unpack(jax.device_get(packed))  # ... and the one that comes down
        want = reference.round(key[at], src[at], seq[at])
        assert_round_equal(out, want, at)
        slow += want.slow_paths
        fast += sum(want.fast_path)
        waited += want.tallies["wait_rows"]
        passes = max(passes, want.wait_passes)
    assert slow > 0 and fast > 0 and waited > slow and passes > 1 and not reference.pending
    print(f"{rounds} rounds at {buckets} buckets, batch {batch}: {fast} fast, {slow} slow, "
          f"{waited} waited, {passes} passes at most, equal on every row, "
          f"on {jax.default_backend()}")
