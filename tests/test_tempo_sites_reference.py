"""The device Newt round with a coordinator at every site
(``mesh_step.newt_protocol_step(sites=n)``) against the plain reference of the
mechanism (``tests/tempo_sites_reference.py``): seeded random rounds at a small
size on the CPU, n = 3, 5, 7 at f = 1 and 2, a hot key and zipf keys, one to
five sites, every replica live, one short of the fast quorum, under the write
quorum, several rounds in a row so that carried rows and learnt clocks are
exercised, through the round and through the chained program.  Clocks, commit
and fast-path flags, what executed and in which order, what is carried, the
tallies and the tables at the end: equal, exactly.  And the at-shape case the
chip runs (``chiprun -- python3 -c "from tests.test_tempo_sites_reference import
...``; the verify skill has the line)."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from fantoch_tpu.parallel import mesh_step
from tests import newt_reference
from tests.tempo_sites_reference import PAD, TempoSitesReference, quorum_sizes, ring

BUCKETS, CAPACITY, BATCH, SITE_BASE = 64, 24, 40, 1


def forced_mesh(replica=1, batch=1):
    devices = np.array(jax.devices()[: replica * batch]).reshape(replica, batch)
    return Mesh(devices, (mesh_step.REPLICA_AXIS, mesh_step.BATCH_AXIS))


def sites_in_turn(sites, count):
    """The sites of a batch of ``count`` commands, taken in turn."""
    return np.arange(count) % sites


def batches(rng, rounds, n, sites, keys="hot", batch=BATCH, buckets=BUCKETS):
    """Seeded rounds of commands: ``hot``, half of them on bucket 0 and the
    rest anywhere; ``zipf``, a zipf-1.0 draw over the buckets; a seventh of
    the rows empty.  A command's source is its site's process, its sequence
    the site's own."""
    key = np.full((rounds, batch), PAD, np.int32)
    src = np.zeros((rounds, batch), np.int32)
    seq = np.zeros((rounds, batch), np.int32)
    weights = 1.0 / np.arange(1, buckets + 1)
    next_seq = [1] * n
    for r in range(rounds):
        for row, site in enumerate(sites_in_turn(sites, batch)):
            if rng.random() < 0.15:
                continue
            if keys == "hot":
                key[r, row] = 0 if rng.random() < 0.5 else rng.integers(buckets)
            else:
                key[r, row] = rng.choice(buckets, p=weights / weights.sum())
            src[r, row], seq[r, row] = SITE_BASE + site, next_seq[site]
            next_seq[site] += 1
    return key, src, seq


def assert_round_equal(out, want, at):
    executed = np.asarray(out.executed)
    for name, got, expected in (
        ("clock", out.clock, want.clock), ("committed", out.committed, want.committed),
        ("fast_path", out.fast_path, want.fast_path), ("executed", executed, want.executed),
        ("order", np.asarray(out.order)[: int(executed.sum())], want.order),
        ("slow_paths", out.slow_paths, want.slow_paths), ("pending", out.pending, want.pending),
        ("pend_dropped", out.pend_dropped, want.dropped),
        ("stable_watermark", out.stable_watermark, want.watermark),
        ("tallies", out.tallies,
         [want.tallies[name] for name in mesh_step.NEWT_SITE_ROUND_TALLIES]),
    ):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(expected),
                                      err_msg=f"{name}, round {at}")


def assert_state_equal(state, reference, buckets=BUCKETS):
    for table, learnt in ((state.key_clock, reference.clock), (state.vote_frontier, reference.votes)):
        table = np.asarray(table)
        for r in range(reference.n):
            want = np.zeros(buckets, np.int64)
            for key, clock in learnt[r].items():
                want[key] = clock
            np.testing.assert_array_equal(table[r], want)
    carried = [(k[0], s, q, c) for k, s, q, c in zip(
        np.asarray(state.pend_key).tolist(), np.asarray(state.pend_src).tolist(),
        np.asarray(state.pend_seq).tolist(), np.asarray(state.pend_clock).tolist())
        if k[0] != PAD]
    assert carried == [tuple(cmd) for cmd in reference.pending]


def run_against_reference(n, f, sites, seed, keys="hot", length=1, dispatches=4,
                          live_replicas=None, mesh=None, capacity=CAPACITY):
    mesh = forced_mesh() if mesh is None else mesh
    state = mesh_step.init_newt_state(mesh, n, key_buckets=BUCKETS, pending_capacity=capacity)
    kwargs = dict(f=f, live_replicas=live_replicas, sites=n, site_base=SITE_BASE)
    program = (mesh_step.jit_newt_step if length == 1 else mesh_step.jit_newt_multi_step)(
        mesh, **kwargs)
    reference = TempoSitesReference(n, f, capacity, SITE_BASE, live_replicas)
    rng = np.random.default_rng([seed, n, f, sites, length])
    seen = {"executed": 0, "slow": 0, "fast": 0, "dropped": 0, "carried": 0, "proposed_anew": 0,
            **dict.fromkeys(mesh_step.NEWT_SITE_ROUND_TALLIES, 0)}
    for dispatch in range(dispatches):
        key, src, seq = batches(rng, length, n, sites, keys)
        if length == 1:
            state, out = program(state, key[0], src[0], seq[0])
            outs = [out]
        else:
            state, stacked = program(state, key, src, seq)
            stacked = jax.device_get(stacked)
            outs = [type(stacked)(*(column[r] for column in stacked)) for r in range(length)]
        for r, out in enumerate(outs):
            assert isinstance(out, mesh_step.NewtSiteStepOutput)
            want = reference.round(key[r], src[r], seq[r])
            assert_round_equal(out, want, dispatch * length + r)
            seen["executed"] += len(want.order)
            seen["slow"] += want.slow_paths
            seen["fast"] += sum(want.fast_path)
            seen["dropped"] += want.dropped
            seen["carried"] += want.pending
            seen["proposed_anew"] += sum(cmd.clock < 0 for cmd in reference.pending)
            for name, count in want.tallies.items():
                seen[name] += count
    assert_state_equal(state, reference)
    return seen, reference


@pytest.mark.parametrize("keys", ("hot", "zipf"))
@pytest.mark.parametrize("sites", (1, 2, 3, 5))
@pytest.mark.parametrize("n, f", [(3, 1), (5, 1), (5, 2), (7, 1), (7, 2)])
def test_the_round_equals_the_reference_with_every_replica_live(n, f, sites, keys):
    seen, reference = run_against_reference(n, f, min(sites, n), seed=53 + sites, keys=keys)
    sites = min(sites, n)
    assert seen["executed"] > 100 and not reference.pending  # all live: nothing is held
    if f == 1:  # the highest proposal is always reported by one
        assert seen["slow"] == 0
    if sites == 1:  # one view: the coordinator's proposal is every member's
        assert seen["site_clock_spread"] == seen["arrival_reordered"] == seen["slow"] == 0
    elif keys == "hot":
        assert seen["site_clock_spread"] > 0


@pytest.mark.parametrize("length", (2, 4))
@pytest.mark.parametrize("n, f", [(5, 1), (5, 2)])
def test_every_round_of_a_chain_equals_the_reference(n, f, length):
    seen, reference = run_against_reference(n, f, 5, seed=7, length=length, dispatches=3)
    assert seen["executed"] > 50 * length and not reference.pending


def test_at_f_1_no_row_is_slow_and_at_f_2_under_a_hot_key_some_are_and_some_are_not():
    one, _ = run_against_reference(5, 1, 5, seed=11, dispatches=6)
    assert one["slow"] == 0 and one["fast"] == one["executed"] > 0
    two, _ = run_against_reference(5, 2, 5, seed=11, dispatches=6)
    assert two["slow"] > 10 and two["fast"] > 10
    assert two["slow"] + two["fast"] == two["executed"]
    # five views of a hot key: the rings' maxima lie above their minima, two
    # coordinators' commands take one clock, and the order out is not the order in
    assert two["site_clock_spread"] > 0 and two["clock_ties"] > 0
    assert two["arrival_reordered"] > 0


@pytest.mark.parametrize("n, f, live, held", [
    (5, 2, 3, "stable"),  # one short of the fast quorum of four: three votes make a key stable
    (5, 1, 2, "unstable"),  # the write quorum of two commits, two votes of five hold every key
    (5, 2, 2, "uncommitted"),  # under the write quorum of three: a slow row stays uncommitted
    (7, 2, 4, "stable"), (7, 2, 2, "uncommitted"), (3, 1, 1, "unstable"),
])
@pytest.mark.parametrize("length", (1, 2))
def test_the_round_equals_the_reference_with_replicas_lagging(n, f, live, held, length):
    # (a buffer that holds what does not commit: the committed are carried first)
    capacity = 6 * BATCH if held == "uncommitted" else CAPACITY
    seen, reference = run_against_reference(
        n, f, min(n, 5), seed=29, length=length, dispatches=6 // length, live_replicas=live,
        capacity=capacity)
    if held == "stable":
        assert seen["executed"] > 100 and not reference.pending
    if held == "unstable":
        assert seen["carried"] > 0 and seen["dropped"] > 0
        assert len(reference.pending) == CAPACITY
        assert all(cmd.clock >= 0 for cmd in reference.pending)
    if held == "uncommitted":  # ... and is proposed again, with its site's commands
        assert seen["slow"] > 0 and seen["proposed_anew"] > 50 and seen["dropped"] == 0
        assert any(cmd.clock < 0 for cmd in reference.pending)


def test_the_round_equals_the_reference_with_a_replica_a_device():
    """Five replica rows over five devices along ``replica``: the clocks at
    the round's keys are gathered along the axis, the proposals are every
    device's own copy, and each scatters into the rows it holds."""
    seen, reference = run_against_reference(5, 2, 5, seed=3, mesh=forced_mesh(5, 1))
    assert seen["executed"] > 100 and seen["slow"] > 0
    seen, reference = run_against_reference(5, 2, 5, seed=3, length=2, dispatches=2,
                                            mesh=forced_mesh(1, 4), live_replicas=2)
    assert seen["dropped"] > 0


def test_one_site_is_the_parents_round():
    """``sites == 1`` traces the round as it was: the jitted program is built
    without the two arguments, its output is ``NewtStepOutput``, and it is the
    one-coordinator reference's round (``tests/newt_reference.py``), which the
    program with a coordinator at every site is not: there replica 0 alone
    coordinates whatever the dot says, and the quorum is the first rows."""
    mesh = forced_mesh()
    program = mesh_step.jit_newt_step(mesh, f=2)
    assert set(program.__wrapped__.keywords) == {
        "mesh", "f", "tiny_quorums", "live_replicas", "shard_count"}
    assert "sites" in mesh_step.jit_newt_step(mesh, f=2, sites=5).__wrapped__.keywords
    chained = mesh_step.jit_newt_multi_step(mesh, f=2)
    assert "sites" not in chained.__wrapped__.keywords
    state = mesh_step.init_newt_state(mesh, 5, key_buckets=BUCKETS, pending_capacity=CAPACITY)
    reference = newt_reference.NewtReference(5, 2, 1, BUCKETS, CAPACITY, 1)
    rng = np.random.default_rng(53)
    for at in range(4):
        key, src, seq = batches(rng, 1, 5, 5)
        state, out = program(state, key[0], src[0], seq[0])
        assert type(out) is mesh_step.NewtStepOutput
        want = reference.round(key[0], src[0], seq[0])
        for name in ("clock", "committed", "fast_path", "executed"):
            np.testing.assert_array_equal(np.asarray(getattr(out, name)), getattr(want, name))
        assert int(out.slow_paths) == want.slow_paths == 0  # one view: no row is slow
    with pytest.raises(AssertionError, match="one key a command, one shard"):
        mesh_step.newt_protocol_step(
            mesh_step.init_newt_state(mesh, 10, key_buckets=BUCKETS, pending_capacity=8),
            key[0], src[0], seq[0], mesh=mesh, shard_count=2, sites=5)
    with pytest.raises(AssertionError, match="a site a replica"):
        mesh_step.newt_protocol_step(state, key[0], src[0], seq[0], mesh=mesh, sites=3)


def test_the_reference_by_hand_two_sites_one_key():
    """n = 5, f = 2 (fast quorum 4): sites 0 and 2 each submit one command on
    key 9 to a fresh store.  Both coordinators propose 1.  Replicas 1 and 3
    have neither site's command first: replica 1, in site 0's ring alone,
    proposes 1 for it; replica 3, in both rings, proposes 1 for site 0's (it
    stands first) and then 2 for site 2's; replica 2 has its own first and
    proposes 2 for site 0's; replica 0 has its own first and proposes 2 for
    site 2's.  Site 0's command: {0: 1, 1: 1, 2: 2, 3: 1}, highest 2 reported
    once: slow.  Site 2's: {2: 1, 3: 2, 4: 1, 0: 2}, highest 2 reported twice:
    fast.  Both commit at 2; the dot orders them."""
    assert quorum_sizes(5, 2) == (4, 3, 3) and ring(2, 5, 4) == [2, 3, 4, 0]
    reference = TempoSitesReference(5, 2, 4, SITE_BASE)
    got = reference.round([9, 9], [SITE_BASE + 0, SITE_BASE + 2], [1, 1])
    assert got.proposals == {4: {0: 1, 1: 1, 2: 2, 3: 1}, 5: {2: 1, 3: 2, 4: 1, 0: 2}}
    assert got.clock[4:] == [2, 2] and got.fast_path[4:] == [False, True]
    assert got.committed[4:] == got.executed[4:] == [True, True] and got.order == [4, 5]
    assert got.slow_paths == 1
    assert got.tallies == {"site_clock_spread": 2, "clock_ties": 2, "arrival_reordered": 0}
    assert all(reference.clock[r] == {9: 2} for r in range(5))


def at_shape_rounds(rng, rounds, clients=8192, batch=4096, buckets=1048576, n=5):
    """Rounds of the cell's mix: ``clients`` closed-loop clients, an equal
    share a site, key 0 at 50% else the client's own bucket, each round the
    next ``batch`` clients in turn with their sites taken in turn."""
    key = np.full((rounds, batch), PAD, np.int32)
    src = np.zeros((rounds, batch), np.int32)
    seq = np.zeros((rounds, batch), np.int32)
    next_seq = [1] * n
    client = 0
    for r in range(rounds):
        for row in range(batch):
            site = client % n
            own = (client * 2654435761) % buckets
            key[r, row] = 0 if rng.random() < 0.5 else own
            src[r, row], seq[r, row] = SITE_BASE + site, next_seq[site]
            next_seq[site] += 1
            client = (client + 1) % clients
    return key, src, seq


def test_rounds_at_the_cells_shape_agree_with_the_reference(rounds=6, small=True):
    """The at-shape comparison (n = 5, f = 2, 1,048,576 buckets, batch and
    pending 4096, the cell's keys, five sites in turn) through
    ``NewtDeviceDriver``'s own programs, the round and a chain of two; under
    pytest a small shape on the CPU, by hand on the chip the cell's."""
    from fantoch_tpu.run.device_runner import NewtDeviceDriver
    from fantoch_tpu.run.pipeline import StagedColumns

    buckets, batch = (256, 64) if small else (1048576, 4096)
    driver = NewtDeviceDriver(5, f=2, batch_size=batch, key_buckets=buckets,
                              pending_capacity=batch, site_base=SITE_BASE)
    driver.precompile_chains([1, 2])
    driver.register_site(3)
    reference = TempoSitesReference(5, 2, batch, SITE_BASE)
    rng = np.random.default_rng(2**31 + 53)
    key, src, seq = at_shape_rounds(rng, rounds, clients=2 * batch, batch=batch, buckets=buckets)
    slow = fast = reordered = 0
    for first in range(0, rounds, 3):  # a round, then a chain of two
        for length in (1, 2):
            at = first + (length == 2)
            program, sharding, layout = driver._program(length)
            columns = (key[at][:, None], src[at], seq[at]) if length == 1 else (
                key[at:at + 2, :, None], src[at:at + 2], seq[at:at + 2])
            # the columns as a dispatch stages them: views of the one array that goes up
            staged = StagedColumns(driver._column_specs(), lead=() if length == 1 else (2,))
            for view, column in zip(staged, columns):
                view[...] = column
            driver._state, packed, _rest = program(
                driver._state, jax.device_put(staged.packed, sharding))
            out = layout.unpack(jax.device_get(packed))  # ... and the one that comes down
            outs = [out] if length == 1 else [
                type(out)(*(column[r] for column in out)) for r in range(2)]
            for r, one in enumerate(outs):
                want = reference.round(key[at + r], src[at + r], seq[at + r])
                assert_round_equal(one, want, at + r)
                slow += want.slow_paths
                fast += sum(want.fast_path)
                reordered += want.tallies["arrival_reordered"]
    assert slow > 0 and fast > 0 and reordered > 0 and not reference.pending
    print(f"{rounds} rounds at {buckets} buckets, batch {batch}: {fast} fast, {slow} slow, "
          f"{reordered} reordered, equal on every row, on {jax.default_backend()}")
