"""A dispatch crosses to the device once and comes back once (PR 54): the
program a driver dispatches takes a round's staged columns as one ``int32``
array and gives what its drain reads as one (`run/pipeline.py`
`packed_round`), and it computes what the round functions of `mesh_step`
compute when called directly on the columns, every output leaf fetched (the
parent's dispatch: `_Unpacked`): the same results, tallies and device state,
round after round, in all four drivers, both site programs and Newt's
chains."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fantoch_tpu.core import Command, Dot, KVOp, Rifl
from fantoch_tpu.parallel import mesh_step
from fantoch_tpu.run.device_drivers import (
    CaesarDeviceDriver, DeviceDriver, NewtDeviceDriver, PaxosDeviceDriver, _bucket,
)
from fantoch_tpu.run.pipeline import StagedColumns, packed_columns, packed_shape

BATCH = 8


@jax.tree_util.register_pytree_node_class
class _Leaves:
    """A round's whole output tuple where a driver holds its one packed
    array: fetched leaf by leaf, and each leaf's copy back started where
    the driver starts the one (PR 58)."""

    def __init__(self, out):
        self.out = out

    def copy_to_host_async(self):
        for leaf in jax.tree_util.tree_leaves(self.out):
            leaf.copy_to_host_async()

    def tree_flatten(self):
        return (self.out,), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)


class _Fetched:
    """The layout of a dispatch that packs nothing: the output tuple is
    fetched leaf by leaf and read as it is."""

    @staticmethod
    def unpack(fetched):
        return fetched.out


class _Unpacked:
    """A driver whose dispatch is the parent's: the jitted round function
    of `mesh_step`, called directly on the staged columns as arrays of their
    own, and its whole output tuple fetched."""

    def _lowered(self, jitted, S=1, state=None):
        return (jitted, self._column_specs()), _Fetched

    @staticmethod
    def _compiled(lowered, layout):
        jitted, specs = lowered

        def program(state, staged):
            state, out = jitted(state, *(
                jnp.array(column, dtype=dtype)
                for column, (_name, _shape, dtype, _fill) in zip(staged, specs)))
            return state, _Leaves(out), out

        return program, None, layout

    def _columns_to_device(self, staged, sharding):
        return staged


def _step(protocol, d, live=None, **more):
    """The jitted round function of ``protocol`` with ``live`` replicas
    answering (None: all), as `mesh_step` makes it."""
    n = d.num_replicas if hasattr(d, "num_replicas") else CASES[protocol][1]["num_replicas"]
    return {
        "epaxos": lambda: mesh_step.jit_protocol_step(d._mesh, live_replicas=live, **more),
        "newt": lambda: mesh_step.jit_newt_step(d._mesh, f=2, live_replicas=live, **more),
        "caesar": lambda: mesh_step.jit_caesar_step(d._mesh, num_replicas=n, live_replicas=live),
        "fpaxos": lambda: mesh_step.jit_paxos_step(d._mesh, f=1, num_replicas=n, live_replicas=live),
    }[protocol]()


# name -> (driver, its arguments, sites clients are at, rounds a dispatch)
CASES = {
    "epaxos": (DeviceDriver, dict(num_replicas=5), 1, 1),
    "epaxos_sites": (DeviceDriver, dict(num_replicas=5), 5, 1),
    "newt": (NewtDeviceDriver, dict(num_replicas=5, f=2), 1, 1),
    "newt_sites": (NewtDeviceDriver, dict(num_replicas=5, f=2), 5, 1),
    "newt_chain_2": (NewtDeviceDriver, dict(num_replicas=5, f=2), 1, 2),
    "newt_chain_4": (NewtDeviceDriver, dict(num_replicas=5, f=2), 1, 4),
    "caesar": (CaesarDeviceDriver, dict(num_replicas=7), 1, 1),
    "fpaxos": (PaxosDeviceDriver, dict(num_replicas=3, f=1), 1, 1),
}


def _pair(name, pending):
    cls, kwargs, sites, _chain = CASES[name]
    kwargs = dict(kwargs, batch_size=BATCH, key_buckets=64, pending_capacity=pending,
                  monitor_execution_order=True)
    pair = cls(**kwargs), type("Unpacked" + cls.__name__, (_Unpacked, cls), {})(**kwargs)
    for driver in pair:
        for site in range(sites):
            driver.register_site(site)
    return pair


class _Feed:
    """Seeded commands, the same for both drivers of a pair: from ``sites``
    coordinators, on the hot key or a few others, a share of them reads."""

    def __init__(self, seed, sites, read_share):
        self.rng = np.random.default_rng(seed)
        self.sites, self.read_share = sites, read_share
        self.sequence = 0

    def batch(self, count, hot=0.5):
        out = []
        for _ in range(count):
            self.sequence += 1
            source = 1 + int(self.rng.integers(0, self.sites))
            key = "hot" if self.rng.random() < hot else f"k{int(self.rng.integers(0, 4))}"
            op = KVOp.get() if self.rng.random() < self.read_share else KVOp.put(f"v{self.sequence}")
            out.append((Dot(source, self.sequence),
                        Command.from_single(Rifl(source, self.sequence), 0, key, op)))
        return out


def _flat(results):
    return [(r.rifl, r.key, tuple(r.op_results)) for r in results]


def _same(real, ref):
    """Everything a drain leaves behind, and the device's state."""
    for name in ("executed", "fast_paths", "slow_paths", "stable_watermark", "rounds",
                 "requeued", "in_flight", "round_tallies", "round_gauges", "drain_rows_walked"):
        assert getattr(real, name) == getattr(ref, name), name
    assert list(real._cmds) == list(ref._cmds)
    assert [dot for dot, _ in real._requeue] == [dot for dot, _ in ref._requeue]
    assert type(real._state) is type(ref._state)
    for field, mine, theirs in zip(real._state._fields, real._state, ref._state):
        assert mine.dtype == theirs.dtype and np.array_equal(mine, theirs), field
    for key in ref.store.monitor.keys():
        assert real.store.monitor.get_order(key) == ref.store.monitor.get_order(key)


def _serve_both(real, ref, batches, overlap=False):
    got = real.serve([list(b) for b in batches], overlap=overlap)
    want = ref.serve([list(b) for b in batches], overlap=overlap)
    assert _flat(got) == _flat(want)
    _same(real, ref)
    return got


def _degrade(name, real, ref, live):
    """One round function for both, as each makes its program of it."""
    protocol = name.split("_")[0]
    sites = CASES[name][2]
    more = {"sites": sites, "site_base": 1} if sites > 1 else {}
    for driver in (real, ref):
        program = driver._precompile(_step(protocol, driver, live, **more))
        if sites > 1 and protocol == "epaxos":
            driver._site_program = program
        else:
            driver._programs[1] = program


@pytest.mark.parametrize("name", CASES)
def test_the_packed_dispatch_is_the_round_functions_called_directly(name):
    """Seeded rounds through a driver and through its unpacked twin: a
    part-full round, full ones, rounds under the quorum that carry and then
    overflow the pending buffer (a requeue), the requeue served after
    healing; with several sites, reads among the writes (rows the host's
    Tarjan finishes).  After every dispatch: the same `ExecutorResult`s,
    tallies, watermark, paths, registry, requeue and device state, and two
    transfers a dispatch (a third where `finish` rows' dependencies were
    fetched)."""
    protocol = name.split("_")[0]
    _cls, _kwargs, sites, chain = CASES[name]
    real, ref = _pair(name, pending=12)
    feed = _Feed(seed=3 + sites, sites=sites, read_share=0.4 if sites > 1 else 0.0)

    def rounds(*counts, **kw):
        """One serve call: a dispatch a round, or one of the whole chain."""
        before = real.dispatches, real.transfers, real.stages.n["finish"]
        got = _serve_both(real, ref, [feed.batch(count, **kw) for count in counts])
        dispatches = real.dispatches - before[0]
        assert dispatches == (1 if chain > 1 and len(counts) == chain else len(counts))
        assert real.transfers - before[1] == 2 * dispatches + real.stages.n["finish"] - before[2]
        return got

    if protocol == "epaxos" and sites == 1:
        _degrade(name, real, ref, live=1)  # only replica 0 learns the seed: what follows splits the quorum
    assert len(rounds(3, hot=1.0)) == 3  # a part-full round
    if chain > 1:
        rounds(*[BATCH] * chain)  # full rounds, one dispatch
        rounds(*[5] * chain)
        assert real.executed == 3 + 13 * chain and real.dispatches == 3
    else:
        for _ in range(3):
            rounds(BATCH)  # full ones
    if protocol == "caesar":
        # stagger replica 0's ceiling on the hot bucket: proposals diverge
        for d in (real, ref):
            kc = np.array(d._state.key_clock)
            kc[0, _bucket(0, "hot", 64, 1)] += 7
            d._state = d._state._replace(key_clock=jax.device_put(
                jnp.array(kc), d._state.key_clock.sharding))
    _degrade(name, real, ref, live=1)
    carried = 0
    for _ in range(4):
        rounds(BATCH, hot=1.0)
        carried = max(carried, real.in_flight)
    assert carried >= BATCH  # a pending carry
    assert real.requeued > 0 and real.has_requeue  # an overflow's requeue
    _degrade(name, real, ref, live=None)
    for _ in range(8):
        mine, theirs = real.take_requeue(), ref.take_requeue()
        assert [dot for dot, _ in mine] == [dot for dot, _ in theirs]
        for at in range(0, len(mine), BATCH):
            assert _flat(real.step(mine[at:at + BATCH])) == _flat(ref.step(theirs[at:at + BATCH]))
            _same(real, ref)
    assert real.in_flight == 0 and not real.has_requeue
    assert real.executed == feed.sequence
    if sites > 1 and protocol == "epaxos":  # the case is what it says
        assert real.round_tallies["finisher_rows"] > 0 and real.stages.n["finish"] > 0
    # the pipelined drain reads a round's own packed output, rounds later
    real.pipeline_depth = ref.pipeline_depth = 2
    for count in (BATCH, 2, BATCH):
        _serve_both(real, ref, [feed.batch(count)], overlap=True)
    assert _flat(real.flush_pipeline()) == _flat(ref.flush_pipeline())
    _same(real, ref)
    assert real.in_flight == 0
    # what a drain does not read stays on the device: the program hands it over un-fetched
    program, _sharding, layout = real._program(1)
    unfetched = [field for field, at in zip(layout.type._fields, layout.fields) if at is None]
    assert unfetched == (["deps_gid", "pending"] if protocol == "epaxos" else [])
    counters = real.device_counters()
    assert counters["device_transfers"] == real.transfers >= 2 * counters["device_dispatches"]


def test_a_slots_columns_alias_one_buffer_the_device_is_handed():
    """The columns `_column_specs` names are views of a slot's one
    C-contiguous `int32` buffer, a `bool` column as 0/1, in every driver;
    `_columns_to_device` hands jax that buffer and nothing else."""
    for name in ("epaxos", "newt", "caesar", "fpaxos"):
        real, _ref = _pair(name, pending=4)
        specs = real._column_specs()
        staged = real._staging(*specs)
        assert isinstance(staged, StagedColumns) and len(staged) == len(specs)
        packed = staged.packed
        assert packed.dtype == np.int32 and packed.flags.c_contiguous
        assert packed.shape == packed_shape(specs) == (len(specs), BATCH)
        for column, view, (_name, shape, _dtype, fill) in zip(staged, packed_columns(packed, specs), specs):
            assert column.shape == shape and column.dtype == np.int32
            assert np.shares_memory(column, packed) and np.array_equal(column, view)
            assert (column == fill).all()
        handed = []
        put = jax.device_put
        try:
            jax.device_put = lambda x, sharding: handed.append(x) or put(x, sharding)
            real.step(_Feed(1, 1, 0.5).batch(3))
        finally:
            jax.device_put = put
        assert len(handed) == 1 and handed[0].shape == packed.shape and handed[0].dtype == np.int32
        assert real.transfers == 2 and real.dispatches == 1


def test_two_keys_a_command_are_two_rows_of_the_buffer():
    specs = (("key", (BATCH, 2), np.int32, -1), ("src", (BATCH,), np.int32, 0), ("read", (BATCH,), np.bool_, False))
    staged = StagedColumns(specs)
    key, src, read = staged
    assert staged.packed.shape == packed_shape(specs) == (4, BATCH) and key.shape == (BATCH, 2)
    key[3] = (5, 9)
    src[3], read[3] = 7, True
    assert staged.packed[:, 3].tolist() == [5, 9, 7, 1]
    chain = StagedColumns(specs, lead=(3,))
    assert chain.packed.shape == (3, 4, BATCH) and chain[0].shape == (3, BATCH, 2)
    assert (chain[0] == -1).all() and not chain[1].any() and not chain[2].any()
    chain[0][2, 1] = (4, 6)
    assert chain.packed[2, :2, 1].tolist() == [4, 6]


def test_a_slot_is_not_rewritten_while_its_round_is_in_flight_at_depth_1():
    """At depth 1 the ring has two slots: when a round has been assembled
    and goes up, the round before it is still in flight, and the buffer that
    one was handed is another and holds what it held."""
    real, _ref = _pair("newt", pending=4)
    real.pipeline_depth = 1
    feed = _Feed(3, 1, 0.0)
    handed = []
    to_device = real._columns_to_device

    def watching(staged, sharding):
        assert real._undrained == len(handed[-1:])  # the round before: dispatched, not drained
        for buffer, as_handed in handed[-1:]:
            assert buffer is not staged.packed and np.array_equal(buffer, as_handed)
        if len(handed) >= 2:
            assert handed[-2][0] is staged.packed  # the ring came round: that round has drained
        handed.append((staged.packed, staged.packed.copy()))
        return to_device(staged, sharding)

    real._columns_to_device = watching
    executed = 0
    for _ in range(6):
        executed += len(real.serve([feed.batch(5)], overlap=True))
        assert real.has_outstanding and real._ring.slots == 2
    executed += len(real.flush_pipeline())
    assert executed == 30 and len(handed) == 6
    assert len({id(buffer) for buffer, _ in handed}) == 2
