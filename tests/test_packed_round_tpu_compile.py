"""The packed programs of PR 54 at the cells' shapes, compiled for a v5e that
is described and not attached (no chip, nothing runs): the chip's compiler
takes them, the four-chip Tempo round keeps the collectives it had, its packed
input is split along the batch axis and its packed output is replicated.  The
topology is described inside a fixture, by the one worker that is given this
file (`/opt/skills/guides/on-chip-measurement`, section 2)."""

import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fantoch_tpu.parallel import mesh_step as ms
from fantoch_tpu.run.pipeline import packed_round, packed_shape

BATCH = PENDING = 4096
COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|collective-permute|all-to-all|reduce-scatter)(?:-start)?\(")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        described = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no compiler for the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    # such a compile is written to the persistent cache but cannot be read back without a chip
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _collectives(compiled):
    found = {}
    for match in COLLECTIVE.finditer(compiled.as_text()):
        found[match.group(1)] = found.get(match.group(1), 0) + 1
    return found


def _shaped(mesh, shape, spec, dtype=np.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))


def _packed(mesh, jitted, specs, kept, state):
    program, layout = packed_round(jitted.__wrapped__, specs, kept, NamedSharding(mesh, P()))
    packed = _shaped(mesh, packed_shape(specs), P(None, ms.BATCH_AXIS))
    return jax.jit(program, donate_argnums=(0,)).lower(state, packed).compile(), layout


def test_the_four_chip_tempo_round_keeps_its_collectives_when_packed(topo):
    """`tempo_n5_4shard_2key`: 4 shards x 5 rows on `replica:4 x batch:1`,
    4,194,304 buckets, two keys a command."""
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), (ms.REPLICA_AXIS, ms.BATCH_AXIS))
    rows, buckets, width = 20, 4194304, 2
    table = P(ms.REPLICA_AXIS, None)
    state = ms.NewtMeshState(
        _shaped(mesh, (rows, buckets), table), _shaped(mesh, (rows, buckets), table),
        _shaped(mesh, (PENDING, width), P()), _shaped(mesh, (PENDING,), P()),
        _shaped(mesh, (PENDING,), P()), _shaped(mesh, (PENDING,), P()))
    specs = (("key", (BATCH, width), np.int32, ms.KEY_PAD), ("src", (BATCH,), np.int32, 0),
             ("seq", (BATCH,), np.int32, 0))
    jitted = ms.jit_newt_step(mesh, f=1, shard_count=4)
    unpacked = jitted.lower(
        state, *(jax.ShapeDtypeStruct(shape, dtype) for _name, shape, dtype, _fill in specs)).compile()
    packed, layout = _packed(mesh, jitted, specs, (), state)
    assert _collectives(packed) == _collectives(unpacked) != {}
    assert packed.input_shardings[0][1].spec == P(None, ms.BATCH_AXIS)
    assert packed.output_shardings[1].is_fully_replicated
    assert layout.type is ms.NewtStepOutput and None not in layout.fields
    # seven rows of W and four scalars come down as one array
    assert packed.out_info[1].shape == (7 * (BATCH + PENDING) + 4,)


def test_the_one_chip_dependency_round_compiles_packed(topo):
    """`epaxos_n5_1m`: n = 5, 1,048,576 buckets, one key a command, `read`
    staged as 0/1; `deps_gid` and `pending` stay device leaves."""
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), (ms.REPLICA_AXIS, ms.BATCH_AXIS))
    n, buckets = 5, 1048576
    table = P(ms.REPLICA_AXIS, None)
    state = ms.ReplicaState(
        _shaped(mesh, (n, buckets), table), _shaped(mesh, (n,), P(ms.REPLICA_AXIS)), _shaped(mesh, (), P()),
        _shaped(mesh, (PENDING, 1), P()), _shaped(mesh, (PENDING,), P()), _shaped(mesh, (PENDING,), P()),
        _shaped(mesh, (PENDING,), P()), _shaped(mesh, (n, buckets), table),
        _shaped(mesh, (PENDING,), P(), np.bool_))
    specs = (("key", (BATCH, 1), np.int32, ms.KEY_PAD), ("src", (BATCH,), np.int32, 0),
             ("seq", (BATCH,), np.int32, 0), ("read", (BATCH,), np.bool_, False))
    packed, layout = _packed(mesh, ms.jit_protocol_step(mesh), specs, ("deps_gid", "pending"), state)
    _state, packed_out, rest = packed.out_info
    # order, resolved, fast_path, gids: four rows of W; slow_paths, stable, pend_dropped; the five tallies
    assert packed_out.shape == (4 * (BATCH + PENDING) + 3 + len(ms.ROUND_TALLIES),)
    assert rest.deps_gid.shape == (BATCH + PENDING, 2) and rest.pending.shape == () and rest.order is None
    assert [field is None for field in layout.fields] == [
        name in ("deps_gid", "pending") for name in ms.StepOutput._fields]
