"""TPU serving path tests: the device protocol step behind a real TCP
client plane (run/device_runner.py), plus direct DeviceDriver rounds.

The serving architecture being validated is the device-step analog of the
reference's runner (fantoch/src/run/mod.rs:105-445): client sessions feed
an array commit buffer, one jit-compiled protocol round orders the batch
for every replica at once, and execution results drain back through
AggregatePending to the sessions.
"""

import asyncio

import jax
import numpy as np
import pytest

# jaxlib 0.4.x CPU segfaults *flakily* while tracing the device drivers'
# scan bodies (C-stack overflow in _scan tracing) — a crash mid-suite
# aborts the whole pytest run, so on that pin this module is skipped
# outright rather than allowed to take the suite down with it
if tuple(int(x) for x in jax.__version__.split(".")[:2]) < (0, 5):
    pytest.skip(
        "jax<0.5: device-driver scan tracing segfaults flakily on this "
        "jaxlib; run the device suite on the jax>=0.5 pin",
        allow_module_level=True,
    )

from fantoch_tpu.client import ConflictRateKeyGen, Workload
from fantoch_tpu.core import Command, Config, Dot, KVOp, Rifl
from fantoch_tpu.run import device_runner as dr
from fantoch_tpu.run.device_runner import DeviceDriver
from fantoch_tpu.run.harness import run_device_server

COMMANDS_PER_CLIENT = 10


def _driver(n=3, **kw):
    kw.setdefault("batch_size", 16)
    kw.setdefault("key_buckets", 64)
    kw.setdefault("monitor_execution_order", True)
    return DeviceDriver(n, **kw)


def test_driver_hot_key_chain():
    """All commands on one key execute in dependency order: every PUT
    returns the previous PUT's value — across rounds too (the key clock
    carries the last executed gid between batches)."""
    d = _driver()
    batch = [
        (Dot(1, i + 1), Command.from_single(Rifl(1, i + 1), 0, "hot", KVOp.put(str(i))))
        for i in range(10)
    ]
    results = d.step(batch)
    assert [r.op_results[0] for r in results] == [None] + [str(i) for i in range(9)]
    assert d.executed == 10
    assert d.fast_paths == 10  # identical replica views: all fast path
    assert d.in_flight == 0

    # next round chains on the device-resident key clock
    (r,) = d.step(
        [(Dot(1, 11), Command.from_single(Rifl(1, 11), 0, "hot", KVOp.put("x")))]
    )
    assert r.op_results[0] == "9"


def test_driver_multi_key_commands():
    """key_width=2 commands route through the general on-mesh resolver and
    still execute with per-key chains intact."""
    d = _driver(key_width=2)
    # two interleaved chains on keys a/b plus commands touching both
    cmds = []
    for i in range(6):
        keys = {"a": (KVOp.put(f"a{i}"),)} if i % 2 else {
            "a": (KVOp.put(f"a{i}"),),
            "b": (KVOp.put(f"b{i}"),),
        }
        cmds.append((Dot(1, i + 1), Command.from_keys(Rifl(1, i + 1), 0, keys)))
    results = d.step(cmds)
    assert d.executed == 6
    by_key = {}
    for r in results:
        by_key.setdefault(r.key, []).append(r.op_results[0])
    # per-key previous-value chains are consistent
    assert by_key["a"] == [None, "a0", "a1", "a2", "a3", "a4"]
    assert by_key["b"] == [None, "b0", "b2"]


def test_driver_batch_padding_rounds():
    """Short batches pad to the compiled batch size; pad rows execute as
    no-ops and never surface as results."""
    d = _driver(batch_size=32)
    for i in range(5):
        results = d.step(
            [(Dot(1, i + 1), Command.from_single(Rifl(1, i + 1), 0, "k", KVOp.put(str(i))))]
        )
        assert len(results) == 1
    assert d.executed == 5
    assert d.rounds == 5


def test_device_runtime_tcp_serving():
    """Real TCP clients against the device-step server: every client
    finishes its closed-loop workload and every executed command is
    recorded exactly once per key by the execution monitor."""
    config = Config(3, 1, shard_count=1)
    workload = Workload(
        shard_count=1,
        key_gen=ConflictRateKeyGen(50),
        keys_per_command=1,
        commands_per_client=COMMANDS_PER_CLIENT,
        payload_size=1,
    )
    runtime, clients = asyncio.run(
        run_device_server(config, workload, client_count=4, batch_size=32)
    )
    assert len(clients) == 4
    for client in clients.values():
        assert client.issued_commands == COMMANDS_PER_CLIENT
        assert len(list(client.data().latency_data())) == COMMANDS_PER_CLIENT

    driver = runtime.driver
    assert driver.executed == 4 * COMMANDS_PER_CLIENT
    assert driver.in_flight == 0
    # the monitor saw every rifl exactly once across its keys
    monitor = driver.store.monitor
    seen = [
        rifl for key in monitor.keys() for rifl in monitor.get_order(key)
    ]
    assert len(seen) == len(set(seen)) and len(seen) == 4 * COMMANDS_PER_CLIENT
    # the protocol took real paths (tallies are self-evidencing)
    assert driver.fast_paths + driver.slow_paths >= driver.executed


@pytest.mark.overload
def test_device_runtime_bounded_submit_ring_sheds_and_serves():
    """Overload plane at the device serving edge (run/pipeline.py
    BoundedSubmitRing): an open-loop Poisson burst into a tiny admission
    bound sheds with typed Overloaded replies, backoff-retrying clients
    still complete everything, and the ring's depth high-watermark never
    passes its capacity."""
    config = Config(
        3, 1, shard_count=1, admission_limit=4, overload_retry_after_ms=5,
    )
    workload = Workload(
        shard_count=1,
        key_gen=ConflictRateKeyGen(50),
        keys_per_command=1,
        commands_per_client=COMMANDS_PER_CLIENT,
        payload_size=1,
    )
    runtime, clients = asyncio.run(
        run_device_server(
            config, workload, client_count=4, batch_size=8,
            arrival_rate_per_s=500.0, arrival_seed=1,
        )
    )
    for client in clients.values():
        assert len(list(client.data().latency_data())) == COMMANDS_PER_CLIENT
        assert client.shed_commands == 0  # no deadline: retries finish it
    ring = runtime._submit_queue
    assert ring.depth_hwm <= 4
    assert ring.sheds > 0, "the burst must trip the submit-ring bound"
    assert sum(c.overload_retries for c in clients.values()) >= ring.sheds
    # the overload gauges ride the serving tallies
    assert runtime._tallies["queue_capacity"] == 4
    assert runtime._tallies["shed_submissions"] == ring.sheds
    assert runtime.driver.executed == 4 * COMMANDS_PER_CLIENT


def test_device_runtime_multi_key_tcp():
    """keys_per_command=2 over TCP: the general resolver serves."""
    config = Config(3, 1, shard_count=1)
    workload = Workload(
        shard_count=1,
        key_gen=ConflictRateKeyGen(50),
        keys_per_command=2,
        commands_per_client=5,
        payload_size=1,
    )
    runtime, clients = asyncio.run(
        run_device_server(
            config, workload, client_count=2, batch_size=16, key_width=2
        )
    )
    for client in clients.values():
        assert client.issued_commands == 5
    assert runtime.driver.executed == 10
    assert runtime.driver.in_flight == 0
    assert runtime.backend_report()["resolver"] == "general"


def test_device_runtime_zipf_workload_tcp():
    """The zipf key generator end to end over TCP (the reference's other
    key-gen family; conflict-rate covers the rest of the suite)."""
    from fantoch_tpu.client.key_gen import ZipfKeyGen

    config = Config(3, 1, shard_count=1)
    workload = Workload(
        shard_count=1,
        key_gen=ZipfKeyGen(coefficient=1.0, keys_per_shard=64),
        keys_per_command=1,
        commands_per_client=COMMANDS_PER_CLIENT,
        payload_size=1,
    )
    runtime, clients = asyncio.run(
        run_device_server(config, workload, client_count=3, batch_size=16)
    )
    for client in clients.values():
        assert client.issued_commands == COMMANDS_PER_CLIENT
    driver = runtime.driver
    assert driver.executed == 3 * COMMANDS_PER_CLIENT
    assert driver.in_flight == 0
    monitor = driver.store.monitor
    # zipf keys are numeric ranks within keys_per_shard
    assert all(1 <= int(k) <= 64 for k in monitor.keys())
    assert runtime.backend_report()["resolver"] == "run_position"


def test_device_runtime_read_mix_tcp():
    """Mixed read/write workload through the device plane: the device
    round orders read-only commands conservatively (by conflict key,
    like writes — the _LatestRW read optimization is a host-KeyDeps
    refinement, not a device-plane one), and gets execute against the
    KVStore through the serving path without wedging any client."""
    config = Config(3, 1, shard_count=1)
    workload = Workload(
        shard_count=1,
        key_gen=ConflictRateKeyGen(100),  # every command on the hot key
        keys_per_command=1,
        commands_per_client=COMMANDS_PER_CLIENT,
        payload_size=4,
        read_only_percentage=50,
    )
    runtime, clients = asyncio.run(
        run_device_server(config, workload, client_count=3, batch_size=16)
    )
    for client in clients.values():
        assert client.issued_commands == COMMANDS_PER_CLIENT
    driver = runtime.driver
    assert driver.executed == 3 * COMMANDS_PER_CLIENT
    assert driver.in_flight == 0
    monitor = driver.store.monitor
    order = monitor.get_order("CONFLICT")  # the hot key (key_gen.py:18)
    assert len(order) == len(set(order)) == 3 * COMMANDS_PER_CLIENT
    assert runtime.failure is None


def test_newt_driver_hot_key_chain():
    """The Newt device driver orders a hot key by (clock, dot) and the
    key clock carries across rounds (second protocol family served)."""
    from fantoch_tpu.run.device_runner import NewtDeviceDriver

    d = NewtDeviceDriver(3, batch_size=16, key_buckets=64,
                         monitor_execution_order=True)
    batch = [
        (Dot(1, i + 1), Command.from_single(Rifl(1, i + 1), 0, "hot", KVOp.put(str(i))))
        for i in range(10)
    ]
    results = d.step(batch)
    assert [r.op_results[0] for r in results] == [None] + [str(i) for i in range(9)]
    assert d.executed == 10 and d.in_flight == 0
    assert d.fast_paths == 10  # identical replica clocks: all fast
    (r,) = d.step(
        [(Dot(1, 11), Command.from_single(Rifl(1, 11), 0, "hot", KVOp.put("x")))]
    )
    assert r.op_results[0] == "9"


def test_device_runtime_newt_tcp_serving():
    """Real TCP clients served through the Newt timestamp round."""
    config = Config(3, 1, shard_count=1)
    workload = Workload(
        shard_count=1,
        key_gen=ConflictRateKeyGen(50),
        keys_per_command=1,
        commands_per_client=COMMANDS_PER_CLIENT,
        payload_size=1,
    )
    runtime, clients = asyncio.run(
        run_device_server(
            config, workload, client_count=4, batch_size=32, protocol="newt"
        )
    )
    assert len(clients) == 4
    for client in clients.values():
        assert client.issued_commands == COMMANDS_PER_CLIENT
    assert runtime.driver.executed == 4 * COMMANDS_PER_CLIENT
    assert runtime.driver.in_flight == 0


def test_newt_driver_multi_key():
    """Multi-key commands through the Newt device driver: per-key
    previous-value chains stay consistent (a command executes only once
    stable on every key)."""
    from fantoch_tpu.run.device_runner import NewtDeviceDriver

    d = NewtDeviceDriver(3, batch_size=16, key_buckets=64, key_width=2,
                         monitor_execution_order=True)
    cmds = []
    for i in range(6):
        keys = {"a": (KVOp.put(f"a{i}"),)} if i % 2 else {
            "a": (KVOp.put(f"a{i}"),),
            "b": (KVOp.put(f"b{i}"),),
        }
        cmds.append((Dot(1, i + 1), Command.from_keys(Rifl(1, i + 1), 0, keys)))
    results = d.step(cmds)
    assert d.executed == 6 and d.in_flight == 0
    by_key = {}
    for r in results:
        by_key.setdefault(r.key, []).append(r.op_results[0])
    assert by_key["a"] == [None, "a0", "a1", "a2", "a3", "a4"]
    assert by_key["b"] == [None, "b0", "b2"]


def test_caesar_driver_hot_key_chain():
    """The Caesar device driver orders a hot key by timestamp and the
    clock index carries across rounds (the fourth consensus shape
    served; caesar.rs:216-451)."""
    from fantoch_tpu.run.device_runner import CaesarDeviceDriver

    d = CaesarDeviceDriver(3, batch_size=16, key_buckets=64,
                           monitor_execution_order=True)
    batch = [
        (Dot(1, i + 1), Command.from_single(Rifl(1, i + 1), 0, "hot", KVOp.put(str(i))))
        for i in range(10)
    ]
    results = d.step(batch)
    assert [r.op_results[0] for r in results] == [None] + [str(i) for i in range(9)]
    assert d.executed == 10 and d.in_flight == 0
    assert d.fast_paths == 10  # consistent clock views: all fast
    (r,) = d.step(
        [(Dot(1, 11), Command.from_single(Rifl(1, 11), 0, "hot", KVOp.put("x")))]
    )
    assert r.op_results[0] == "9"


def test_caesar_driver_multi_key():
    """Multi-key commands through the Caesar device driver: per-key
    previous-value chains stay consistent (timestamp order is global, so
    a multi-key command holds one position on every key it touches)."""
    from fantoch_tpu.run.device_runner import CaesarDeviceDriver

    d = CaesarDeviceDriver(3, batch_size=16, key_buckets=64, key_width=2,
                           monitor_execution_order=True)
    cmds = []
    for i in range(6):
        keys = {"a": (KVOp.put(f"a{i}"),)} if i % 2 else {
            "a": (KVOp.put(f"a{i}"),),
            "b": (KVOp.put(f"b{i}"),),
        }
        cmds.append((Dot(1, i + 1), Command.from_keys(Rifl(1, i + 1), 0, keys)))
    results = d.step(cmds)
    assert d.executed == 6 and d.in_flight == 0
    by_key = {}
    for r in results:
        by_key.setdefault(r.key, []).append(r.op_results[0])
    assert by_key["a"] == [None, "a0", "a1", "a2", "a3", "a4"]
    assert by_key["b"] == [None, "b0", "b2"]


def test_device_runtime_caesar_tcp_serving():
    """Real TCP clients served through the Caesar round: the fourth
    protocol shape behind --device-step."""
    config = Config(3, 1, shard_count=1)
    workload = Workload(
        shard_count=1,
        key_gen=ConflictRateKeyGen(50),
        keys_per_command=1,
        commands_per_client=COMMANDS_PER_CLIENT,
        payload_size=1,
    )
    runtime, clients = asyncio.run(
        run_device_server(
            config, workload, client_count=4, batch_size=32, protocol="caesar"
        )
    )
    assert len(clients) == 4
    for client in clients.values():
        assert client.issued_commands == COMMANDS_PER_CLIENT
    driver = runtime.driver
    assert driver.executed == 4 * COMMANDS_PER_CLIENT
    assert driver.in_flight == 0
    monitor = driver.store.monitor
    for key in monitor.keys():
        order = monitor.get_order(key)
        assert len(order) == len(set(order))


def test_sharded_driver_cross_shard_chain():
    """VERDICT r4 missing #2: shard_count=2 on one mesh.  A multi-shard
    command orders after its per-shard dependency chains on BOTH shards
    and before later commands on either — the device twin of the
    reference's cross-shard dep resolution
    (fantoch_ps/src/executor/graph/mod.rs:279-408)."""
    from fantoch_tpu.client.workload import Workload
    from fantoch_tpu.run.device_runner import DeviceDriver

    d = DeviceDriver(
        3, shard_count=2, batch_size=16, key_buckets=64, key_width=2,
        monitor_execution_order=True,
    )
    # pick one key per shard (workload hash rule: shard = key_hash % S)
    from fantoch_tpu.utils import key_hash

    key0 = next(f"a{i}" for i in range(100) if key_hash(f"a{i}") % 2 == 0)
    key1 = next(f"b{i}" for i in range(100) if key_hash(f"b{i}") % 2 == 1)

    def single(seq, key, value, shard):
        return (
            Dot(1, seq),
            Command.from_single(Rifl(1, seq), shard, key, KVOp.put(value)),
        )

    def multi(seq, v0, v1):
        return (
            Dot(1, seq),
            Command(Rifl(1, seq), {
                0: {key0: (KVOp.put(v0),)},
                1: {key1: (KVOp.put(v1),)},
            }),
        )

    batch = [
        single(1, key0, "s0a", 0),
        single(2, key1, "s1a", 1),
        multi(3, "m0", "m1"),
        single(4, key0, "s0b", 0),
        single(5, key1, "s1b", 1),
    ]
    results = d.step(batch)
    assert d.executed == 5 and d.in_flight == 0
    # per-key chains prove the multi-shard command landed between the
    # singles on BOTH shards
    by_key = {}
    for r in results:
        by_key.setdefault(r.key, []).append(r.op_results[0])
    assert by_key[key0] == [None, "s0a", "m0"]
    assert by_key[key1] == [None, "s1a", "m1"]
    mon = d.store.monitor
    assert mon.get_order(key0)[1] == Rifl(1, 3) == mon.get_order(key1)[1]


def test_device_runtime_sharded_tcp_cluster():
    """A 2-shard device-step server behind real TCP clients: multi-shard
    commands resolve cross-shard dependencies, every client completes,
    and the monitor agrees per key (the r4 'Done' criterion for sharded
    serving)."""
    config = Config(3, 1, shard_count=2)
    workload = Workload(
        shard_count=2,
        key_gen=ConflictRateKeyGen(50),
        keys_per_command=2,  # two keys -> frequently two shards
        commands_per_client=COMMANDS_PER_CLIENT,
        payload_size=1,
    )
    runtime, clients = asyncio.run(
        run_device_server(
            config, workload, client_count=4, batch_size=32,
            key_width=2, key_buckets=64,
        )
    )
    assert len(clients) == 4
    for client in clients.values():
        assert client.issued_commands == COMMANDS_PER_CLIENT
    driver = runtime.driver
    assert driver.executed == 4 * COMMANDS_PER_CLIENT
    assert driver.in_flight == 0
    monitor = driver.store.monitor
    for key in monitor.keys():
        order = monitor.get_order(key)
        assert len(order) == len(set(order))
    assert runtime.failure is None


@pytest.mark.parametrize("protocol", ["epaxos", "newt"])
def test_device_runtime_sharded_pipelined_tcp_cluster(protocol):
    """Sharded serving through the pipelined dispatch/drain loop: the
    pipelining scaffold lives in the shared driver core, so both sharded
    drivers (dep-commit and Newt timestamp) must serve saturated
    multi-shard traffic with cross-shard dependencies intact — the
    missing cells of the (sharded x pipelined) matrix."""
    # (a depth set is the opt-in to overlap on the CPU test backend)
    config = Config(3, 1, shard_count=2, serving_pipeline_depth=1)
    workload = Workload(
        shard_count=2,
        key_gen=ConflictRateKeyGen(50),
        keys_per_command=2,
        commands_per_client=COMMANDS_PER_CLIENT,
        payload_size=1,
    )
    runtime, clients = asyncio.run(
        run_device_server(
            config, workload, client_count=4, batch_size=8,
            key_width=2, key_buckets=64,
            open_loop_interval_ms=1,
            protocol=protocol,
        )
    )
    for client in clients.values():
        assert client.issued_commands == COMMANDS_PER_CLIENT
    driver = runtime.driver
    assert driver.executed == 4 * COMMANDS_PER_CLIENT
    assert driver.in_flight == 0 and not driver.has_outstanding
    monitor = driver.store.monitor
    for key in monitor.keys():
        order = monitor.get_order(key)
        assert len(order) == len(set(order))
    assert runtime.failure is None


def test_sharded_newt_driver_cross_shard_chain():
    """shard_count=2 on the Newt device driver: a multi-shard command's
    timestamp orders it after its per-shard predecessors and before later
    commands on BOTH shards (the MShardCommit max-clock aggregation on
    one mesh)."""
    from fantoch_tpu.run.device_runner import NewtDeviceDriver
    from fantoch_tpu.utils import key_hash

    d = NewtDeviceDriver(
        3, shard_count=2, batch_size=16, key_buckets=64, key_width=2,
        monitor_execution_order=True,
    )
    key0 = next(f"a{i}" for i in range(100) if key_hash(f"a{i}") % 2 == 0)
    key1 = next(f"b{i}" for i in range(100) if key_hash(f"b{i}") % 2 == 1)

    def single(seq, key, value, shard):
        return (
            Dot(1, seq),
            Command.from_single(Rifl(1, seq), shard, key, KVOp.put(value)),
        )

    def multi(seq, v0, v1):
        return (
            Dot(1, seq),
            Command(Rifl(1, seq), {
                0: {key0: (KVOp.put(v0),)},
                1: {key1: (KVOp.put(v1),)},
            }),
        )

    batch = [
        single(1, key0, "s0a", 0),
        single(2, key1, "s1a", 1),
        multi(3, "m0", "m1"),
        single(4, key0, "s0b", 0),
        single(5, key1, "s1b", 1),
    ]
    results = d.step(batch)
    assert d.executed == 5 and d.in_flight == 0
    by_key = {}
    for r in results:
        by_key.setdefault(r.key, []).append(r.op_results[0])
    assert by_key[key0] == [None, "s0a", "m0"]
    assert by_key[key1] == [None, "s1a", "m1"]
    mon = d.store.monitor
    assert mon.get_order(key0)[1] == Rifl(1, 3) == mon.get_order(key1)[1]


@pytest.mark.slow
def test_device_runtime_sharded_newt_tcp_cluster():
    """A 2-shard Newt device-step server behind real TCP clients:
    multi-shard commands commit at the max of their shards' clocks,
    every client completes, and per-key execution order is
    duplicate-free."""
    config = Config(3, 1, shard_count=2)
    workload = Workload(
        shard_count=2,
        key_gen=ConflictRateKeyGen(50),
        keys_per_command=2,
        commands_per_client=COMMANDS_PER_CLIENT,
        payload_size=1,
    )
    runtime, clients = asyncio.run(
        run_device_server(
            config, workload, client_count=4, batch_size=32,
            key_width=2, key_buckets=64, protocol="newt",
        )
    )
    assert len(clients) == 4
    for client in clients.values():
        assert client.issued_commands == COMMANDS_PER_CLIENT
    driver = runtime.driver
    assert driver.executed == 4 * COMMANDS_PER_CLIENT
    assert driver.in_flight == 0
    monitor = driver.store.monitor
    for key in monitor.keys():
        order = monitor.get_order(key)
        assert len(order) == len(set(order))
    assert runtime.failure is None


@pytest.mark.slow
def test_sharded_newt_driver_randomized_soak():
    """Randomized soak of the 2-shard Newt driver: 12 rounds of mixed
    single/multi-shard commands with a degraded stretch in the middle
    (shard 1's majority dead -> its commands and multi-shard commands
    stall on stability, then drain on recovery).  Invariants: everything
    eventually executes exactly once, per-key execution order is
    duplicate-free, and the registry drains."""
    import random as _random

    from fantoch_tpu.parallel import mesh_step
    from fantoch_tpu.run.device_runner import NewtDeviceDriver
    from fantoch_tpu.utils import key_hash

    rng = _random.Random(29)
    d = NewtDeviceDriver(
        3, shard_count=2, batch_size=16, key_buckets=64, key_width=2,
        pending_capacity=32, monitor_execution_order=True,
    )
    keys0 = [next(f"a{i}{j}" for i in range(100)
                  if key_hash(f"a{i}{j}") % 2 == 0) for j in range(3)]
    keys1 = [next(f"b{i}{j}" for i in range(100)
                  if key_hash(f"b{i}{j}") % 2 == 1) for j in range(3)]
    # (what a dispatch of one round runs is ``_programs[1]``: the round
    # compiled on the driver's state and columns)
    degraded_step = d._precompile(mesh_step.jit_newt_step(
        d._mesh, f=1, shard_count=2, live_replicas=4
    ))
    healthy_step = d._program()

    seq = 0
    issued = 0
    multis = 0
    for round_no in range(12):
        d._programs[1] = degraded_step if round_no in (4, 5, 6) else healthy_step
        batch = list(d.take_requeue())
        for _ in range(rng.randrange(1, 9)):
            seq += 1
            issued += 1
            kind = rng.random()
            if kind < 0.4:
                cmd = Command.from_single(
                    Rifl(1, seq), 0, rng.choice(keys0), KVOp.put(f"v{seq}")
                )
            elif kind < 0.8:
                cmd = Command.from_single(
                    Rifl(1, seq), 1, rng.choice(keys1), KVOp.put(f"v{seq}")
                )
            else:
                multis += 1
                cmd = Command(Rifl(1, seq), {
                    0: {rng.choice(keys0): (KVOp.put(f"m0{seq}"),)},
                    1: {rng.choice(keys1): (KVOp.put(f"m1{seq}"),)},
                })
            batch.append((Dot(1, seq), cmd))
        d.step(batch[: d.batch_size])
        for extra in batch[d.batch_size:]:
            d._requeue.append(extra)

    # drain: healthy empty rounds until everything in flight executes
    d._programs[1] = healthy_step
    for _ in range(8):
        if d.in_flight == 0 and not d._requeue:
            break
        batch = list(d.take_requeue())
        d.step(batch[: d.batch_size])
        for extra in batch[d.batch_size:]:
            d._requeue.append(extra)
    assert d.in_flight == 0 and not d._requeue
    assert d.executed == issued
    mon = d.store.monitor
    seen = 0
    for key in mon.keys():
        order = mon.get_order(key)
        assert len(order) == len(set(order)), f"duplicate execution on {key}"
        seen += len(order)
    # every single-shard command appears on one key, every multi-shard
    # command on exactly two (keys0/keys1 are parity-disjoint) — a
    # half-executed multi-shard command would break the count
    assert seen == issued + multis


def _put(src, seq, key, value):
    return (Dot(src, seq), Command.from_single(Rifl(src, seq), 0, key, KVOp.put(value)))


def test_caesar_driver_degraded_requeue_recovery():
    """Caesar driver parity with the Newt/Paxos degraded cases: a round
    with the fast quorum unreachable commits nothing — uncommitted rows
    carry on the device (capacity permitting) and overflow to the host
    requeue — and a healthy round drains everything exactly once with a
    consistent hot-key previous-value chain."""
    from fantoch_tpu.parallel import mesh_step
    from fantoch_tpu.run.device_runner import CaesarDeviceDriver

    import jax
    import jax.numpy as jnp

    from fantoch_tpu.run.device_drivers import _bucket

    d = CaesarDeviceDriver(
        4, batch_size=8, key_buckets=64, pending_capacity=4,
        monitor_execution_order=True,
    )
    healthy = d._program()
    values = {i + 1: f"v{i + 1}" for i in range(12)}
    results = {}

    def absorb(rs):
        for r in rs:
            assert r.rifl.sequence not in results, "duplicate result"
            results[r.rifl.sequence] = r.op_results[0]

    # healthy round seeds the clock index on the hot bucket
    absorb(d.step([_put(1, s, "hot", values[s]) for s in range(1, 5)]))
    assert sorted(results) == [1, 2, 3, 4]

    # stagger replica 0's hot-bucket ceiling: the next proposals diverge
    # across the fast quorum -> retry path; with live=1 < write quorum
    # the retry cannot commit, so everything carries
    bucket = _bucket(0, "hot", 64, 1)
    kc = np.array(d._state.key_clock)
    kc[0, bucket] += 7
    d._state = d._state._replace(
        key_clock=jax.device_put(jnp.asarray(kc), d._state.key_clock.sharding)
    )
    d._programs[1] = d._precompile(
        mesh_step.jit_caesar_step(d._mesh, num_replicas=4, live_replicas=1))
    absorb(d.step([_put(1, s, "hot", values[s]) for s in range(5, 13)]))
    assert sorted(results) == [1, 2, 3, 4], "divergent views must not commit"
    requeued = d.take_requeue()
    assert len(requeued) == 4, "pending capacity 4 of 8 uncommitted"
    assert d.in_flight == 4  # the device-carried half stays registered

    d._programs[1] = healthy
    absorb(d.step(requeued))
    for _ in range(4):
        if d.in_flight == 0 and not d._requeue:
            break
        absorb(d.step(d.take_requeue()))
    assert d.in_flight == 0
    assert sorted(results) == sorted(values)
    # previous-value chain: execution order's result sequence is exactly
    # the values in monitor order, shifted by one
    order = d.store.monitor.get_order("hot")
    assert len(order) == 12 and len(set(order)) == 12
    chain = [results[r.sequence] for r in order]
    expected = [None] + [values[r.sequence] for r in order[:-1]]
    assert chain == expected


def test_epaxos_gid_epoch_reset_with_carried_command():
    """VERDICT r4 missing #6: the gid space rebases instead of dying by
    assert — including a command carried uncommitted across the epoch
    boundary, whose pend_gid / registry key / key-clock view all rebase
    together and whose per-key chain survives.

    Setup: one degraded (live=1) round executes A fast but only replica 0
    learns it, so B on the same key splits the fast quorum, misses, fails
    Synod (1 < write quorum) and carries.  The gid counter is then jumped
    to the reset threshold; the next step rebases by B's gid (the oldest
    in flight), clamps A's stale key-clock entry to -1, and B + C commit
    with the a->b->c value chain intact."""
    import jax
    import jax.numpy as jnp

    from fantoch_tpu.run.device_runner import DeviceDriver

    d = _driver(live_replicas=1)
    (ra,) = d.step([_put(1, 1, "k", "a")])
    assert ra.op_results[0] is None and d.executed == 1

    assert d.step([_put(1, 2, "k", "b")]) == []  # B: fast miss, carries
    assert d.in_flight == 1

    jump = DeviceDriver.GID_RESET_THRESHOLD - 8
    span = jump - d._next_gid
    st = d._state
    # jump both mirrors of the gid counter, keeping live gids live: shift
    # B's gid too so the in-flight span stays rebasable
    pend_gid = np.asarray(st.pend_gid)
    pend_gid = np.where(pend_gid >= 0, pend_gid + span, -1)
    d._state = st._replace(
        next_gid=jax.device_put(jnp.int32(jump), st.next_gid.sharding),
        pend_gid=jax.device_put(jnp.asarray(pend_gid), st.pend_gid.sharding),
    )
    d._next_gid = jump
    d._cmds = {g + span: v for g, v in d._cmds.items()}

    results = d.step([_put(1, 3, "k", "c")])
    assert d.gid_epochs == 1
    assert d._next_gid < DeviceDriver.GID_RESET_THRESHOLD
    # the epoch clamp erased the divergent key-clock entry, so B commits
    # fast and C chains behind it — values prove the order a -> b -> c
    assert [r.op_results[0] for r in results] == ["a", "b"]
    assert d.in_flight == 0 and d.executed == 3
    order = d.store.monitor.get_order("k")
    assert len(order) == len(set(order)) == 3


def test_newt_clock_window_advance():
    """Newt timestamp clocks rebase against the stable floor when they
    near int32: serving continues across the window advance with per-key
    chains intact (ops/table_ops.ClockWindow applied to the device
    plane)."""
    import jax
    import jax.numpy as jnp

    from fantoch_tpu.run.device_runner import NewtDeviceDriver

    d = NewtDeviceDriver(3, batch_size=16, key_buckets=64,
                         monitor_execution_order=True)
    high = d.CLOCK_RESET_THRESHOLD + 10
    st = d._state
    d._state = st._replace(
        key_clock=jax.device_put(
            jnp.full_like(st.key_clock, high), st.key_clock.sharding
        ),
        vote_frontier=jax.device_put(
            jnp.full_like(st.vote_frontier, high), st.vote_frontier.sharding
        ),
    )
    results = d.step([_put(1, i + 1, "hot", str(i)) for i in range(5)])
    assert [r.op_results[0] for r in results] == [None, "0", "1", "2", "3"]
    assert d.clock_epochs == 1
    assert d.stable_watermark >= high  # floor accumulates: still monotone
    # next round proposes from the rebased (small) clocks and chains on
    (r,) = d.step([_put(1, 6, "hot", "x")])
    assert r.op_results[0] == "4"
    assert d.executed == 6 and d.in_flight == 0


def test_seq_window_advance_newt():
    """Dot sequences beyond int32 ride the 31-bit window: the driver
    rebases device columns + host mirror + registry keys and keeps
    serving (VERDICT r4 missing #6, the device_runner.py:319 assert)."""
    from fantoch_tpu.run.device_runner import NewtDeviceDriver

    d = NewtDeviceDriver(3, batch_size=16, key_buckets=64,
                         monitor_execution_order=True)
    S = 2**31 - 4  # a long-lived client plane's sequence space
    results = d.step([_put(1, S + i, "hot", str(i)) for i in range(5)])
    assert [r.op_results[0] for r in results] == [None, "0", "1", "2", "3"]
    assert d.seq_epochs == 1
    # sequences keep growing past 2^31 across rounds
    (r,) = d.step([_put(1, S + 10, "hot", "x")])
    assert r.op_results[0] == "4"
    assert d.executed == 6 and d.in_flight == 0


def test_paxos_slot_epoch_reset():
    """The slot log rebases against the contiguous exec frontier before
    int32 exhaustion; the watermark stays monotone across the epoch."""
    import jax
    import jax.numpy as jnp

    from fantoch_tpu.run.device_runner import PaxosDeviceDriver

    d = PaxosDeviceDriver(3, f=1, batch_size=16, monitor_execution_order=True)
    results = d.step([_put(1, i + 1, "k", str(i)) for i in range(3)])
    assert len(results) == 3

    jump = PaxosDeviceDriver.SLOT_RESET_THRESHOLD - 8
    st = d._state
    d._state = st._replace(
        next_slot=jax.device_put(jnp.int32(jump), st.next_slot.sharding),
        exec_frontier=jax.device_put(jnp.int32(jump), st.exec_frontier.sharding),
    )
    d._next_slot = jump

    (r,) = d.step([_put(1, 4, "k", "c")])
    assert d.slot_epochs == 1
    assert r.op_results[0] == "2"  # chain intact across the epoch
    assert d.stable_watermark == jump + 1  # monotone: base + new frontier
    assert d.in_flight == 0 and d.executed == 4


def test_paxos_driver_slot_chain():
    """The leader-based slot round behind the driver seam: execution is
    contiguous slot order == submission order, the key chain reflects it,
    and the frontier carries across rounds (third protocol family
    served; fantoch_ps/src/bin/fpaxos.rs analog)."""
    from fantoch_tpu.run.device_runner import PaxosDeviceDriver

    d = PaxosDeviceDriver(3, f=1, batch_size=16, monitor_execution_order=True)
    batch = [
        (Dot(1, i + 1), Command.from_single(Rifl(1, i + 1), 0, "hot", KVOp.put(str(i))))
        for i in range(10)
    ]
    results = d.step(batch)
    assert [r.op_results[0] for r in results] == [None] + [str(i) for i in range(9)]
    assert d.executed == 10 and d.in_flight == 0
    assert d.stable_watermark == 10
    (r,) = d.step(
        [(Dot(1, 11), Command.from_single(Rifl(1, 11), 0, "hot", KVOp.put("x")))]
    )
    assert r.op_results[0] == "9"
    assert d.stable_watermark == 11


def test_paxos_driver_degraded_requeue_recovery():
    """Slot stickiness + overflow slot-rollback at the driver seam: a
    degraded round commits nothing, overflow beyond the pending buffer
    re-queues the highest slots, and after recovery every command
    executes exactly once in a dense slot log."""
    from fantoch_tpu.parallel import mesh_step
    from fantoch_tpu.run.device_runner import PaxosDeviceDriver

    d = PaxosDeviceDriver(
        3, f=1, batch_size=8, pending_capacity=4,
        live_replicas=1, monitor_execution_order=True,
    )
    batch = [
        (Dot(1, i + 1), Command.from_single(Rifl(1, i + 1), 0, "k", KVOp.put(str(i))))
        for i in range(8)
    ]
    assert d.step(batch) == []
    requeued = d.take_requeue()
    # 8 valid rows, capacity 4: the 4 highest slots were dropped and
    # their commands re-queued under their original dots
    assert [dot.sequence for dot, _ in requeued] == [5, 6, 7, 8]
    assert d.in_flight == 4

    # recovery: all replicas answer again (the runtime would re-jit the
    # step the same way on failure-detector feedback)
    d._programs[1] = d._precompile(
        mesh_step.jit_paxos_step(d._mesh, f=1, num_replicas=3))
    results = d.step(requeued)
    assert d.executed == 8 and d.in_flight == 0
    # carried slots (0-3) execute before the reassigned ones; per-key
    # chain shows every put exactly once
    assert [r.op_results[0] for r in results] == [None, "0", "1", "2", "3", "4", "5", "6"]
    order = d.store.monitor.get_order("k")
    assert len(order) == len(set(order)) == 8


def test_device_runtime_paxos_tcp_serving():
    """Real TCP clients served through the leader-based slot round:
    --device-step --protocol fpaxos end-to-end."""
    config = Config(3, 1, shard_count=1)
    workload = Workload(
        shard_count=1,
        key_gen=ConflictRateKeyGen(50),
        keys_per_command=2,  # slot order needs no key rows: any width
        commands_per_client=COMMANDS_PER_CLIENT,
        payload_size=1,
    )
    runtime, clients = asyncio.run(
        run_device_server(
            config, workload, client_count=4, batch_size=32, protocol="fpaxos"
        )
    )
    assert len(clients) == 4
    for client in clients.values():
        assert client.issued_commands == COMMANDS_PER_CLIENT
    driver = runtime.driver
    assert driver.executed == 4 * COMMANDS_PER_CLIENT
    assert driver.in_flight == 0
    # two keys per command: a rifl appears once in each touched key's order
    monitor = driver.store.monitor
    for key in monitor.keys():
        order = monitor.get_order(key)
        assert len(order) == len(set(order))


def test_newt_runtime_requeue_after_degraded_round():
    """VERDICT r4 weak #5: a Newt command that overflows the pending
    buffer in a degraded round re-enters the submit queue under the same
    dot and completes after recovery — through the real TCP runtime —
    with per-key order intact.

    Topology chosen to produce *uncommitted* (requeue-able) overflow:
    n=5, f=2, one live replica.  The first degraded round still commits
    its batch on the fast path (all proposals agree: max-count f is met),
    but those commands cannot stabilize without live voters; from the
    next round the lone live replica's clock has diverged, the fast path
    misses (max reported by 1 < f) and Synod gets 1 < f+1 acks, so later
    commands stay uncommitted.  With 24 hot-key commands against a
    16-slot pending buffer the committed backlog (8, carried with
    priority) plus uncommitted rows overflow — the overflowed uncommitted
    tail cycles through take_requeue() under its original dots."""
    from fantoch_tpu.parallel import mesh_step
    from fantoch_tpu.run.client_runner import run_clients
    from fantoch_tpu.run.device_runner import DeviceRuntime
    from fantoch_tpu.run.harness import free_port

    async def go():
        config = Config(5, 2, shard_count=1)
        port = free_port()
        runtime = DeviceRuntime(
            config,
            ("127.0.0.1", port),
            protocol="newt",
            batch_size=8,
            key_buckets=64,
            pending_capacity=16,
            live_replicas=1,
            monitor_execution_order=True,
        )
        await runtime.start()
        try:
            workload = Workload(
                shard_count=1,
                key_gen=ConflictRateKeyGen(100),  # one hot key: max contention
                keys_per_command=1,
                commands_per_client=8,
                payload_size=1,
            )
            # open-loop clients keep submitting without waiting, pushing
            # past the pending capacity while degraded
            client_task = asyncio.ensure_future(
                run_clients(
                    [1, 2, 3], {0: ("127.0.0.1", port)}, workload,
                    open_loop_interval_ms=5,
                )
            )
            # wait until all 24 commands are in flight with rounds cycling
            # and nothing executing: 24 > pending_capacity=16 proves the
            # overflow tail is living in the requeue loop
            driver = runtime.driver
            for _ in range(400):
                await asyncio.sleep(0.025)
                if driver.rounds >= 6 and driver.in_flight == 24:
                    break
            assert driver.in_flight == 24 and driver.rounds >= 6
            assert driver.executed == 0
            # recovery: swap in the healthy step (what a failure-detector
            # integration would do); in-flight commands must now commit
            driver._step = mesh_step.jit_newt_step(
                driver._mesh, f=config.f, tiny_quorums=False
            )
            driver._programs.clear()  # the next dispatch compiles the new round
            clients = await client_task
            for client in clients.values():
                assert client.issued_commands == 8
            assert driver.executed == 24
            assert driver.in_flight == 0
            order = driver.store.monitor.get_order(
                next(iter(driver.store.monitor.keys()))
            )
            assert len(order) == len(set(order)) == 24
            assert runtime.failure is None
        finally:
            await runtime.stop()

    asyncio.run(go())


def test_device_runtime_survives_bad_client():
    """A client submitting a command wider than the compiled key_width is
    rejected at the session boundary with an empty CommandResult — the
    driver's asserts are unreachable from the network, the bad session
    keeps serving valid commands, and a concurrent well-behaved client
    completes its workload (per-connection failure isolation,
    fantoch/src/run/task/process.rs:320-325)."""
    from fantoch_tpu.run.device_runner import DeviceRuntime
    from fantoch_tpu.run.harness import free_port
    from fantoch_tpu.run.client_runner import run_clients
    from fantoch_tpu.run.prelude import ClientHi, ClientHiAck, Submit, ToClient
    from fantoch_tpu.run.device_drivers import _bucket
    from fantoch_tpu.run.rw import Rw

    key_buckets = 64
    # two keys guaranteed to land in distinct buckets (over-wide for kw=1)
    key_a = "a"
    key_b = next(
        k
        for k in (f"b{i}" for i in range(1000))
        if _bucket(0, k, key_buckets, 1) != _bucket(0, key_a, key_buckets, 1)
    )

    async def go():
        config = Config(3, 1, shard_count=1)
        port = free_port()
        runtime = DeviceRuntime(
            config,
            ("127.0.0.1", port),
            batch_size=16,
            key_buckets=key_buckets,
            key_width=1,
            monitor_execution_order=True,
        )
        await runtime.start()
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            rw = Rw(reader, writer)
            await rw.send(ClientHi([99]))
            assert isinstance(await rw.recv(), ClientHiAck)
            # over-wide submit: rejected, not crashed
            bad = Command.from_keys(
                Rifl(99, 1), 0,
                {key_a: (KVOp.put("x"),), key_b: (KVOp.put("y"),)},
            )
            await rw.send(Submit(bad))
            reply = await rw.recv()
            assert isinstance(reply, ToClient)
            assert reply.cmd_result.rifl == Rifl(99, 1)
            assert reply.cmd_result.ready  # zero-key error result
            # the same session still serves valid commands afterwards
            good = Command.from_single(Rifl(99, 2), 0, key_a, KVOp.put("z"))
            await rw.send(Submit(good))
            reply = await rw.recv()
            assert isinstance(reply, ToClient)
            assert reply.cmd_result.rifl == Rifl(99, 2)
            writer.close()

            # a concurrent well-behaved client completes its workload
            workload = Workload(
                shard_count=1,
                key_gen=ConflictRateKeyGen(50),
                keys_per_command=1,
                commands_per_client=5,
                payload_size=1,
            )
            clients = await run_clients([1], {0: ("127.0.0.1", port)}, workload)
            assert clients[1].issued_commands == 5
            assert runtime.failure is None
        finally:
            await runtime.stop()
        return runtime

    runtime = asyncio.run(go())
    # the rejected command never reached the driver
    assert runtime.driver.executed == 1 + 5


def test_device_runtime_newt_multi_key_tcp():
    """keys_per_command=2 served through the Newt timestamp round."""
    config = Config(3, 1, shard_count=1)
    workload = Workload(
        shard_count=1,
        key_gen=ConflictRateKeyGen(50),
        keys_per_command=2,
        commands_per_client=5,
        payload_size=1,
    )
    runtime, clients = asyncio.run(
        run_device_server(
            config, workload, client_count=2, batch_size=16,
            key_width=2, protocol="newt",
        )
    )
    for client in clients.values():
        assert client.issued_commands == 5
    assert runtime.driver.executed == 10
    assert runtime.driver.in_flight == 0


def test_driver_pipelined_equivalence():
    """serve under overlap returns each round's results one call late and, with
    a final flush, produces exactly the sync driver's execution: same
    per-round result values, same per-key monitor order, same tallies
    (the overlap must be pure scheduling, never reordering)."""
    def batches():
        out, seq = [], 0
        for r in range(6):
            batch = []
            for j in range(4):
                seq += 1
                key = "hot" if (seq % 2) else f"priv{j}"
                batch.append(_put(1, seq, key, f"v{seq}"))
            out.append(batch)
        return out

    d_sync, d_pipe = _driver(), _driver()
    sync_rounds = [d_sync.step(b) for b in batches()]
    pipe_rounds = [d_pipe.serve([b], overlap=True) for b in batches()]
    assert pipe_rounds[0] == []  # one round of delivery lag
    pipe_rounds.append(d_pipe.flush_pipeline())
    assert not d_pipe.has_outstanding

    def flat(rounds):
        return [(r.rifl, r.key, tuple(r.op_results)) for rr in rounds for r in rr]

    assert flat(pipe_rounds) == flat(sync_rounds)
    # the lag is exactly one round: pipelined round k+1 == sync round k
    assert flat(pipe_rounds[1:2]) == flat(sync_rounds[0:1])
    assert d_pipe.executed == d_sync.executed == 24
    assert d_pipe.in_flight == 0
    for key in d_sync.store.monitor.keys():
        assert (
            d_pipe.store.monitor.get_order(key)
            == d_sync.store.monitor.get_order(key)
        )


@pytest.mark.parametrize("protocol", ["newt", "caesar", "fpaxos"])
def test_dot_driver_pipelined_equivalence(protocol):
    """The Newt/Caesar/Paxos drivers gain the dispatch/drain split:
    pipelined rounds lag by one call and, with a final flush, reproduce
    the sync driver's execution exactly — results, per-key monitor
    order, and tallies (identity comes from the step outputs, so no host
    mirror can drift while a round is in flight)."""
    from fantoch_tpu.run.device_runner import (
        CaesarDeviceDriver,
        NewtDeviceDriver,
        PaxosDeviceDriver,
    )

    cls = {"newt": NewtDeviceDriver, "caesar": CaesarDeviceDriver,
           "fpaxos": PaxosDeviceDriver}[protocol]
    mk = lambda: cls(3, batch_size=16, key_buckets=64,  # noqa: E731
                     monitor_execution_order=True)

    def batches():
        out, seq = [], 0
        for _r in range(5):
            batch = []
            for j in range(4):
                seq += 1
                key = "hot" if (seq % 2) else f"priv{j}"
                batch.append(_put(1, seq, key, f"v{seq}"))
            out.append(batch)
        return out

    d_sync, d_pipe = mk(), mk()
    sync_rounds = [d_sync.step(b) for b in batches()]
    pipe_rounds = [d_pipe.serve([b], overlap=True) for b in batches()]
    assert pipe_rounds[0] == []  # one round of delivery lag
    pipe_rounds.append(d_pipe.flush_pipeline())
    assert not d_pipe.has_outstanding
    assert d_pipe.pipelined_rounds == 4

    def flat(rounds):
        return [(r.rifl, r.key, tuple(r.op_results)) for rr in rounds for r in rr]

    assert flat(pipe_rounds) == flat(sync_rounds)
    assert flat(pipe_rounds[1:2]) == flat(sync_rounds[0:1])
    assert d_pipe.executed == d_sync.executed == 20
    assert d_pipe.in_flight == 0
    for key in d_sync.store.monitor.keys():
        assert (
            d_pipe.store.monitor.get_order(key)
            == d_sync.store.monitor.get_order(key)
        )


def test_newt_pipelined_clock_threshold_flushes_outstanding():
    """A Newt clock-window advance must never run with a round in
    flight: when the max committed clock nears the reset threshold,
    serve under overlap retires the outstanding round first (and the drain
    asserts the invariant)."""
    from fantoch_tpu.run.device_runner import NewtDeviceDriver

    d = NewtDeviceDriver(3, batch_size=8, key_buckets=16,
                         pending_capacity=8,
                         monitor_execution_order=True)
    # force the flush condition without 2^31 rounds of work
    d._max_clock = NewtDeviceDriver.CLOCK_RESET_THRESHOLD - 1
    r1 = d.serve([[_put(1, 1, "k", "a")]], overlap=True)
    assert r1 == [] and d.has_outstanding
    # threshold trips: the next pipelined call must flush first
    assert d._pipeline_flush_needed([_put(1, 2, "k", "b")])
    r2 = d.serve([[_put(1, 2, "k", "b")]], overlap=True)
    # the early flush returned round 1's results; round 2 is in flight
    assert [r.op_results[0] for r in r2] == [None]
    assert d.has_outstanding
    r3 = d.flush_pipeline()
    assert [r.op_results[0] for r in r3] == ["a"]
    assert d.in_flight == 0


def test_pipelined_gid_reset_flushes_outstanding():
    """The gid epoch reset rebases the registry that drain reads, so
    serve under overlap must retire the outstanding round *before* resetting
    (the early-flush branch); the reset then proceeds and chains stay
    intact across it."""
    d = _driver(batch_size=16)
    assert d.serve([[_put(1, 1, "k", "a")]], overlap=True) == []
    assert d.has_outstanding
    # lower the threshold on this instance so the next dispatch triggers
    d.GID_RESET_THRESHOLD = d._next_gid + d.batch_size
    r1 = d.serve([[_put(1, 2, "k", "b")]], overlap=True)
    # the early flush returned round 1's results ahead of the reset
    assert [r.op_results[0] for r in r1] == [None]
    assert d.gid_epochs == 1 and d.has_outstanding
    r2 = d.flush_pipeline()
    assert [r.op_results[0] for r in r2] == ["a"]
    assert d.executed == 2 and d.in_flight == 0
    order = d.store.monitor.get_order("k")
    assert len(order) == len(set(order)) == 2


@pytest.mark.parametrize("protocol", ["epaxos", "newt", "caesar", "fpaxos"])
def test_device_runtime_pipelined_tcp_serving(protocol):
    """Saturated serving engages the pipelined loop (batch_size smaller
    than the standing queue) and still answers every client with per-key
    order agreement — the TCP twin of the equivalence test; the Newt
    driver serves through the same dispatch/drain scaffold."""
    # (a depth set is the opt-in to overlap on the CPU test backend)
    config = Config(3, 1, shard_count=1, serving_pipeline_depth=1)
    workload = Workload(
        shard_count=1,
        key_gen=ConflictRateKeyGen(50),
        keys_per_command=1,
        commands_per_client=COMMANDS_PER_CLIENT,
        payload_size=1,
    )
    runtime, clients = asyncio.run(
        run_device_server(
            config,
            workload,
            client_count=4,
            batch_size=8,
            open_loop_interval_ms=1,
            protocol=protocol,
        )
    )
    for client in clients.values():
        assert client.issued_commands == COMMANDS_PER_CLIENT
        assert len(list(client.data().latency_data())) == COMMANDS_PER_CLIENT
    driver = runtime.driver
    assert driver.executed == 4 * COMMANDS_PER_CLIENT
    assert driver.in_flight == 0 and not driver.has_outstanding
    # engagement itself is asserted deterministically in
    # test_runtime_pipeline_engages_on_backlog (whether the open-loop
    # firehose outpaces the rounds here is host-speed-dependent)
    monitor = driver.store.monitor
    seen = [rifl for key in monitor.keys() for rifl in monitor.get_order(key)]
    assert len(seen) == len(set(seen)) == 4 * COMMANDS_PER_CLIENT


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("proto_cls", ["epaxos", "newt"])
def test_depth_k_pipelined_parity(proto_cls, depth):
    """The depth-K loop is pure scheduling: at every depth the pipelined
    run (with a mid-stream flush_pipeline thrown in) produces exactly
    the sync driver's execution — same per-round result values in the
    same order, same per-key monitor order, same tallies."""
    from fantoch_tpu.run.device_runner import NewtDeviceDriver

    cls = {"epaxos": DeviceDriver, "newt": NewtDeviceDriver}[proto_cls]
    mk = lambda: cls(3, batch_size=16, key_buckets=64,  # noqa: E731
                     monitor_execution_order=True)

    def batches():
        out, seq = [], 0
        for _r in range(7):
            batch = []
            for j in range(4):
                seq += 1
                key = "hot" if (seq % 2) else f"priv{j}"
                batch.append(_put(1, seq, key, f"v{seq}"))
            out.append(batch)
        return out

    d_sync, d_pipe = mk(), mk()
    d_pipe.pipeline_depth = depth
    sync_rounds = [d_sync.step(b) for b in batches()]
    pipe_rounds = []
    for r, b in enumerate(batches()):
        pipe_rounds.append(d_pipe.serve([b], overlap=True))
        if r == 3:  # mid-stream flush must retire in order, then refill
            pipe_rounds.append(d_pipe.flush_pipeline())
            assert not d_pipe.has_outstanding
    pipe_rounds.append(d_pipe.flush_pipeline())
    assert not d_pipe.has_outstanding

    def flat(rounds):
        return [(r.rifl, r.key, tuple(r.op_results)) for rr in rounds for r in rr]

    assert flat(pipe_rounds) == flat(sync_rounds)
    # the lag is exactly min(depth, rounds so far): round 0's results
    # surface on call `depth`
    if depth < 4:
        assert flat(pipe_rounds[:depth]) == []
        assert flat(pipe_rounds[depth : depth + 1]) == flat(sync_rounds[0:1])
    assert d_pipe.executed == d_sync.executed == 28
    assert d_pipe.in_flight == 0
    for key in d_sync.store.monitor.keys():
        assert (
            d_pipe.store.monitor.get_order(key)
            == d_sync.store.monitor.get_order(key)
        )
    counters = d_pipe.device_counters()
    assert counters["device_pipeline_depth"] == depth
    assert 0.0 <= counters["device_idle_frac"] <= 1.0
    assert counters["device_busy_ms"] <= counters["device_span_ms"] + 1e-6


@pytest.mark.parametrize("depth", [2, 3])
def test_seq_window_advance_races_inflight_dispatches(depth):
    """A dot-sequence window advance may only run with the pipeline
    empty: forcing a tiny window mid-stream must early-flush the
    in-flight rounds, rebase, and keep bit-for-bit parity with a sync
    driver under the same tiny window."""
    from fantoch_tpu.run.device_runner import NewtDeviceDriver

    def mk():
        d = NewtDeviceDriver(3, batch_size=8, key_buckets=64,
                             monitor_execution_order=True)
        d.SEQ_WINDOW_MAX = 24  # instance override: advance every ~3 rounds
        return d

    def batches():
        out, seq = [], 0
        for _r in range(10):
            batch = []
            for _j in range(4):
                seq += 1
                batch.append(_put(1, seq, "hot" if seq % 2 else "cold",
                                  f"v{seq}"))
            out.append(batch)
        return out

    d_sync, d_pipe = mk(), mk()
    d_pipe.pipeline_depth = depth
    sync_rounds = [d_sync.step(b) for b in batches()]
    pipe_rounds = [d_pipe.serve([b], overlap=True) for b in batches()]
    pipe_rounds.append(d_pipe.flush_pipeline())

    def flat(rounds):
        return [(r.rifl, r.key, tuple(r.op_results)) for rr in rounds for r in rr]

    assert flat(pipe_rounds) == flat(sync_rounds)
    assert d_pipe.seq_epochs >= 1  # the window really advanced mid-run
    assert d_pipe.seq_epochs == d_sync.seq_epochs
    assert d_pipe.executed == d_sync.executed == 40
    for key in d_sync.store.monitor.keys():
        assert (
            d_pipe.store.monitor.get_order(key)
            == d_sync.store.monitor.get_order(key)
        )


def test_pipelined_requeue_interleaving():
    """Device pending-buffer overflow requeues interleave with the
    depth-2 pipeline: degraded rounds carry + overflow while rounds are
    in flight, requeued commands re-enter through pipelined rounds, and
    after healing everything executes exactly once with the hot-key
    previous-value chain intact.  Topology per
    test_newt_runtime_requeue_after_degraded_round: n=5/f=2/live=1 makes
    the first degraded round's commits a carried (priority) backlog and
    later rounds' rows uncommitted — so the overflow tail is
    requeue-able, never committed."""
    from fantoch_tpu.parallel import mesh_step
    from fantoch_tpu.run.device_runner import NewtDeviceDriver

    d = NewtDeviceDriver(5, f=2, batch_size=8, key_buckets=64,
                         pending_capacity=12,
                         monitor_execution_order=True)
    d.pipeline_depth = 2
    healthy = d._program()
    values = {i + 1: f"v{i + 1}" for i in range(20)}
    results = {}

    def absorb(rs):
        for r in rs:
            assert r.rifl.sequence not in results, "duplicate result"
            results[r.rifl.sequence] = r.op_results[0]

    # healthy pipelined round seeds the hot-key chain
    absorb(d.serve([[_put(1, s, "hot", values[s]) for s in range(1, 5)]], overlap=True))
    # degrade to one live replica with rounds in flight: round d1 still
    # commits (agreeing proposals) but cannot stabilize; round d2's rows
    # stay uncommitted and, with the committed backlog carried first,
    # overflow the 12-slot pending buffer into the host requeue
    d._programs[1] = d._precompile(
        mesh_step.jit_newt_step(d._mesh, f=2, live_replicas=1))
    absorb(d.serve([[_put(1, s, "hot", values[s]) for s in range(5, 13)]], overlap=True))
    absorb(d.serve([[_put(1, s, "hot", values[s]) for s in range(13, 21)]], overlap=True))
    absorb(d.flush_pipeline())
    assert d.in_flight > 0  # carried (committed backlog + uncommitted)
    requeued = d.take_requeue()
    assert requeued, "pending capacity 12 must have overflowed"

    # heal and feed requeues back through pipelined rounds until drained
    # (empty rounds at the tail let the carried backlog stabilize)
    d._programs[1] = healthy
    pending = requeued
    for _ in range(30):
        absorb(d.serve([pending[:4]], overlap=True))
        pending = pending[4:] + d.take_requeue()
        if not pending and d.in_flight == 0 and not d.has_outstanding:
            break
    absorb(d.flush_pipeline())
    while d.in_flight or d._requeue:
        absorb(d.step(d.take_requeue()))
    assert sorted(results) == sorted(values)
    order = d.store.monitor.get_order("hot")
    assert len(order) == 20 and len(set(order)) == 20
    chain = [results[r.sequence] for r in order]
    expected = [None] + [values[r.sequence] for r in order[:-1]]
    assert chain == expected


def test_chained_pipelined_parity():
    """serve of a chain under overlap (S in-dispatch rounds x depth-K
    in-flight chains) reproduces the sync per-round execution exactly,
    like the chain without overlap but with chains carried in flight."""
    from fantoch_tpu.run.device_runner import NewtDeviceDriver

    mk = lambda: NewtDeviceDriver(3, batch_size=8, key_buckets=64,  # noqa: E731
                                  monitor_execution_order=True)

    def batches():
        out, seq = [], 0
        for _r in range(12):
            batch = []
            for j in range(4):
                seq += 1
                key = "hot" if (seq % 2) else f"priv{j}"
                batch.append(_put(1, seq, key, f"v{seq}"))
            out.append(batch)
        return out

    d_sync, d_chp = mk(), mk()
    d_chp.pipeline_depth = 2
    bs = batches()
    groups = [bs[i * 3 : (i + 1) * 3] for i in range(4)]
    sync_rounds = [d_sync.step(b) for b in bs]
    chp_rounds = [d_chp.serve(g, overlap=True) for g in groups]
    chp_rounds.append(d_chp.flush_pipeline())

    def flat(rounds):
        return [(r.rifl, r.key, tuple(r.op_results)) for rr in rounds for r in rr]

    assert flat(chp_rounds) == flat(sync_rounds)
    assert d_chp.executed == d_sync.executed == 48
    assert not d_chp.has_outstanding and d_chp.in_flight == 0
    for key in d_sync.store.monitor.keys():
        assert (
            d_chp.store.monitor.get_order(key)
            == d_sync.store.monitor.get_order(key)
        )
    # one dispatch per chain (the tail flush only drains), rounds
    # counted per protocol round
    assert d_chp.dispatches == 4
    assert d_chp.rounds == 12


# --- the masked drain (PR 37) against the walks it replaced ---
#
# Until PR 37 every drain walked all W working rows in Python: the order
# walk of `_execute_ordered` / `DeviceDriver._execute`, and in
# `_drain_and_carry` / `PaxosDeviceDriver._execute` a second walk for the
# carried rows.  The loops below are those, verbatim, as the oracle: the
# drivers' drains must execute the same commands in the same order and
# leave the same registry, tallies and requeue.


class _WalkOracle:
    """The dot-keyed drains as they stood: mixed in ahead of a driver."""

    def _execute_ordered(self, order, executed, work_src, work_seq):
        results = []
        for w in order.tolist():
            if not executed[w]:
                continue
            entry = self._cmds.pop(
                self._packed(work_src[w], work_seq[w]), None
            )
            if entry is None:
                continue  # pad row
            results.extend(self._execute_entry(entry[1]))
            self.executed += 1
        return results

    def _drain_and_carry(self, out, label, committed_noun):
        order = np.asarray(out.order)
        executed = np.asarray(out.executed)
        committed = np.asarray(out.committed)
        work_src = np.asarray(out.work_src)
        work_seq = np.asarray(out.work_seq)
        results = self._execute_ordered(order, executed, work_src, work_seq)

        carried = [
            w
            for w in range(len(work_src))
            if self._packed(work_src[w], work_seq[w]) in self._cmds
        ]
        carried.sort(key=lambda w: (not committed[w], w))
        dropped = carried[self._pend_cap:]
        if any(committed[w] for w in dropped):
            raise RuntimeError(
                f"{label} device pending buffer overflowed with "
                f"committed-but-{committed_noun} commands: raise "
                "pending_capacity (a committed timestamp cannot be "
                "re-proposed)"
            )
        self._requeue_rows(dropped, work_src, work_seq, label)
        return results


class _EpaxosWalk(dr.DeviceDriver):
    def _execute(self, _tok, out):
        order = np.asarray(out.order)
        resolved = np.asarray(out.resolved)
        gids = np.asarray(out.gids)
        fast = np.asarray(out.fast_path)
        self.stable_watermark = self._frontier_base + int(out.stable)

        results = []
        for w in order.tolist():
            gid = int(gids[w])
            if gid < 0 or not resolved[w]:
                continue
            entry = self._cmds.pop(gid, None)
            if entry is None:
                continue  # padding row (registered by no one)
            _dot, cmd = entry
            results.extend(self._execute_entry(cmd))
            self.executed += 1
            if fast[w]:
                self.fast_paths += 1
        self.slow_paths += int(out.slow_paths)

        if int(out.pend_dropped) > 0:
            carried = [
                int(gids[w])
                for w in range(len(gids))
                if gids[w] >= 0 and not resolved[w]
            ]  # working order == device carry order
            pend_cap = self._state.pend_gid.shape[0]
            dropped = carried[pend_cap:]
            for gid in dropped:
                entry = self._cmds.pop(gid, None)
                if entry is not None:
                    self._requeue.append(entry)
        return results

class _NewtWalk(_WalkOracle, dr.NewtDeviceDriver):
    pass

class _CaesarWalk(_WalkOracle, dr.CaesarDeviceDriver):
    pass

class _PaxosWalk(_WalkOracle, dr.PaxosDeviceDriver):
    def _execute(self, tok, out):
        n_batch = tok[1]
        order = np.asarray(out.order)
        executed = np.asarray(out.executed)
        slot = np.asarray(out.slot)
        work_src = np.asarray(out.work_src)
        work_seq = np.asarray(out.work_seq)
        self._next_slot += n_batch - int(out.pend_dropped)
        self.stable_watermark = self._slot_base + int(out.exec_frontier)
        self.slow_paths += int(executed.sum())

        results = self._execute_ordered(order, executed, work_src, work_seq)

        carried = [
            w
            for w in range(len(work_src))
            if slot[w] >= 0
            and not executed[w]
            and self._packed(work_src[w], work_seq[w]) in self._cmds
        ]
        carried.sort(key=lambda w: int(slot[w]))
        self._requeue_rows(carried[self._pend_cap:], work_src, work_seq, "paxos")
        return results


# protocol -> (the driver, its walking oracle, the replicas of the degraded
# scenarios the requeue tests above use, further constructor arguments)
DRAIN_DRIVERS = {
    "epaxos": (dr.DeviceDriver, _EpaxosWalk, 3, {}),
    "newt": (dr.NewtDeviceDriver, _NewtWalk, 5, {"f": 2}),
    "caesar": (dr.CaesarDeviceDriver, _CaesarWalk, 4, {}),
    "fpaxos": (dr.PaxosDeviceDriver, _PaxosWalk, 3, {"f": 1}),
}


def _drain_pair(protocol, **kw):
    """The protocol's driver and its walking oracle, built alike; the
    oracle runs the driver's own programs."""
    cls, walk, n, extra = DRAIN_DRIVERS[protocol]
    kw = {"key_buckets": 64, "monitor_execution_order": True, **extra, **kw}
    real, oracle = cls(n, **kw), walk(n, **kw)
    oracle._programs = real._programs
    return real, oracle


def _degrade(protocol, *drivers, live=1):
    """Swap in a round with ``live`` replicas answering (None: all),
    one program for all ``drivers``."""
    from fantoch_tpu.parallel import mesh_step

    d = drivers[0]
    n = DRAIN_DRIVERS[protocol][2]
    step = {
        "epaxos": lambda: mesh_step.jit_protocol_step(d._mesh, live_replicas=live),
        "newt": lambda: mesh_step.jit_newt_step(d._mesh, f=2, live_replicas=live),
        "caesar": lambda: mesh_step.jit_caesar_step(
            d._mesh, num_replicas=n, live_replicas=live),
        "fpaxos": lambda: mesh_step.jit_paxos_step(
            d._mesh, f=1, num_replicas=n, live_replicas=live),
    }[protocol]()
    program = d._precompile(step)
    for driver in drivers:
        driver._programs[1] = program


def _puts(seqs, own=True):
    """Two batches of the same commands (a driver each): odd sequences
    on the hot key, even ones on a key of their own (``own``) or there
    too."""
    return tuple(
        [_put(1, s, "hot" if s % 2 or not own else f"own{s}", f"v{s}") for s in seqs]
        for _ in range(2)
    )


def _flat(results):
    return [(r.rifl, r.key, tuple(r.op_results)) for r in results]


def _assert_same_drain(real, oracle):
    assert {k: v[0] for k, v in real._cmds.items()} == {
        k: v[0] for k, v in oracle._cmds.items()
    }
    assert [dot for dot, _ in real._requeue] == [dot for dot, _ in oracle._requeue]
    for tally in ("executed", "fast_paths", "slow_paths", "stable_watermark", "rounds"):
        assert getattr(real, tally) == getattr(oracle, tally), tally
    for key in oracle.store.monitor.keys():
        assert real.store.monitor.get_order(key) == oracle.store.monitor.get_order(key)


def _step_both(real, oracle, seqs, **kw):
    mine, theirs = _puts(seqs, **kw)
    got, want = real.step(mine), oracle.step(theirs)
    assert _flat(got) == _flat(want)
    _assert_same_drain(real, oracle)
    return got


@pytest.mark.parametrize("protocol", DRAIN_DRIVERS)
def test_masked_drain_executes_what_the_walk_executed(protocol):
    """At the benchmark's working set (4096 + 4096 rows) a round 1.2%
    full, a full one and an empty one: the same commands in the same
    order, the same registry and the same tallies as the 8192-row walks
    gave."""
    real, oracle = _drain_pair(
        protocol, batch_size=4096, pending_capacity=4096, key_buckets=1024
    )
    assert len(_step_both(real, oracle, range(1, 51))) == 50
    assert len(_step_both(real, oracle, range(51, 51 + 4096))) == 4096
    assert _step_both(real, oracle, []) == []
    assert real.executed == 4146 and real.in_flight == 0
    order = real.store.monitor.get_order("hot")
    assert len(order) == len(set(order)) == 2073


@pytest.mark.parametrize("protocol", DRAIN_DRIVERS)
def test_a_part_full_drain_walks_its_executed_rows_not_the_working_set(protocol):
    """`drain_rows_walked` counts the working rows a drain's Python
    visited: 50 after a round of 50 commands at W = 8192 (the walks made
    it 8192, Newt's and Caesar's 16384), the dep-commit round's 4046
    resolved rows of padding left out too; and the runtime publishes it
    beside `executed`."""
    real, _ = _drain_pair(
        protocol, batch_size=4096, pending_capacity=4096, key_buckets=1024
    )
    (mine, _) = _puts(range(1, 51))
    assert len(real.step(mine)) == 50
    assert real.step([]) == []
    assert real.executed == 50 and real.drain_rows_walked == 50

    runtime, _clients = _served(protocol=protocol)
    t = runtime._tallies
    assert t["drain_rows_walked"] == t["executed"] == 4 * COMMANDS_PER_CLIENT


@pytest.mark.parametrize("protocol", DRAIN_DRIVERS)
def test_overflow_requeues_what_the_walk_requeued_and_scans_only_then(protocol):
    """A degraded round that carries without dropping runs no scan (the
    device's `pend_dropped` is 0: nothing walked); one that overflows
    the pending buffer of 4 requeues the rows the walk requeued, under
    their dots and in its order; after healing both drain alike.  Newt's
    committed overflow (and Caesar's, through the shared tail) fails as
    loudly as it did."""
    real, oracle = _drain_pair(protocol, batch_size=8, pending_capacity=4)
    dropped, inner = [], real._execute

    def watched(tok, out):
        dropped.append(int(out.pend_dropped))
        return inner(tok, out)

    real._execute = watched

    if protocol == "epaxos":
        # degraded from the start: only replica 0 learns the seed, so
        # what follows on its key splits the fast quorum and carries
        _degrade(protocol, real, oracle)
    assert len(_step_both(real, oracle, range(1, 5), own=False)) == 4
    if protocol == "caesar":
        # stagger replica 0's ceiling on the hot bucket: proposals diverge
        from fantoch_tpu.run.device_drivers import _bucket

        for d in (real, oracle):
            kc = np.array(d._state.key_clock)
            kc[0, _bucket(0, "hot", 64, 1)] += 7
            d._state = d._state._replace(key_clock=jax.device_put(
                jax.numpy.asarray(kc), d._state.key_clock.sharding))
    _degrade(protocol, real, oracle)
    walked = real.drain_rows_walked

    if protocol == "newt":
        # the first degraded round commits and cannot stabilise: eight
        # committed rows against a capacity of 4 cannot be re-proposed
        mine, theirs = _puts(range(5, 13), own=False)
        with pytest.raises(RuntimeError, match="committed-but-unstable") as theirs_exc:
            oracle.step(theirs)
        with pytest.raises(RuntimeError, match="committed-but-unstable") as mine_exc:
            real.step(mine)
        assert str(mine_exc.value) == str(theirs_exc.value)
        assert dropped == [0, 4]
        # an uncommitted overflow: a capacity of 12 holds the committed
        # eight, the next round's uncommitted rows overflow it
        real, oracle = _drain_pair(protocol, batch_size=8, pending_capacity=12)
        _step_both(real, oracle, range(1, 5), own=False)
        _degrade(protocol, real, oracle)
        walked = real.drain_rows_walked
        assert _step_both(real, oracle, range(5, 13), own=False) == []
        assert real.in_flight == 8 and real.drain_rows_walked == walked
        assert _step_both(real, oracle, range(13, 21), own=False) == []
        assert [dot.sequence for dot, _ in real._requeue] == [17, 18, 19, 20]
        # the scan's candidates: every unexecuted row of the 12 + 8
        assert real.drain_rows_walked == walked + 20
        total = 20
    else:
        # three rows carry, none dropped: no scan, nothing walked
        assert _step_both(real, oracle, range(5, 8), own=False) == []
        assert dropped[-1] == 0 and real.in_flight == 3
        assert real.drain_rows_walked == walked and not real.has_requeue
        # eight more: 11 unexecuted rows against a capacity of 4
        assert _step_both(real, oracle, range(8, 16), own=False) == []
        assert dropped[-1] == 7 and real.in_flight == 4
        assert [dot.sequence for dot, _ in real._requeue] == list(range(9, 16))
        # the scan's candidates: the 11 valid unexecuted rows (Caesar's
        # tail masks on `executed` alone: the empty slot too)
        assert real.drain_rows_walked == walked + (12 if protocol == "caesar" else 11)
        total = 15

    _degrade(protocol, real, oracle, live=None)
    for _ in range(6):
        mine, theirs = real.take_requeue(), oracle.take_requeue()
        assert [dot for dot, _ in mine] == [dot for dot, _ in theirs]
        assert _flat(real.step(mine)) == _flat(oracle.step(theirs))
        _assert_same_drain(real, oracle)
    assert real.in_flight == 0 and not real.has_requeue
    assert real.executed == total
    order = real.store.monitor.get_order("hot")
    assert len(order) == len(set(order)) == total


@pytest.mark.parametrize("label, noun", [("newt", "unstable"), ("caesar", "blocked")])
def test_committed_overflow_fails_loudly_through_the_shared_tail(label, noun):
    """`_drain_and_carry` on a round's outputs made by hand: six
    registered unexecuted rows, five of them committed, against a
    capacity of 4: the walk's `RuntimeError`, letter for letter; with
    two committed the four uncommitted requeue committed-first order's
    tail; and a `pend_dropped` of 0 leaves even those alone."""
    from types import SimpleNamespace

    protocol = label
    real, oracle = _drain_pair(protocol, batch_size=8, pending_capacity=4)
    W = 12

    def out(committed_rows, pend_dropped):
        committed = np.zeros(W, bool)
        committed[committed_rows] = True
        executed = np.zeros(W, bool)
        executed[[4, 5]] = True
        return SimpleNamespace(
            order=np.array([5, 4] + [w for w in range(W) if w not in (4, 5)], np.int32),
            executed=executed, committed=committed,
            work_src=np.array([-1, -1, -1, -1] + [1] * 8, np.int32),
            work_seq=np.array([-1, -1, -1, -1] + list(range(1, 9)), np.int32),
            pend_dropped=np.int32(pend_dropped),
        )

    def register(d):
        d._cmds.clear()
        d._requeue.clear()
        for dot, cmd in _puts(range(1, 9))[0]:
            d._cmds[d._packed(dot.source, dot.sequence)] = (dot, cmd)

    errors = []
    for d in (real, oracle):
        register(d)
        with pytest.raises(RuntimeError, match=f"{label} .*committed-but-{noun}") as exc:
            d._drain_and_carry(out([6, 7, 8, 9, 10], 2), label, noun)
        errors.append(str(exc.value))
    assert errors[0] == errors[1]

    requeued = []
    for d in (real, oracle):
        register(d)
        got = d._drain_and_carry(out([10, 11], 2), label, noun)
        assert [r.rifl.sequence for r in got] == [2, 1]  # device order
        requeued.append([dot.sequence for dot, _ in d.take_requeue()])
        assert sorted(d._cmds) == [d._packed(1, s) for s in (3, 4, 7, 8)]
    assert requeued[0] == requeued[1] == [5, 6]

    register(real)
    walked = real.drain_rows_walked
    real._drain_and_carry(out([10, 11], 0), label, noun)
    assert real.drain_rows_walked == walked + 2 and not real.has_requeue
    assert len(real._cmds) == 6


@pytest.mark.parametrize("seq", [0, 1, 2**31 - 2, -1])
def test_the_key_column_is_packed_row_by_row(seq):
    """`_packed_column` is `_packed` for the identity columns the rounds
    return: `int32`, sources 0..12, sequences to the window's top; and
    for an empty pending slot's (-1, -1)."""
    from fantoch_tpu.run.device_runner import _DriverCore

    sources = list(range(13)) if seq >= 0 else [-1]
    work_src = np.array(sources, np.int32)
    work_seq = np.full(len(sources), seq, np.int32)
    rows = np.arange(len(sources))[::-1]
    column = _DriverCore._packed_column(work_src, work_seq, rows)
    assert column == [
        _DriverCore._packed(work_src[w], work_seq[w]) for w in rows.tolist()
    ]
    assert all(type(packed) is int for packed in column)
    if seq >= 0:
        assert column == [(src << 32) | seq for src in reversed(sources)]


def test_runtime_resolves_depth_from_config():
    """Config.serving_pipeline_depth reaches the driver, and an explicit
    depth opts the runtime into pipelining even on the CPU backend."""
    from fantoch_tpu.run.device_runner import DeviceRuntime
    from fantoch_tpu.run.harness import free_port

    runtime = DeviceRuntime(
        Config(3, 1, serving_pipeline_depth=2),
        ("127.0.0.1", free_port()),
        batch_size=8,
        key_buckets=64,
    )
    assert runtime.pipeline_depth == 2
    assert runtime.driver.pipeline_depth == 2
    assert runtime.pipeline  # depth request == pipelining opt-in


def test_device_runtime_depth2_tcp_serving():
    """Saturated TCP serving through the depth-2 loop answers every
    client with per-key order agreement and retires the pipeline."""
    config = Config(3, 1, serving_pipeline_depth=2)
    workload = Workload(
        shard_count=1,
        key_gen=ConflictRateKeyGen(50),
        keys_per_command=1,
        commands_per_client=COMMANDS_PER_CLIENT,
        payload_size=1,
    )
    runtime, clients = asyncio.run(
        run_device_server(
            config,
            workload,
            client_count=4,
            batch_size=8,
            open_loop_interval_ms=1,
            protocol="newt",
        )
    )
    for client in clients.values():
        assert client.issued_commands == COMMANDS_PER_CLIENT
        assert len(list(client.data().latency_data())) == COMMANDS_PER_CLIENT
    driver = runtime.driver
    assert driver.pipeline_depth == 2
    assert driver.executed == 4 * COMMANDS_PER_CLIENT
    assert driver.in_flight == 0 and not driver.has_outstanding
    monitor = driver.store.monitor
    seen = [rifl for key in monitor.keys() for rifl in monitor.get_order(key)]
    assert len(seen) == len(set(seen)) == 4 * COMMANDS_PER_CLIENT
    counters = runtime._tallies
    assert 0.0 <= counters["device_idle_frac"] <= 1.0
    assert counters["device_pipeline_depth"] == 2


def test_runtime_pipeline_engages_on_backlog():
    """Deterministic pipeline engagement: a backlog deeper than the batch
    is enqueued before the driver task first runs, so the queue is
    non-empty at every early batch fill and the overlap must engage
    (no dependence on client arrival timing)."""
    from fantoch_tpu.run.device_runner import DeviceRuntime
    from fantoch_tpu.run.harness import free_port

    async def go():
        config = Config(3, 1, shard_count=1, serving_pipeline_depth=1)
        runtime = DeviceRuntime(
            config,
            ("127.0.0.1", free_port()),
            batch_size=8,
            key_buckets=64,
            monitor_execution_order=True,
        )
        for i in range(24):
            cmd = Command.from_single(
                Rifl(9, i + 1), 0, f"k{i % 3}", KVOp.put(str(i))
            )
            runtime.submit(runtime.dot_gen.next_id(), cmd)
        await runtime.start()
        for _ in range(500):
            if runtime.failure is not None:
                raise runtime.failure
            if (
                runtime.driver.executed >= 24
                and not runtime.driver.has_outstanding
            ):
                break
            await asyncio.sleep(0.02)
        await runtime.stop()
        return runtime

    runtime = asyncio.run(go())
    driver = runtime.driver
    assert driver.executed == 24
    assert driver.pipelined_rounds > 0
    assert driver.in_flight == 0 and not driver.has_outstanding
    # per-key chains survived the pipelined rounds
    monitor = driver.store.monitor
    seen = [r for key in monitor.keys() for r in monitor.get_order(key)]
    assert len(seen) == len(set(seen)) == 24


def test_quiet_flush_vs_new_arrival_race():
    """r16 audit fix regression: under depth K>1 the quiet-flush path
    (queue went empty with rounds still in flight) retires each
    in-flight round exactly once even as fresh submissions keep landing
    mid-flush on the event loop — no stranded results, no double
    delivery, no dispatch interleaved into the flushing pipeline."""
    from fantoch_tpu.run.device_runner import DeviceRuntime
    from fantoch_tpu.run.harness import free_port

    async def go():
        config = Config(3, 1, shard_count=1, serving_pipeline_depth=2)
        runtime = DeviceRuntime(
            config,
            ("127.0.0.1", free_port()),
            batch_size=8,
            key_buckets=64,
            monitor_execution_order=True,
        )
        # two full rounds land before the driver task first runs: both
        # dispatch pipelined, then the queue is quiet with rounds in
        # flight and the loop takes the quiet-flush branch...
        for i in range(16):
            cmd = Command.from_single(
                Rifl(9, i + 1), 0, f"k{i % 3}", KVOp.put(str(i))
            )
            runtime.submit(runtime.dot_gen.next_id(), cmd)
        await runtime.start()
        # ...while fresh arrivals race it from the event-loop side
        for i in range(16, 40):
            await asyncio.sleep(0.002)
            cmd = Command.from_single(
                Rifl(9, i + 1), 0, f"k{i % 3}", KVOp.put(str(i))
            )
            runtime.submit(runtime.dot_gen.next_id(), cmd)
        # generous bound: the first dispatch pays the driver's XLA
        # compile, ~18 s on the older jaxlib pins
        for _ in range(1500):
            if runtime.failure is not None:
                raise runtime.failure
            if (
                runtime.driver.executed >= 40
                and not runtime.driver.has_outstanding
            ):
                break
            await asyncio.sleep(0.02)
        await runtime.stop()
        return runtime

    runtime = asyncio.run(go())
    driver = runtime.driver
    assert driver.executed == 40
    assert driver.in_flight == 0 and not driver.has_outstanding
    # exactly-once execution across flush/dispatch interleavings
    monitor = driver.store.monitor
    seen = [r for key in monitor.keys() for r in monitor.get_order(key)]
    assert len(seen) == len(set(seen)) == 40


def test_lone_command_fast_path_releases_immediately():
    """The idle-system fast path (run/ingest.py): a lone closed-loop
    command on an idle runtime releases without sitting out the ingest
    deadline.  The deadline here is far longer than the wait loop, so a
    missing fast path fails the test by timeout, not by a timing
    margin; the batcher's cause tally pins the path taken."""
    from fantoch_tpu.run.device_runner import DeviceRuntime
    from fantoch_tpu.run.harness import free_port

    async def go():
        config = Config(3, 1, shard_count=1, ingest_deadline_ms=300_000.0)
        runtime = DeviceRuntime(
            config,
            ("127.0.0.1", free_port()),
            batch_size=8,
            key_buckets=64,
        )
        cmd = Command.from_single(Rifl(9, 1), 0, "k0", KVOp.put("v"))
        runtime.submit(runtime.dot_gen.next_id(), cmd)
        await runtime.start()
        # ~30 s (covers the first-dispatch XLA compile): far under the
        # 300 s deadline a missing fast path would sit out
        for _ in range(1500):
            if runtime.failure is not None:
                raise runtime.failure
            if runtime.driver.executed >= 1:
                break
            await asyncio.sleep(0.02)
        await runtime.stop()
        return runtime

    runtime = asyncio.run(go())
    assert runtime.driver.executed == 1
    assert runtime._batcher.releases_fast >= 1
    assert runtime._batcher.releases_deadline == 0


# --- round-stage spans on the served path (observability/device.py) ---


def _served(tmp_path=None, **kw):
    config = Config(3, 1, shard_count=1, serving_pipeline_depth=1, **kw.pop("config", {}))
    workload = Workload(
        shard_count=1, key_gen=ConflictRateKeyGen(50), keys_per_command=1,
        commands_per_client=COMMANDS_PER_CLIENT, payload_size=1,
    )
    return asyncio.run(
        run_device_server(
            config, workload, client_count=4, batch_size=8,
            open_loop_interval_ms=1, **kw,
        )
    )


@pytest.mark.parametrize("protocol", ["epaxos", "newt", "caesar", "fpaxos"])
def test_served_round_self_times_telescope(protocol, tmp_path):
    """Every driver's round splits the same way: the step holds its
    four children, the round holds the loop's stages and the step with
    its two hand-offs, and a drain is its fetch plus its execute (so
    dispatch + fetch + drain holds the fetch twice)."""
    import json

    runtime, _clients = _served(
        protocol=protocol, telemetry_file=str(tmp_path / "series.jsonl"))
    t = runtime._tallies
    assert t["executed"] == t["replied"] == 4 * COMMANDS_PER_CLIENT
    inside = sum(t[f"stage_{name}_ms"] for name in ("assemble", "enqueue", "fetch", "execute"))
    assert t["stage_step_ms"] >= inside - 0.01 and inside > 0
    assert t["stage_step_cpu_ms"] > 0
    around = sum(t[f"stage_{name}_ms"] for name in
                 ("collect", "handoff", "step", "resume", "deliver", "publish"))
    # the round's own publish is still open when its tallies are taken
    assert t["stage_round_ms"] + t["stage_publish_ms"] >= around - 0.05 * t["stage_round_n"]
    assert t["device_drain_ms"] == pytest.approx(
        t["stage_fetch_ms"] + t["stage_execute_ms"], abs=0.002)
    assert t["stage_step_n"] == t["stage_handoff_n"] == t["stage_resume_n"]
    assert t["stage_fetch_n"] == t["device_dispatches"]
    # the per-command boundaries
    assert t["session_decoded"] >= t["submitted"] == 4 * COMMANDS_PER_CLIENT
    assert t["queue_released"] == t["submitted"] and t["queue_wait_ms"] > 0
    assert t["session_decode_ms"] > 0 and t["session_admit_ms"] > 0
    assert t["reply_flushes"] > 0 and t["reply_flush_ms"] > 0
    # the ring, written on stop beside the series; spans name their thread
    with open(tmp_path / "round_spans.json") as fh:
        ring = json.load(fh)
    rows = [dict(zip(ring["columns"], row)) for row in ring["spans"]]
    by_name = {row["name"]: row for row in rows}
    assert {"round", "collect", "handoff", "step", "assemble", "enqueue", "fetch",
            "execute", "resume", "deliver", "publish", "idle_wait"} <= set(by_name)
    assert by_name["step"]["parent"] == by_name["deliver"]["parent"] == "round"
    assert by_name["fetch"]["parent"] == by_name["assemble"]["parent"] == "step"
    assert by_name["step"]["thread"] != by_name["deliver"]["thread"]  # pool vs loop
    assert by_name["handoff"]["thread"] == by_name["step"]["thread"]
    # a step lies inside a round of its number (a round that only retires the round in
    # flight makes no dispatch, so it shares its number with the round after it)
    for row in rows:
        if row["name"] == "step":
            assert any(whole["t0_ns"] <= row["t0_ns"] <= row["t1_ns"] <= whole["t1_ns"]
                       for whole in rows if whole["name"] == "round" and whole["round"] == row["round"])


def test_round_spans_are_written_only_beside_a_metrics_or_telemetry_file(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    runtime, _clients = _served()
    assert runtime.stages.ring and not list(tmp_path.iterdir())
    assert "profile_dir" not in runtime._tallies  # a string: not among the numbers


def test_stage_counters_are_monotone_across_snapshots_and_name_the_profile_dir(tmp_path):
    """What ``snapshot_delta`` needs of every new key: in the first
    snapshot already, numeric, never falling."""
    import json

    from fantoch_tpu.run.device_runner import DeviceRuntime
    from fantoch_tpu.run.harness import free_port

    async def go():
        runtime = DeviceRuntime(
            Config(3, 1, shard_count=1), ("127.0.0.1", free_port()),
            batch_size=8, key_buckets=64, metrics_file=str(tmp_path / "snap.json"),
        )
        runtime._write_metrics_snapshot()
        with open(tmp_path / "snap.json") as fh:
            first = json.load(fh)
        await runtime.start()
        for i in range(3):
            cmd = Command.from_single(Rifl(9, i + 1), 0, f"k{i}", KVOp.put("v"))
            runtime.submit(runtime.dot_gen.next_id(), cmd)
        for _ in range(1500):
            if runtime.failure is not None:
                raise runtime.failure
            # (a stop while the step still runs on its pool thread would
            # close the cancelled round ahead of its step)
            if runtime.driver.executed >= 3 and runtime.stages.n["round"] >= 1:
                break
            await asyncio.sleep(0.02)
        await runtime.stop()
        with open(tmp_path / "snap.json") as fh:
            return first, json.load(fh)

    first, last = asyncio.run(go())
    assert first["profile_dir"] == last["profile_dir"] == str(tmp_path)
    new_keys = [key for key in last if key.startswith(("stage_", "session_", "queue_wait",
                                                       "queue_released", "reply_flush", "loop_"))]
    assert len(new_keys) >= 2 * 14 + 1 + 10
    for key in new_keys:
        assert key in first, key
        assert isinstance(first[key], (int, float)) and isinstance(last[key], (int, float))
        assert last[key] >= first[key], key
    assert last["stage_round_n"] >= 1 and last["stage_step_ms"] > 0
    assert (tmp_path / "round_spans.json").exists()


def test_the_lag_task_records_a_stall_of_the_loop(tmp_path):
    import json
    import time

    from fantoch_tpu.run.device_runner import DeviceRuntime
    from fantoch_tpu.run.harness import free_port

    async def go():
        runtime = DeviceRuntime(
            Config(3, 1, shard_count=1), ("127.0.0.1", free_port()),
            batch_size=8, key_buckets=64, metrics_file=str(tmp_path / "snap.json"),
        )
        await runtime.start()
        await asyncio.sleep(0.05)  # the probe is asleep in its 10 ms
        time.sleep(0.06)           # the loop does not run for 60 ms
        await asyncio.sleep(0.05)
        await runtime.stop()
        return runtime

    runtime = asyncio.run(go())
    t = runtime._tallies
    assert t["loop_stalls"] >= 1 and t["loop_stall_ms"] >= 40.0
    assert t["loop_lag_hwm_ms"] >= 40.0
    with open(tmp_path / "round_spans.json") as fh:
        ring = json.load(fh)
    stalls = [row for row in ring["spans"] if row[0] == "loop_stall"]
    assert stalls and (stalls[0][2] - stalls[0][1]) / 1e6 >= 40.0
    assert t["stage_loop_stall_n"] == t["loop_stalls"]


def test_full_collections_and_snapshot_writes_are_in_the_ring(tmp_path):
    """The two things that run on the loop beside the rounds and can stop
    it: the telemetry tick's file write (a ``snapshot`` span) and a full
    collection of the interpreter (a ``gc`` entry, whatever thread ran
    it).  The hook is gone again when the runtime stops."""
    import gc

    from fantoch_tpu.run.device_runner import DeviceRuntime
    from fantoch_tpu.run.harness import free_port

    async def go():
        runtime = DeviceRuntime(
            Config(3, 1, shard_count=1), ("127.0.0.1", free_port()),
            batch_size=8, key_buckets=64, metrics_file=str(tmp_path / "snap.json"),
            metrics_interval_ms=20,
        )
        await runtime.start()
        assert runtime._on_gc in gc.callbacks
        gc.collect()   # generation 2
        gc.collect(0)  # a young collection is not recorded
        await asyncio.sleep(0.1)
        await runtime.stop()
        return runtime

    runtime = asyncio.run(go())
    assert runtime._on_gc not in gc.callbacks
    t = runtime._tallies
    assert t["stage_gc_n"] >= 1 and t["stage_gc_ms"] > 0
    assert t["stage_snapshot_n"] >= 2 and t["stage_snapshot_ms"] > 0
    names = [row[0] for row in runtime.stages.ring]
    assert "gc" in names and "snapshot" in names


def test_round_spans_reach_the_operators_trace(tmp_path):
    """With the per-command tracer on, each finished round is one ``rs``
    event, the ``ingest`` stamp names the round that released the
    command, ``to-perfetto`` draws the rounds, and the tools that do not
    know the kind skip it."""
    from fantoch_tpu.observability import read_trace
    from fantoch_tpu.observability.critpath import critpath_report
    from fantoch_tpu.observability.perfetto import (
        ROUNDS_TID, to_perfetto, validate_perfetto,
    )
    from fantoch_tpu.observability.report import diff_stages, summarize

    trace = str(tmp_path / "trace.jsonl")
    runtime, _clients = _served(trace_file=trace, config={"trace_sample_rate": 1.0})
    events = read_trace(trace)
    rounds = [ev for ev in events if ev["k"] == "rs"]
    assert rounds and len(rounds) == runtime.stages.n["round"]
    assert all(ev["name"] == "round" and ev["t1"] >= ev["t0"] and ev["pid"] == 1
               for ev in rounds)
    assert all(ev["t1"] <= ev["t"] for ev in rounds)  # on the log's own clock
    ingests = [ev for ev in events if ev["k"] == "span" and ev["stage"] == "ingest"]
    assert len(ingests) == 4 * COMMANDS_PER_CLIENT
    assert {ev["m"]["round"] for ev in ingests} <= {ev["round"] for ev in rounds}
    drawn = to_perfetto(events)
    validate_perfetto(drawn)
    slices = [ev for ev in drawn["traceEvents"] if ev.get("cat") == "round"]
    assert len(slices) == len(rounds) and {ev["tid"] for ev in slices} == {ROUNDS_TID}
    # report, critpath and diff see the same spans with and without the kind
    plain = [ev for ev in events if ev["k"] != "rs"]
    with_kind, without = summarize(events), summarize(plain)
    assert with_kind.pop("events") == without.pop("events") + len(rounds)
    assert with_kind == without
    differ = diff_stages(events, plain, tol_frac=0.0, tol_abs_us=0)
    assert differ["matched"] == 4 * COMMANDS_PER_CLIENT
    assert not (differ["only_a"] or differ["only_b"] or differ["mismatches"])
    assert critpath_report(events) == critpath_report(plain)


# --- the reply stage works on a round (DeviceRuntime._deliver) ---


class _CountingWriter:
    """What ``Rw`` needs of a ``StreamWriter``: every ``write`` is one
    entry of ``writes``; ``fail`` is raised by each of them instead."""

    def __init__(self, fail=None):
        self.writes = []
        self.fail = fail

    def get_extra_info(self, name):
        return None

    def write(self, data):
        if self.fail is not None:
            raise self.fail
        self.writes.append(bytes(data))


def _reply_stage(shard_count=1, connections=3, failing=None):
    """An unstarted runtime with one session per connection, each over
    a counting writer; ``failing`` maps a connection to what its writes
    raise."""
    from fantoch_tpu.run.device_runner import DeviceRuntime
    from fantoch_tpu.run.device_session import _DeviceClientSession
    from fantoch_tpu.run.rw import Rw

    runtime = DeviceRuntime(
        Config(3, 1, shard_count=shard_count), ("127.0.0.1", 0),
        batch_size=8, key_buckets=64, key_width=2,
    )
    writers = [_CountingWriter((failing or {}).get(i)) for i in range(connections)]
    sessions = [_DeviceClientSession(runtime, Rw(None, writer)) for writer in writers]
    return runtime, sessions, writers


def _submitted(runtime, session, cmd):
    """What ``_DeviceClientSession._admit`` does for an admitted Submit,
    short of the ring."""
    session.track(cmd)
    assert runtime.rifl_sessions[cmd.rifl] is session


def _frames(data):
    """The ``ToClient``s of a connection's bytes, through the client
    plane's own decoder."""
    from fantoch_tpu.run import rw

    out, at = [], 0
    while at < len(data):
        (length,) = rw._LEN.unpack_from(data, at)
        out.append(rw.deserialize(data[at + 4:at + 4 + length]))
        at += 4 + length
    assert at == len(data)
    return out


def _fields(to_client):
    result = to_client.cmd_result
    return result.rifl, result._key_count, result.results, result.ready


def _one_at_a_time(tracked, rounds):
    """The reference: a result at a time, a ``ToClient`` at a time, as
    the stage worked before it took a round.  ``tracked`` maps a
    connection to its live commands; returns connection -> replies."""
    from fantoch_tpu.core.command import CommandResult
    from fantoch_tpu.run.prelude import ToClient

    owner, parts, left, out = {}, {}, {}, {conn: [] for conn in tracked}
    for conn, cmds in tracked.items():
        for cmd in cmds:
            owner[cmd.rifl], left[cmd.rifl] = conn, cmd.shard_count
            for sid in cmd.shards():
                part = CommandResult(cmd.rifl, cmd.key_count(sid))
                for key in cmd.keys(sid):
                    parts[cmd.rifl, key] = part
    for results in rounds:
        for result in results:
            if result.rifl not in owner:
                continue
            part = parts[result.rifl, result.key]
            if part.add_partial(result.key, result.op_results):
                out[owner[result.rifl]].append(ToClient(part))
                left[result.rifl] -= 1
                if left[result.rifl] == 0:
                    del owner[result.rifl]
    return out


def _mixed_rounds(shard_count):
    """Two rounds of results over three live connections and a dropped
    one: single-key commands, a two-key command whose partials arrive
    in different rounds, a two-shard command (where the server has two
    shards), a stale rifl (a one-key command of the third connection
    whose partial comes again a round after its reply) and the dropped
    session's rifl."""
    from fantoch_tpu.executor.base import ExecutorResult

    put = KVOp.put("v" * 100)
    singles = {
        conn: [Command.from_single(Rifl(10 + conn, seq), 0, f"k{conn}.{seq}", put)
               for seq in (1, 2, 3)]
        for conn in (0, 1, 2)
    }
    two_key = Command(Rifl(10, 9), {0: {"a": (put,), "b": (put,)}})
    tracked = {0: singles[0] + [two_key], 1: singles[1], 2: singles[2]}
    if shard_count == 2:
        tracked[1] = tracked[1] + [Command(Rifl(11, 9), {0: {"c": (put,)}, 1: {"d": (put, put)}})]
    stale, dropped = Rifl(12, 77), Command.from_single(Rifl(13, 1), 0, "gone", put)
    tracked[2] = tracked[2] + [Command.from_single(stale, 0, "x", put)]

    def res(rifl, key, *values):
        return ExecutorResult(rifl, key, values or ("prev-" + key,))

    first = [res(Rifl(10, 1), "k0.1"), res(Rifl(11, 1), "k1.1"), res(Rifl(10, 9), "b"),
             res(stale, "x"), res(Rifl(12, 1), "k2.1", None), res(Rifl(13, 1), "gone"),
             res(Rifl(10, 2), "k0.2"), res(Rifl(11, 9), "d", None, "d1")]
    second = [res(Rifl(11, 2), "k1.2"), res(Rifl(10, 9), "a"), res(Rifl(11, 9), "c"),
              res(Rifl(10, 3), "k0.3"), res(Rifl(12, 2), "k2.2"), res(stale, "x")]
    live = {cmd.rifl for cmds in tracked.values() for cmd in cmds} | {stale, dropped.rifl}
    rounds = [[r for r in results if r.rifl in live] for results in (first, second)]
    return tracked, stale, dropped, rounds


@pytest.mark.parametrize("shard_count", [1, 2])
def test_a_rounds_replies_equal_the_one_at_a_time_reference(shard_count):
    tracked, stale, dropped, rounds = _mixed_rounds(shard_count)
    runtime, sessions, writers = _reply_stage(shard_count, connections=4)
    for conn, cmds in tracked.items():
        for cmd in cmds:
            _submitted(runtime, sessions[conn], cmd)
    _submitted(runtime, sessions[3], dropped)
    runtime.drop_session(sessions[3])
    for results in rounds:
        runtime._deliver(results)
    expected = _one_at_a_time(tracked, rounds)
    for conn in tracked:
        got = _frames(b"".join(writers[conn].writes))
        assert [_fields(t) for t in got] == [_fields(t) for t in expected[conn]], conn
        assert got and all(t.cmd_result.ready for t in got)
    assert writers[3].writes == []
    answered = sum(len(cmds) for cmds in tracked.values())
    # the stale rifl is answered once; only the unexecuted commands stay routed
    assert runtime.replied == answered - 2
    assert set(runtime.rifl_sessions) == {Rifl(11, 3), Rifl(12, 3)}
    assert not sessions[0]._owed


def test_a_round_is_one_write_per_session_and_the_snapshot_counts_it():
    tracked, stale, dropped, rounds = _mixed_rounds(1)
    runtime, sessions, writers = _reply_stage(connections=4)
    for conn, cmds in tracked.items():
        for cmd in cmds:
            _submitted(runtime, sessions[conn], cmd)
    runtime._deliver(rounds[0])
    # every live connection has a reply in the first round; the fourth has none
    assert [len(w.writes) for w in writers] == [1, 1, 1, 0]
    assert all(s._flush_needed.is_set() for s in sessions[:3])
    assert not sessions[3]._flush_needed.is_set()
    runtime._deliver(rounds[1])
    runtime._deliver([])  # a progress round with nothing executed writes nothing
    assert [len(w.writes) for w in writers] == [2, 2, 2, 0]
    runtime._publish_tallies()
    t = runtime._tallies
    assert t["reply_writes"] == sum(len(w.writes) for w in writers) == 6
    assert t["reply_bytes"] == sum(len(data) for w in writers for data in w.writes)
    assert t["replied"] == sum(len(_frames(data)) for w in writers for data in w.writes) == 9
    # eight one-key commands framed from their one partial, the two-key one aggregated
    assert (t["reply_flat_frames"], t["shard_replies"], t["reply_plain_frames"]) == (8, 9, 9)
    assert t["reply_partial_frames"] == 8


def test_a_dead_connection_costs_only_its_own_replies(caplog):
    tracked, stale, dropped, rounds = _mixed_rounds(1)
    runtime, sessions, writers = _reply_stage(
        connections=3, failing={1: ConnectionResetError("peer went away")})
    for conn, cmds in tracked.items():
        for cmd in cmds:
            _submitted(runtime, sessions[conn], cmd)
    with caplog.at_level("WARNING"):
        for results in rounds:
            runtime._deliver(results)
    expected = _one_at_a_time(tracked, rounds)
    for conn in (0, 2):
        got = _frames(b"".join(writers[conn].writes))
        assert [_fields(t) for t in got] == [_fields(t) for t in expected[conn]]
    assert writers[1].writes == []
    # one warning per session and round, not one per result
    assert len([r for r in caplog.records if "dead session" in r.getMessage()]) == 2
    # the answered rifls of the dead connection are not left routed
    assert set(runtime.rifl_sessions) == {Rifl(11, 3), Rifl(12, 3)}
    assert runtime.replied == len(expected[0]) + len(expected[2])
    # anything but a transport fault still fails the stage loudly
    writers[0].fail = RuntimeError("not a transport fault")
    _submitted(runtime, sessions[0], Command.from_single(Rifl(10, 50), 0, "z", KVOp.put("v")))
    with pytest.raises(RuntimeError):
        runtime._deliver([rounds[0][0]._replace(rifl=Rifl(10, 50), key="z")])


def test_every_reply_is_one_executed_span_and_one_reply_edge():
    class Recorder:
        enabled = True

        def __init__(self):
            self.spans, self.edges = [], []

        def span(self, stage, rifl, **kw):
            self.spans.append((stage, rifl, kw["pid"]))

        def edge(self, direction, kind, *where, rifl):
            self.edges.append((direction, kind, rifl))

    tracked, stale, dropped, rounds = _mixed_rounds(2)
    runtime, sessions, writers = _reply_stage(shard_count=2, connections=3)
    runtime.tracer = Recorder()
    for conn, cmds in tracked.items():
        for cmd in cmds:
            _submitted(runtime, sessions[conn], cmd)
    for results in rounds:
        runtime._deliver(results)
    replies = [t.cmd_result.rifl for w in writers for t in _frames(b"".join(w.writes))]
    assert len(replies) == 11  # 8 single-key, the two-key one, two shards of Rifl(11, 9)
    assert sorted(rifl for _stage, rifl, _pid in runtime.tracer.spans) == sorted(replies)
    assert {(stage, pid) for stage, _rifl, pid in runtime.tracer.spans} == {
        ("executed", runtime.process_id)}
    assert sorted(rifl for _d, _k, rifl in runtime.tracer.edges) == sorted(replies)
    assert {(d, k) for d, k, _rifl in runtime.tracer.edges} == {("s", "Reply")}


# --- a one-key command carries no aggregation state (PR 41) ---


def _partial(cmd, key, *values):
    from fantoch_tpu.executor.base import ExecutorResult

    return ExecutorResult(cmd.rifl, key, values)


def _in_flight(runtime, sessions):
    """Everything the runtime and its sessions hold of commands in flight."""
    held = {"rifl_sessions": dict(runtime.rifl_sessions)}
    for i, session in enumerate(sessions):
        held[i, "_owed"] = dict(session._owed)
        held[i, "_reads"] = set(session._reads)
    return {name: left for name, left in held.items() if left}


@pytest.mark.parametrize("op, values", [
    (KVOp.put("v" * 100), ("previous",)),
    (KVOp.get(), ("v" * 1000,)),
    (KVOp.get(), (None,)),
    (KVOp.put("größer \u2603 \U0001f600"), ("wert \u00e9\u4e2d",)),
], ids=["put", "get-hit", "get-miss", "non-ascii"])
def test_a_one_key_reply_is_the_command_results_frame_byte_for_byte(op, values):
    from fantoch_tpu.core.command import CommandResult
    from fantoch_tpu.run.rw import frame, reply_frame
    from fantoch_tpu.run.prelude import ToClient

    runtime, (session,), (writer,) = _reply_stage(connections=1)
    cmd = Command.from_single(Rifl(2**40 + 7, 2**33), 0, "k\u00fc", op)
    session.track(cmd)
    assert not session._owed
    runtime._deliver([_partial(cmd, "k\u00fc", *values)])
    aggregated = CommandResult(cmd.rifl, 1)
    assert aggregated.add_partial("k\u00fc", values)
    assert writer.writes == [reply_frame(aggregated)] == [frame(ToClient(aggregated))]
    runtime._publish_tallies()
    t = runtime._tallies
    read = cmd.read_only
    assert (t["gets_replied"], t["commands_completed"], t["replied"]) == (int(read), 1, 1)
    carried = sum(len(v.encode()) for v in values if v is not None)
    assert t["get_value_bytes"] == (carried if read else 0)
    assert not _in_flight(runtime, [session])


def _aggregating_reference(process_id, shard_ids, cmds, results):
    """The parent's reply stage, kept here as the reference: an
    ``AggregatePending`` a shard, a key -> shard map a command, and the
    frame of every ``CommandResult`` that completes, in the round's
    order.  Nothing of it is the session's code."""
    from fantoch_tpu.executor.aggregate import AggregatePending
    from fantoch_tpu.run.prelude import ToClient
    from fantoch_tpu.run.rw import frame, reply_frame

    pending = {sid: AggregatePending(process_id, sid) for sid in shard_ids}
    key_shard = {}
    for cmd in cmds:
        for sid in cmd.shards():
            pending[sid].wait_for(cmd)
        key_shard[cmd.rifl] = {key: sid for sid, key in cmd.all_keys()}
    frames = []
    for result in results:
        done = pending[key_shard[result.rifl][result.key]].add_executor_result(result)
        if done is not None:
            frames.append(frame(ToClient(done)))
            assert frames[-1] == reply_frame(done)
    return frames


_SHAPES = {
    "one-key": {0: ("k\u00fc",)},
    "two-keys-two-shards": {0: ("a",), 1: ("b\u00e9",)},
    "two-keys-one-shard": {1: ("a", "b")},
    "three-keys-two-and-one": {0: ("a", "b"), 1: ("c",)},
}


@pytest.mark.parametrize("op, value", [
    (KVOp.put("v" * 100), "previous"),
    (KVOp.get(), "v" * 1000),
    (KVOp.get(), None),
    (KVOp.put("gr\u00f6\u00dfer \u2603 \U0001f600"), "wert \u00e9\u4e2d"),
], ids=["put", "get-hit", "get-miss", "non-ascii"])
@pytest.mark.parametrize("shape", list(_SHAPES))
def test_a_rounds_frames_are_the_aggregating_references_byte_for_byte(shape, op, value):
    """Whatever a command's shape, what its session writes for a round is
    what the parent's aggregation wrote: the same frames, one a touched
    shard, in the order their last partials land."""
    runtime, (session,), (writer,) = _reply_stage(shard_count=2, connections=1)
    rifl = Rifl(2**40 + 7, 2**33)
    cmd = Command(rifl, {sid: {key: (op,) for key in keys} for sid, keys in _SHAPES[shape].items()})
    neighbour = Command.from_single(Rifl(2**40 + 7, 1), 1, "n", op)
    for tracked in (cmd, neighbour):
        session.track(tracked)
    assert set(session._owed) == ({rifl} if cmd.total_key_count > 1 or cmd.shard_count > 1 else set())
    # the partials land last key first, the neighbour's between them
    results = [_partial(cmd, key, value) for _sid, key in reversed(list(cmd.all_keys()))]
    results.insert(1, _partial(neighbour, "n", value))
    runtime._deliver(results)
    assert len(writer.writes) == 1
    expected = _aggregating_reference(runtime.process_id, (0, 1), [cmd, neighbour], results)
    assert writer.writes[0] == b"".join(expected)
    assert len(expected) == cmd.shard_count + 1
    runtime._publish_tallies()
    t = runtime._tallies
    alone = sum(len(keys) == 1 for keys in _SHAPES[shape].values())
    assert (t["shard_replies"], t["reply_partial_frames"]) == (cmd.shard_count + 1, alone + 1)
    assert t["reply_flat_frames"] == 1 + (shape == "one-key")
    assert (t["commands_completed"], t["multi_shard_completed"]) == (2, int(cmd.shard_count > 1))
    assert not _in_flight(runtime, [session])


def _one_key_shapes(shard_count):
    """One-key commands over every shard a client could name, and shapes
    that have one key in all but not one shard."""
    put, get = KVOp.put("v"), KVOp.get()
    cmds = [
        Command.from_single(Rifl(1, seq), sid, f"k{seq}", op)
        for seq, (sid, op) in enumerate(
            (sid, op) for sid in (-1, 0, 1, 3, 4, 7) for op in (put, get)
        )
    ]
    cmds.append(Command(Rifl(1, 100), {0: {"a": (put,)}, 1: {}}))
    cmds.append(Command(Rifl(1, 101), {shard_count: {"a": (put,)}, 0: {}}))
    return cmds


def _several_key_shapes(shard_count, key_buckets=64):
    """Commands of several keys: what the server takes as it stands, and
    every way ``_validate`` has of refusing one."""
    from fantoch_tpu.run.device_drivers import _bucket

    put, get = KVOp.put("v"), KVOp.get()

    def bucket(key):  # every key of these names is on shard 0
        return _bucket(0, key, key_buckets, shard_count)

    names = [f"c{i}" for i in range(200)]
    apart = []  # three keys in three buckets, then one that shares the first's
    for key in names:
        if len(apart) < 3 and all(bucket(key) != bucket(other) for other in apart):
            apart.append(key)
    same = next(key for key in names if key != apart[0] and bucket(key) == bucket(apart[0]))
    last = shard_count - 1
    return [
        Command(Rifl(2, 1), {0: {"a": (put,), "b": (put,)}}),  # two keys, one shard
        Command(Rifl(2, 2), {0: {"a": (get,)}, last: {"b": (get,)}}),  # ... two shards (or one)
        Command(Rifl(2, 3), {0: {"a": (put,)}, shard_count: {"b": (put,)}}),  # a shard out of range
        Command(Rifl(2, 4), {-1: {"a": (get,), "b": (get,)}}),
        Command(Rifl(2, 5), {0: {"a": (put,)}, 1: {"b": (put,)}}),  # two shards, whatever the server has
        Command(Rifl(2, 6), {0: {}}),  # a shard with no keys
        Command(Rifl(2, 7), {0: {}, last: {}}),
        # three keys at width 2: three buckets, and two where two of them collide
        Command(Rifl(2, 8), {0: {key: (get,) for key in apart}}),
        Command(Rifl(2, 9), {0: {key: (put,) for key in (apart[0], apart[1], same)}}),
    ]


@pytest.mark.parametrize("shard_count", [1, 4])
def test_admits_one_key_branch_rejects_what_validate_rejects_in_its_words(shard_count):
    runtime, (session,), (writer,) = _reply_stage(shard_count, connections=1)
    pushed, rejected = [], {}
    runtime.submit_all = lambda admitted, now_ms: pushed.extend(cmd for _dot, cmd in admitted)
    reject = session._reject
    session._reject = lambda cmd, why: (rejected.__setitem__(cmd.rifl, why), reject(cmd, why))
    cmds = _one_key_shapes(shard_count) + _several_key_shapes(shard_count)
    reasons = {cmd.rifl: session._validate(cmd) for cmd in cmds}
    session._admit(list(cmds))
    assert rejected == {rifl: why for rifl, why in reasons.items() if why is not None}
    assert [cmd.rifl for cmd in pushed] == [r for r, why in reasons.items() if why is None]
    assert rejected and pushed
    # every way _validate has of refusing a command of several keys, in its words
    said = {why.split(" but ")[0].split(" shard ")[0] for rifl, why in rejected.items() if rifl[0] == 2}
    assert said == {"command touches no keys", "command touches 3 key buckets"} | (
        {"multi-shard command submitted to a single-shard device server"} if shard_count == 1
        else {"command names"})
    assert {Rifl(2, 1), Rifl(2, 9)} <= {cmd.rifl for cmd in pushed} and Rifl(2, 8) in rejected
    # a rejection is answered with an empty result and leaves nothing behind
    assert [_fields(t) for t in _frames(b"".join(writer.writes))] == [
        (rifl, 0, {}, True) for rifl in rejected]
    assert set(runtime.rifl_sessions) == {cmd.rifl for cmd in pushed}
    assert session._reads == {cmd.rifl for cmd in pushed if cmd.read_only}
    runtime._publish_tallies()
    flat = [cmd for cmd in pushed if cmd.single_key() is not None]
    assert runtime._tallies["session_flat_admitted"] == len(flat) > 0
    assert set(session._owed) == {cmd.rifl for cmd in pushed} - {cmd.rifl for cmd in flat}
    # a shed command leaves as little as a rejected one
    held = _in_flight(runtime, [session])
    runtime.room = lambda: 0
    shed = [Command(Rifl(9, 1), {0: {"a": (KVOp.get(),), "b": (KVOp.get(),)}}),
            Command.from_single(Rifl(9, 2), 0, "a", KVOp.get())]
    session._admit(list(shed))
    assert runtime._submit_queue.sheds == 2 and len(pushed) == len(reasons) - len(rejected)
    assert _in_flight(runtime, [session]) == held


@pytest.mark.parametrize("shards, order", [
    ((("k0",),), "k0 k0"),
    ((("k0", "k1"),), "k0 k1 k0 k1"),
    ((("k0", "k1"),), "k0 k0 k1 k1"),
    ((("k0",), ("k1",)), "k0 k1 k0 k1"),
    ((("k0",), ("k1",)), "k0 k0 k1 k1"),
], ids=["one-key", "two-key", "two-key-pairwise", "two-shard", "two-shard-pairwise"])
def test_the_same_rifl_twice_in_one_round_is_answered_once(shards, order):
    runtime, (session,), (writer,) = _reply_stage(shard_count=2, connections=1)
    put = KVOp.put("v")
    cmd = Command(Rifl(5, 1), {sid: {key: (put,) for key in keys} for sid, keys in enumerate(shards)})
    session.track(cmd)
    session.track(cmd)  # the client sent it again before its reply
    seen, results = set(), []
    for key in order.split():
        results.append(_partial(cmd, key, "second" if key in seen else "first"))
        seen.add(key)
    runtime._deliver(results)
    # one reply a shard, of the first execution's values
    assert [_fields(reply) for reply in _frames(b"".join(writer.writes))] == [
        (cmd.rifl, len(keys), {key: ("first",) for key in keys}, True) for keys in shards]
    assert runtime.replied == 1
    runtime._publish_tallies()
    t = runtime._tallies
    assert t["reply_flat_frames"] == (1 if order == "k0 k0" else 0)
    assert t["reply_partial_frames"] == sum(len(keys) == 1 for keys in shards)
    assert (t["commands_completed"], t["multi_shard_completed"]) == (1, len(shards) - 1)
    assert not _in_flight(runtime, [session])
    runtime._deliver(results[-2:])  # ... and a round later: nowhere to go
    assert len(writer.writes) == 1


def _one_session_round(shard_count):
    """One session's commands, one-key and two-key, reads among both, and
    one round's partials of them, the two-key ones' apart."""
    put, get = KVOp.put("v" * 10), KVOp.get()
    other = shard_count - 1
    cmds = [
        Command.from_single(Rifl(3, 1), 0, "a", put),
        Command(Rifl(3, 2), {0: {"b": (put,)}, other: {"c": (put,)}} if other
                else {0: {"b": (put,), "c": (put,)}}),
        Command.from_single(Rifl(3, 3), other, "d", get),
        Command(Rifl(3, 4), {0: {"e": (get,), "f": (get,)}}),
        Command.from_single(Rifl(3, 5), 0, "g", put),
    ]
    a, two, d, reads, g = cmds
    results = [
        _partial(two, "c", "c0"), _partial(a, "a", None), _partial(reads, "f", "f0"),
        _partial(d, "d", "d0"), _partial(two, "b", "b0"), _partial(g, "g", "g0"),
        _partial(reads, "e", None),
    ]
    return cmds, results


@pytest.mark.parametrize("shard_count", [1, 2])
def test_one_key_and_two_key_replies_of_a_round_keep_its_execution_order(shard_count):
    runtime, (session,), (writer,) = _reply_stage(shard_count, connections=1)
    cmds, results = _one_session_round(shard_count)
    for cmd in cmds:
        session.track(cmd)
    assert set(session._owed) == {Rifl(3, 2), Rifl(3, 4)}
    runtime._deliver(results)
    assert len(writer.writes) == 1
    got = [_fields(t) for t in _frames(writer.writes[0])]
    if shard_count == 1:
        first, second = [], [(Rifl(3, 2), 2, {"c": ("c0",), "b": ("b0",)}, True)]
    else:  # one CommandResult a shard, each when its shard's keys are in
        first = [(Rifl(3, 2), 1, {"c": ("c0",)}, True)]
        second = [(Rifl(3, 2), 1, {"b": ("b0",)}, True)]
    assert got == [
        *first,
        (Rifl(3, 1), 1, {"a": (None,)}, True),
        (Rifl(3, 3), 1, {"d": ("d0",)}, True),
        *second,
        (Rifl(3, 5), 1, {"g": ("g0",)}, True),
        (Rifl(3, 4), 2, {"f": ("f0",), "e": (None,)}, True),
    ]
    assert got == [_fields(t) for t in _one_at_a_time({0: cmds}, [results])[0]]
    runtime._publish_tallies()
    t = runtime._tallies
    assert (t["reply_flat_frames"], t["shard_replies"]) == (3, 4 + shard_count)
    assert (t["commands_completed"], t["multi_shard_completed"]) == (5, shard_count - 1)
    assert (t["gets_replied"], t["get_value_bytes"]) == (2, 4)


@pytest.mark.parametrize("end", ["replied", "dropped"])
def test_nothing_is_left_of_a_command_after_its_reply_or_its_sessions_drop(end):
    runtime, sessions, writers = _reply_stage(shard_count=2, connections=2)
    cmds, results = _one_session_round(2)
    other = [Command.from_single(Rifl(4, 1), 1, "z", KVOp.get())]
    for cmd in cmds:
        sessions[0].track(cmd)
    sessions[1].track(other[0])
    held = _in_flight(runtime, sessions)
    assert {name[1] if isinstance(name, tuple) else name for name in held} == {
        "rifl_sessions", "_owed", "_reads"}
    if end == "replied":
        runtime._deliver(results + [_partial(other[0], "z", None)])
        assert runtime.replied == 6
    else:
        runtime._deliver(results[:3])  # a two-key command half answered, then the close
        runtime.drop_session(sessions[0])
        assert set(runtime.rifl_sessions) == {Rifl(4, 1)}
        runtime._deliver(results[3:])  # executed for the cluster, answered to no one
        assert len(writers[0].writes) == 1
        runtime.drop_session(sessions[1])
    assert not _in_flight(runtime, sessions)


@pytest.mark.parametrize("shard_count", [1, 2])
def test_the_flat_counters_count_one_key_commands_from_admit_to_reply(shard_count):
    """Three one-key commands beside a two-key one on one shard and, where
    the server has two, one over both: ``reply_flat_frames`` counts the
    one-key commands alone, ``reply_partial_frames`` every frame made from
    one partial, ``shard_replies`` all."""
    runtime, (session,), (writer,) = _reply_stage(shard_count=shard_count, connections=1)
    runtime.submit_all = lambda admitted, now_ms: None
    cmds, results = _one_session_round(shard_count)
    session._admit(cmds[:3])
    session._admit(cmds[3:])
    runtime._publish_tallies()
    assert runtime._tallies["session_flat_admitted"] == 3
    assert runtime._tallies["reply_flat_frames"] == 0
    runtime._deliver(results[:4])
    runtime._deliver(results[4:])
    runtime._publish_tallies()
    t = runtime._tallies
    assert (t["session_flat_admitted"], t["reply_flat_frames"]) == (3, 3)
    # ... and both shards of the command over two, each from its one partial
    assert t["reply_partial_frames"] == 3 + (2 if shard_count == 2 else 0)
    assert t["shard_replies"] == t["reply_plain_frames"] == 4 + shard_count
    assert t["replied"] == t["commands_completed"] == 5
    assert t["multi_shard_completed"] == shard_count - 1
    assert not _in_flight(runtime, [session])


@pytest.mark.parametrize("results, key_count", [
    ({}, 0),  # _reject's CommandResult(rifl, 0)
    ({"k": ("previous",)}, 1),
    ({"a": ("x" * 100,), "b": ("y", "z"), "c": (None,)}, 3),
    ({"k": (None,)}, 1),
    ({"a": ("x",)}, 2),  # a partial that is not ready stays not ready
], ids=["empty", "one-key", "three-keys", "none-previous", "not-ready"])
def test_the_compact_reply_pickle_round_trips(results, key_count):
    import pickle

    from fantoch_tpu.core.command import CommandResult
    from fantoch_tpu.run import rw
    from fantoch_tpu.run.prelude import ToClient

    result = CommandResult(Rifl(2**40 + 7, 2**33), key_count)
    for key, values in results.items():
        result.add_partial(key, values)
    payload = rw.serialize(ToClient(result))
    back = rw.deserialize(payload)
    assert type(back) is ToClient and type(back.cmd_result) is CommandResult
    assert type(back.cmd_result.rifl) is Rifl
    assert _fields(back) == (Rifl(2**40 + 7, 2**33), key_count, results, len(results) == key_count)
    assert list(back.cmd_result.results) == list(results)  # key order too
    # a CommandResult on its own (the simulator's recorders) takes the same form
    alone = pickle.loads(pickle.dumps(result))
    assert _fields(ToClient(alone)) == _fields(back)
    # the frame carries values, not the classes' paths and attribute names
    for word in (b"CommandResult", b"_key_count", b"_results", b"fantoch_tpu.core.ids"):
        assert word not in payload
    assert rw.frame(ToClient(result)) == rw._LEN.pack(len(payload)) + payload
