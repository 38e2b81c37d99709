"""A plain round made ready before the first client (`_DriverCore._precompile`,
beside `tests/test_chain_precompile.py`, which holds the Newt ladder): the
Caesar driver's round, the leader round of the Paxos driver (with its own
columns: `valid` is `bool`) and the dep-commit round with its `read` column,
under either quorum rule and at key width 1 and 2, are compiled or loaded
inside `DeviceRuntime.start()`, so their first dispatch compiles nothing; and
the two tests that count the plane programs' jit signatures still pass after
a precompile in the same process."""

import asyncio
import json

import pytest

from fantoch_tpu.core import Command, Config, Dot, KVOp, Rifl
from fantoch_tpu.observability import device as obs
from fantoch_tpu.run.device_runner import (
    CaesarDeviceDriver, DeviceDriver, DeviceRuntime, PaxosDeviceDriver,
)
from fantoch_tpu.run.harness import free_port
from tests.test_chain_precompile import LADDER, _tallies


def _batch(first, count):
    """Writes of one key, every third command a read of it."""
    return [(Dot(1, first + i), Command.from_single(
        Rifl(7, first + i), 0, f"k{(first + i) % 5}",
        KVOp.get() if (first + i) % 3 == 0 else KVOp.put("v")))
            for i in range(count)]


# the drivers whose plain round is made ready ahead, and the tally that counts their
# commits: every Caesar command is fast while seven are live, and the leader class has
# one path, which the tally calls slow
AHEAD = {
    "caesar": (lambda: CaesarDeviceDriver(7, batch_size=8, key_buckets=64, pending_capacity=8),
               "fast_paths"),
    "fpaxos": (lambda: PaxosDeviceDriver(5, f=1, batch_size=8, pending_capacity=8), "slow_paths"),
    # the dep-commit round, its read column among the precompiled shapes
    "epaxos": (lambda: DeviceDriver(5, batch_size=8, key_buckets=64, pending_capacity=8),
               "fast_paths"),
    "atlas": (lambda: DeviceDriver(5, f=1, rule="atlas", batch_size=8, key_buckets=64,
                                   pending_capacity=8), "fast_paths"),
    "atlas_2key": (lambda: DeviceDriver(5, f=1, rule="atlas", batch_size=8, key_buckets=64,
                                        key_width=2, pending_capacity=8), "fast_paths"),
}
SERVED = ("caesar", "fpaxos", "epaxos", "atlas")
ahead = pytest.mark.parametrize("protocol", AHEAD)


@ahead
def test_the_round_is_one_program_ready_once_and_then_dispatches_compile_nothing(protocol):
    obs.subscribe_recompiles()
    build, tally = AHEAD[protocol]
    driver = build()
    assert driver.precompiled_programs == 0 and driver.stages.n["precompile"] == 0
    assert driver.precompile_chains(LADDER) == LADDER  # a chain is S plain rounds
    assert driver.precompiled_programs == 1 and driver.stages.n["precompile"] == 1
    before = _tallies()
    assert driver.precompile_chains(LADDER) == LADDER  # ready already: nothing to do
    executed = len(driver.step(_batch(0, 8)))
    executed += len(driver.serve([_batch(8, 5)], overlap=True))
    executed += len(driver.serve([_batch(16, 8), _batch(24, 3)]))
    executed += len(driver.serve([_batch(32, 8), _batch(40, 8)], overlap=True))
    executed += len(driver.flush_pipeline())
    assert _tallies() == before and driver.stages.n["precompile"] == 1
    assert executed == 40 and driver.in_flight == 0 and getattr(driver, tally) == 40


def _newt():
    from fantoch_tpu.run.device_runner import NewtDeviceDriver

    return NewtDeviceDriver(5, f=1, batch_size=8, key_buckets=64, pending_capacity=8)


@pytest.mark.parametrize("protocol", [*AHEAD, "newt"])
def test_a_driver_stepped_without_a_start_up_reaches_the_same_program(protocol):
    """One way into a ready round: a driver that no server started makes
    its round's program ready at its first `step`, by the route `start()`
    takes (`_precompile`, under the `precompile` span), and keeps it: the
    second `step` compiles nothing, and neither does a `precompile_chains`
    of the length it already has."""
    obs.subscribe_recompiles()
    driver = AHEAD[protocol][0]() if protocol in AHEAD else _newt()
    assert driver.precompiled_programs == 0 and driver.stages.n["precompile"] == 0
    executed = len(driver.step(_batch(0, 8)))
    assert driver.precompiled_programs == 1 and driver.stages.n["precompile"] == 1
    program = driver._program()
    before = _tallies()
    executed += len(driver.step(_batch(8, 8)))
    assert driver.precompile_chains([1]) == [1]
    assert _tallies() == before and driver.stages.n["precompile"] == 1
    assert driver._program() is program and executed == 16


def _serve(protocol, tmp_path, commands=40):
    """A runtime of ``protocol`` started, fed ``commands`` writes, stopped:
    (its first snapshot, its last, the compile tallies when `start()`
    returned, and when everything was executed)."""
    obs.subscribe_recompiles()

    async def go():
        runtime = DeviceRuntime(
            Config(7, 3, leader=1 if protocol == "fpaxos" else None),
            ("127.0.0.1", free_port()), protocol=protocol, batch_size=8, key_buckets=64,
            pending_capacity=8, metrics_file=str(tmp_path / "snap.json"),
        )
        runtime._write_metrics_snapshot()
        with open(tmp_path / "snap.json") as fh:
            first = json.load(fh)
        await runtime.start()
        started = _tallies()
        for i in range(commands):
            cmd = Command.from_single(Rifl(9, i + 1), 0, f"k{i % 7}", KVOp.put("v"))
            runtime.submit(runtime.dot_gen.next_id(), cmd)
        for _ in range(1500):
            if runtime.failure is not None:
                raise runtime.failure
            if runtime.driver.executed >= commands and not runtime.driver.has_outstanding:
                break
            await asyncio.sleep(0.02)
        served = _tallies()
        assert runtime.driver.executed == commands
        await runtime.stop()
        with open(tmp_path / "snap.json") as fh:
            return runtime, first, json.load(fh), started, served

    return asyncio.run(go())


@pytest.mark.parametrize("protocol", SERVED)
def test_a_server_has_its_round_before_it_listens_and_says_how_long_that_took(protocol, tmp_path):
    runtime, first, last, started, served = _serve(protocol, tmp_path)
    assert first["precompiled_programs"] == 0 and first["stage_precompile_n"] == 0
    assert last["precompiled_programs"] == 1 and last["stage_precompile_n"] == 1
    assert last["stage_precompile_ms"] > 0
    assert served == started  # the first dispatch, and every one after it, compiled nothing
    assert runtime._chain_tuner.chain_max == 8  # every chain length is plain rounds: all ready
    # what the round's trace left is frozen out of the collector with the rest of start-up
    assert last["gc_frozen_objects"] > 0 and last[AHEAD[protocol][1]] == 40
    if protocol == "fpaxos":  # what the leader driver knows of its round is in the snapshot
        assert last["backend"]["round"] == "paxos_slot" and last["backend"]["accept_quorum"] == 4
        assert last["requeued"] == 0 and last["device_slot_epochs"] == 0
        assert last["stable_watermark"] == last["executed"] == 40  # a dense log: a slot a command
    else:
        assert "round" not in last["backend"] and "accept_quorum" not in last["backend"]
    if protocol in ("epaxos", "atlas"):  # the dep-commit round says whose quorums it runs
        assert last["backend"]["rule"] == protocol
        # n = 7, f = 3: EPaxos's 3 + 2 and 4, Atlas's 3 + 3 and 4
        assert last["backend"]["quorums"] == {"epaxos": [5, 4], "atlas": [6, 4]}[protocol]
        assert [name for name, *_ in runtime.driver._column_specs()] == ["key", "src", "seq", "read"]
    else:
        assert "rule" not in last["backend"] and "quorums" not in last["backend"]


@pytest.mark.parametrize("key_width", (1, 2))
def test_the_dep_commit_round_takes_its_read_column_at_either_key_width(key_width, tmp_path):
    """One compile a key width: the program is lowered on the four staged
    columns as one array, `read` (`bool[B]` to the round, 0/1 in it) among
    them, and a round with reads and writes then compiles nothing."""
    obs.subscribe_recompiles()
    driver = DeviceDriver(5, f=1, rule="atlas", batch_size=8, key_buckets=64,
                          key_width=key_width, pending_capacity=8)
    assert driver.precompile_chains(LADDER) == LADDER
    assert driver.precompiled_programs == 1 and driver.stages.n["precompile"] == 1
    _program, sharding, layout = driver._programs[1]
    # where it takes its columns: key_width rows of keys, then src, seq and read, along the batch
    assert sharding.shard_shape((key_width + 3, 8))[0] == key_width + 3
    assert layout.fields[3] is None and layout.fields[7] is None  # deps_gid, pending: un-fetched
    before = _tallies()
    assert len(driver.step(_batch(0, 8))) == 8
    assert _tallies() == before
    assert driver.round_tallies["read_rows"] == 3  # commands 0, 3 and 6


def test_the_signature_counting_tests_still_pass_after_a_precompile_in_this_process():
    """`PERF.md` s7's two order-dependent tests count the jit signatures of
    the registered plane programs; lowering and compiling a round ahead of
    time touches no jit's cache."""
    from tests.test_chip_smoke import test_leg_planes_tiny
    from tests.test_compile_cache import test_plane_sweep_compiles_each_program_once

    driver = CaesarDeviceDriver(7, batch_size=8, key_buckets=64, pending_capacity=8)
    driver.precompile_chains(LADDER)
    assert driver.precompiled_programs == 1
    test_plane_sweep_compiles_each_program_once()
    test_leg_planes_tiny()
