"""What a `--device-step` server does before its first client, and what it
says of itself: every chain length of the tuner's ladder compiled or loaded
in start-up so that the serving loop compiles nothing, whole shards on a
device where the devices can hold them, and the reply stage's count of a
command over several shards."""

import asyncio

import jax
import numpy as np
import pytest

from fantoch_tpu.client import ConflictRateKeyGen, Workload
from fantoch_tpu.core import Command, Config, Dot, KVOp, Rifl
from fantoch_tpu.observability import device as obs
from fantoch_tpu.parallel import mesh_step
from fantoch_tpu.run.device_runner import DeviceRuntime, NewtDeviceDriver
from fantoch_tpu.run.harness import free_port, run_device_server
from fantoch_tpu.run.ingest import ChainAutoTuner

LADDER = [1, 2, 4, 8]


# --- the mesh: whole shards on a device ---------------------------------------


@pytest.mark.parametrize("devices,rows,shards,want", [
    (4, 20, 4, (4, 1)),   # the deployment: one shard a chip
    (8, 20, 4, (4, 2)),   # one shard a replica slice, the batch over two
    (4, 12, 4, (4, 1)),
    (4, 10, 2, (2, 2)),   # two shards on four devices: as before
    (8, 6, 2, (2, 4)),
    (2, 20, 4, (2, 1)),   # two whole shards a device
    (8, 12, 3, (2, 4)),   # no factor of 8 divides 3 shards: the old rule
    (1, 20, 4, (1, 1)),
])
def test_make_mesh_puts_whole_shards_on_a_device_where_the_devices_can_hold_them(
        devices, rows, shards, want):
    mesh = mesh_step.make_mesh(devices, num_replicas=rows, shard_count=shards)
    assert (mesh.shape["replica"], mesh.shape["batch"]) == want
    held = mesh_step.shards_on_devices(mesh, rows, shards)
    assert len(held) == devices
    if shards % want[0] == 0:  # each device holds whole shards, each shard is on one slice
        assert all(len(on) == shards // want[0] for on in held)
        assert sorted({s for on in held for s in on}) == list(range(shards))


@pytest.mark.parametrize("devices", [1, 2, 4, 8])
@pytest.mark.parametrize("rows", [None, 3, 4, 5, 6, 7, 8])
def test_a_one_shard_deployment_gets_the_mesh_it_got(devices, rows):
    """The rule of every PR before this one, written out again."""
    replica = next(cand for cand in range(min(devices, 8), 0, -1)
                   if devices % cand == 0 and cand <= devices // cand
                   and (rows is None or rows % cand == 0))
    mesh = mesh_step.make_mesh(devices, num_replicas=rows)
    assert (mesh.shape["replica"], mesh.shape["batch"]) == (replica, devices // replica)
    assert mesh_step.shards_on_devices(mesh, rows or replica, 1) == [[0]] * devices


def test_shards_on_devices_names_the_layouts_the_cell_was_run_at():
    one_a_chip = mesh_step.make_mesh(4, num_replicas=20, shard_count=4)
    assert mesh_step.shards_on_devices(one_a_chip, 20, 4) == [[0], [1], [2], [3]]
    old_rule = mesh_step.make_mesh(4, num_replicas=20)  # what PR 26's four-chip runs were on
    assert (old_rule.shape["replica"], old_rule.shape["batch"]) == (2, 2)
    assert mesh_step.shards_on_devices(old_rule, 20, 4) == [[0, 1], [0, 1], [2, 3], [2, 3]]


# --- the ladder: ready before serving, nothing compiled after ------------------


def _tallies():
    return (obs.recompile_count(), obs.compile_ms(), obs.cache_hit_count(),
            obs.cache_miss_count())


def _batch(driver, first, count):
    return [(Dot(1, first + i), Command.from_single(Rifl(7, first + i), 0, f"k{(first + i) % 5}",
                                                     KVOp.put("v")))
            for i in range(count)]


@pytest.mark.parametrize("shards,width", [(1, 1), (4, 2)], ids=["one_shard", "four_shards_two_keys"])
def test_after_the_precompile_no_dispatch_of_any_ladder_length_compiles(shards, width):
    """`_enqueue` runs loaded executables: the process's compile,
    compile-time and cache tallies stand still over dispatches of every
    length, with and without overlap."""
    obs.subscribe_recompiles()
    driver = NewtDeviceDriver(5, f=1, batch_size=8, key_buckets=64, key_width=width,
                              pending_capacity=8, shard_count=shards)
    assert driver.precompiled_programs == 0
    assert driver.precompile_chains(LADDER) == LADDER
    assert driver.precompiled_programs == 4 and driver.stages.n["precompile"] == 4
    assert [span[3] for span in driver.stages.ring if span[0] == "precompile"] == LADDER
    before = _tallies()
    assert driver.precompile_chains(LADDER) == LADDER  # ready already: nothing to do
    executed, at = 0, 0
    for length in LADDER + LADDER[::-1]:
        chain = [_batch(driver, at + r * 8, 8) for r in range(length)]
        at += length * 8
        executed += len(driver.serve(chain))
        executed += len(driver.serve(
            [_batch(driver, at + r * 8, 5) for r in range(length)], overlap=True))
        at += length * 8
    executed += len(driver.flush_pipeline())
    assert _tallies() == before
    assert executed == 2 * (8 + 5) * sum(LADDER) and driver.in_flight == 0


def test_a_length_that_cannot_be_made_ready_keeps_the_tuner_below_it(monkeypatch):
    real = mesh_step.jit_newt_multi_step

    def jit_newt_multi_step(mesh, **kwargs):
        chain = real(mesh, **kwargs).__wrapped__

        def refuses(state, keys, *rest):  # where the driver lowers its packed program
            if keys.shape[0] >= 4:
                raise RuntimeError("RESOURCE_EXHAUSTED: no room for this program")
            return chain(state, keys, *rest)

        return jax.jit(refuses, donate_argnums=(0,))

    monkeypatch.setattr(mesh_step, "jit_newt_multi_step", jit_newt_multi_step)
    driver = NewtDeviceDriver(3, f=1, batch_size=8, key_buckets=64, pending_capacity=8)
    assert driver.precompile_chains(LADDER) == [1, 2]
    tuner = ChainAutoTuner(8)
    assert tuner.ladder() == LADDER
    tuner.chain = 8
    tuner.limit_to([1, 2])
    assert (tuner.chain, tuner.chain_max, tuner.ladder()) == (2, 2, [1, 2])
    tuner.limit_to([1, 4, 8])  # a gap: the ladder is climbed a rung at a time
    assert (tuner.chain, tuner.chain_max) == (1, 1)


def test_a_server_has_every_chain_program_before_it_listens_and_says_how_long_that_took(tmp_path):
    """`start()` loads the ladder before it binds the port; the snapshot
    carries the stage, the gauge and the layout, and serving two-key
    commands over two shards afterwards compiles nothing."""
    import json

    obs.subscribe_recompiles()

    async def go():
        runtime = DeviceRuntime(
            Config(3, 1, shard_count=2), ("127.0.0.1", free_port()), protocol="newt",
            batch_size=8, key_buckets=64, key_width=2, pending_capacity=8,
            metrics_file=str(tmp_path / "snap.json"),
        )
        runtime._write_metrics_snapshot()
        with open(tmp_path / "snap.json") as fh:
            first = json.load(fh)
        await runtime.start()
        started = _tallies()
        for i in range(40):
            cmd = Command.from_single(Rifl(9, i + 1), i % 2, f"k{i % 7}", KVOp.put("v"))
            runtime.submit(runtime.dot_gen.next_id(), cmd)
        for _ in range(1500):
            if runtime.failure is not None:
                raise runtime.failure
            if runtime.driver.executed >= 40 and not runtime.driver.has_outstanding:
                break
            await asyncio.sleep(0.02)
        served = _tallies()
        await runtime.stop()
        with open(tmp_path / "snap.json") as fh:
            return runtime, first, json.load(fh), started, served

    runtime, first, last, started, served = asyncio.run(go())
    assert first["precompiled_programs"] == 0 and first["stage_precompile_n"] == 0
    assert last["precompiled_programs"] == 4 and last["stage_precompile_n"] == 4
    assert last["stage_precompile_ms"] > 0
    assert runtime._chain_tuner.chain_max == 8
    assert served == started and runtime.driver.executed == 40
    assert last["backend"]["mesh_shape"] == {"replica": 2, "batch": 4}
    assert last["backend"]["shards_on_device"] == [[0]] * 4 + [[1]] * 4
    assert "resolver" not in last["backend"]  # the dep-commit round's alone
    for key in ("shard_replies", "commands_completed", "multi_shard_completed"):
        assert first[key] == 0


# --- the reply stage's counters -------------------------------------------------


def test_the_reply_stage_counts_a_command_over_two_shards_once_and_its_replies_twice():
    config = Config(3, 1, shard_count=2)
    workload = Workload(shard_count=2, key_gen=ConflictRateKeyGen(50), keys_per_command=2,
                        commands_per_client=25, payload_size=1)
    runtime, clients = asyncio.run(run_device_server(
        config, workload, client_count=4, batch_size=32, key_width=2, key_buckets=64,
        protocol="newt"))
    tallies = runtime._tallies
    assert tallies["commands_completed"] == tallies["replied"] == 100
    multi = tallies["multi_shard_completed"]
    assert 0 < multi < 100
    assert tallies["shard_replies"] == 100 + multi  # one CommandResult a touched shard
    assert runtime.failure is None


def test_a_one_shard_server_completes_every_command_with_one_reply():
    config = Config(3, 1)
    workload = Workload(shard_count=1, key_gen=ConflictRateKeyGen(50), keys_per_command=2,
                        commands_per_client=25, payload_size=1)
    runtime, _ = asyncio.run(run_device_server(
        config, workload, client_count=2, batch_size=16, key_width=2, key_buckets=64))
    tallies = runtime._tallies
    assert tallies["shard_replies"] == tallies["commands_completed"] == tallies["replied"] == 50
    # the dep-commit round is one program, made ready before the server listens
    assert tallies["multi_shard_completed"] == 0 and tallies["precompiled_programs"] == 1
