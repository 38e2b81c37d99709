"""A client's site on the served path (`ClientHi.site`,
`_DeviceClientSession.run`, `DeviceRuntime.register_site`,
`DeviceDriver.register_site`): a hello that names none serves as before, on
the same program; the first site but 0 makes the round with a coordinator at
every site ready before its hello is acknowledged and the next dispatch runs
it; a site that cannot be served ends the session before the ack; a dot is a
coordinator's, one sequence a site.  (A file of its own: `--dist loadfile`
keeps a file on one worker.)"""

import asyncio
import pickle

import numpy as np
import pytest

from fantoch_tpu.core import Command, Config, Dot, KVOp, Rifl
from fantoch_tpu.observability import device as obs
from fantoch_tpu.parallel import mesh_step
from fantoch_tpu.run.device_drivers import (
    CaesarDeviceDriver, DeviceDriver, NewtDeviceDriver, PaxosDeviceDriver, _DriverCore,
)
from fantoch_tpu.run.device_runner import DeviceRuntime
from fantoch_tpu.run.harness import free_port
from fantoch_tpu.run.prelude import ClientHi, ClientHiAck, Submit, ToClient
from fantoch_tpu.run.rw import Rw


def _put(client, seq, key):
    return Command.from_single(Rifl(client, seq), 0, key, KVOp.put(f"{client}:{seq}"))


def _runtime(protocol="epaxos", n=5, **kwargs):
    config = Config(n, kwargs.pop("f", 1), shard_count=kwargs.pop("shard_count", 1))
    port = free_port()
    runtime = DeviceRuntime(config, ("127.0.0.1", port), protocol=protocol, batch_size=16,
                            key_buckets=64, pending_capacity=16, **kwargs)
    return runtime, port


async def _hello(port, hi):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    rw = Rw(reader, writer)
    await rw.send(hi)
    return rw, writer, await rw.recv()


async def _call(rw, cmd):
    await rw.send(Submit(cmd))
    reply = await rw.recv()
    assert isinstance(reply, ToClient) and reply.cmd_result.rifl == cmd.rifl
    return reply


def _put_keys(client, seq, shard_count, key_width):
    """A write of one key on shard 0 and, at key width 2, one on the last shard."""
    op = (KVOp.put(f"{client}:{seq}"),)
    if key_width == 1:
        return Command(Rifl(client, seq), {0: {"a": op}})
    if shard_count == 1:
        return Command(Rifl(client, seq), {0: {"a": op, "b": op}})
    return Command(Rifl(client, seq), {0: {"a": op}, shard_count - 1: {"b": op}})


async def _call_shards(rw, cmd):  # a reply a shard touched
    await rw.send(Submit(cmd))
    for _ in range(len(cmd._shard_to_ops)):
        reply = await rw.recv()
        assert isinstance(reply, ToClient) and reply.cmd_result.rifl == cmd.rifl


def test_a_hello_takes_a_site_and_one_without_is_at_site_0():
    assert ClientHi([1, 2]).site == 0 and ClientHi([1], site=3).site == 3
    # a hello framed by a client that knows no site (its pickle holds no such attribute)
    old = ClientHi([7])
    del old.__dict__["site"]
    assert "site" not in pickle.loads(pickle.dumps(old)).__dict__
    assert pickle.loads(pickle.dumps(old)).site == 0


def test_a_hello_without_a_site_serves_as_before_on_the_same_program():
    obs.subscribe_recompiles()

    async def go():
        runtime, port = _runtime()
        await runtime.start()
        try:
            driver = runtime.driver
            program = driver._program(1)
            assert driver.precompiled_programs == 1 and driver.sites_registered == 1
            for client in (1, 2):
                rw, writer, ack = await _hello(port, ClientHi([client]))
                assert isinstance(ack, ClientHiAck)
                await _call(rw, _put(client, 1, "k"))
                writer.close()
            assert driver._program(1) is program and driver._site_program is None
            assert driver.precompiled_programs == 1 and driver.stages.n["precompile"] == 1
            assert driver.sites_registered == 1 and driver.resolver == "run_position"
            assert runtime._tallies["sites_registered"] == 1
            assert runtime._tallies["scc_rows"] == runtime._tallies["finisher_rows"] == 0
            assert driver.executed == 2 and driver.slow_paths == 0
        finally:
            await runtime.stop()

    asyncio.run(go())


def test_a_second_sites_hello_makes_the_second_program_ready_before_its_ack():
    obs.subscribe_recompiles()

    async def go():
        runtime, port = _runtime()
        await runtime.start()
        try:
            driver = runtime.driver
            one_site = driver._program(1)
            rw0, writer0, ack = await _hello(port, ClientHi([1]))
            await _call(rw0, _put(1, 1, "k"))
            assert driver._site_program is None and driver.stages.n["precompile"] == 1
            rw2, writer2, ack = await _hello(port, ClientHi([2], site=2))
            # the ack came after the program: nothing was dispatched in between
            assert isinstance(ack, ClientHiAck)
            assert driver._site_program is not None and driver.stages.n["precompile"] == 2
            assert driver.precompiled_programs == 2 and driver.sites_registered == 2
            assert driver._program(1) is driver._site_program is not one_site
            compiled = obs.recompile_count() + obs.cache_hit_count()
            rounds = driver.rounds
            # the next dispatch runs it: the two sites' commands on one key disagree
            for seq in range(2, 6):
                await asyncio.gather(_call(rw0, _put(1, seq, "k")), _call(rw2, _put(2, seq, "k")))
            assert driver.rounds > rounds and driver.round_tallies["resolve_iters"] > 0
            assert driver.round_tallies["resolve_iters"] == driver.rounds - rounds
            assert obs.recompile_count() + obs.cache_hit_count() == compiled  # nothing since
            assert driver.executed == 9 and driver.in_flight == 0
            assert runtime._tallies["sites_registered"] == 2
            assert runtime.backend_report()["resolver"] == "key_runs"
            # a third site: the program is there
            rw4, writer4, ack = await _hello(port, ClientHi([3], site=4))
            assert isinstance(ack, ClientHiAck) and driver.stages.n["precompile"] == 2
            await _call(rw4, _put(3, 1, "k"))
            assert driver.sites_registered == 3
            for writer in (writer0, writer2, writer4):
                writer.close()
        finally:
            await runtime.stop()
        return runtime

    runtime = asyncio.run(go())
    store = runtime.driver.store
    assert store.execute("k", KVOp.get(), Rifl(9, 9)) is not None


@pytest.mark.parametrize("protocol, kwargs, site, why", [
    ("epaxos", {}, 5, "the sites are the replicas"),
    ("epaxos", {}, -1, "the sites are the replicas"),
    ("epaxos", {}, "1", "a site is a replica's number"),
    ("epaxos", {}, True, "a site is a replica's number"),
    ("newt", {"shard_count": 2}, 1, "one coordinator"),
    ("newt", {"key_width": 2}, 1, "one coordinator"),
    ("newt", {"f": 2}, 5, "the sites are the replicas"),
    ("caesar", {"n": 7, "key_width": 2}, 1, "caesar's under --device-key-width above 1"),
    ("caesar", {"n": 7}, 7, "the sites are the replicas"),
    ("fpaxos", {}, 1, "one coordinator, replica 0 (fpaxos's always"),
    ("atlas", {"f": 2}, 5, "the sites are the replicas"),
    ("atlas", {"f": 2, "shard_count": 2}, -1, "the sites are the replicas"),
    ("atlas", {"f": 2, "key_width": 2}, "1", "a site is a replica's number"),
])
def test_a_site_that_cannot_be_served_is_refused_before_the_ack(protocol, kwargs, site, why, caplog):
    async def go():
        runtime, port = _runtime(protocol, **dict(kwargs))
        await runtime.start()
        try:
            with caplog.at_level("WARNING"):
                _rw, _writer, ack = await _hello(port, ClientHi([1], site=site))
                assert ack is None  # the connection closed: no ClientHiAck
                await asyncio.sleep(0.05)
            assert why in caplog.text
            assert runtime.failure is None and runtime.driver.sites_registered == 1
            assert runtime.driver.precompiled_programs == runtime.driver.stages.n["precompile"]
            # site 0 by name is every driver's, and the server still serves
            rw, writer, ack = await _hello(port, ClientHi([2], site=0))
            assert isinstance(ack, ClientHiAck)
            await _call(rw, _put(2, 1, "k"))
            writer.close()
        finally:
            await runtime.stop()

    asyncio.run(go())


def test_dots_are_a_coordinators_and_two_sites_never_collide_in_a_registry():
    runtime, _port = _runtime()
    gens = [runtime.register_site(site) for site in (0, 3, 0, 3)]
    dots = [gen() for gen in gens for _ in range(3)]
    # one sequence a site, whoever asks: site 0 is the process itself, site 3 process 4
    assert dots[:3] + dots[6:9] == [Dot(1, seq) for seq in range(1, 7)]
    assert dots[3:6] + dots[9:] == [Dot(4, seq) for seq in range(1, 7)]
    assert len(set(dots)) == 12
    # the registries' key of a dot (`_packed`), row by row and as a column
    src = np.array([dot.source for dot in dots], np.int32)
    seq = np.array([dot.sequence for dot in dots], np.int32)
    packed = _DriverCore._packed_column(src, seq, np.arange(12))
    assert len(set(packed)) == 12
    assert packed == [_DriverCore._packed(dot.source, dot.sequence) for dot in dots]
    # and the round reads the coordinator off the dot: the site program's ring
    driver = runtime.driver
    assert driver.site_base == 1 and driver.sites_registered == 2
    batch = [(dot, _put(1 + at, 1, "k")) for at, dot in enumerate(dots)]
    assert len(driver.serve([batch])) == 12
    assert driver.slow_paths > 0 and driver.round_tallies["scc_rows"] > 0


@pytest.mark.parametrize("build", [
    lambda: NewtDeviceDriver(5, batch_size=8, key_buckets=64, pending_capacity=8, shard_count=2),
    lambda: NewtDeviceDriver(5, batch_size=8, key_buckets=64, pending_capacity=8, key_width=2),
    lambda: CaesarDeviceDriver(7, batch_size=8, key_buckets=64, pending_capacity=8, key_width=2),
    lambda: PaxosDeviceDriver(5, batch_size=8, pending_capacity=8),
])
def test_a_driver_with_one_coordinator_takes_site_0_and_no_other(build):
    driver = build()
    driver.register_site(0)
    with pytest.raises(ValueError, match="one coordinator"):
        driver.register_site(1)
    assert driver.sites_registered == 1 and driver.precompiled_programs == 0
    assert not getattr(driver, "serves_sites", False)


@pytest.mark.parametrize("shard_count, key_width", [(1, 1), (2, 2)])
def test_an_atlas_driver_at_f_two_takes_every_site_and_makes_the_second_program_ready(
        shard_count, key_width):
    """Atlas at f = 2 (a ring of four, the threshold over per-site views): the
    hello of site 1 is taken, the second program is ready when it returns, and
    every other site's finds it there; a site that is none of the replicas' is
    still refused."""
    driver = DeviceDriver(5, rule="atlas", f=2, batch_size=8,
                          key_buckets=64, pending_capacity=8, key_width=key_width,
                          shard_count=shard_count)
    assert driver.serves_sites and (driver.fast_quorum, driver.write_quorum) == (4, 3)
    driver.register_site(0)
    assert driver.precompiled_programs == 0
    driver.register_site(1)
    assert driver._site_program is not None and driver.stages.n["precompile"] == 1
    for site in (2, 3, 4):
        driver.register_site(site)
    assert driver.sites_registered == 5 and driver.stages.n["precompile"] == 1
    assert driver.resolver == ("key_runs" if key_width == 1 else "general_components")
    with pytest.raises(ValueError, match="the sites are the replicas"):
        driver.register_site(5)
    batch = [(Dot(1 + at % 5, 1 + at // 5), _put(1 + at, 1, "k")) for at in range(8)]
    assert len(driver.serve([batch])) == 8 and driver.in_flight == 0
    assert driver.round_tallies["split_quorum_rows"] > 0


def test_the_one_site_program_is_the_round_without_a_sites_argument():
    """`sites == 1` traces today's program: the jitted round of a driver that
    no site but 0 registered at is built without the argument, and the second
    program takes the state and the columns of the first."""
    driver = DeviceDriver(5, batch_size=8, key_buckets=64, pending_capacity=8)
    assert "sites" not in driver._step.__wrapped__.keywords
    driver.register_site(2)
    one, two = driver._programs, driver._site_program
    assert not one  # the one-site program was never asked for
    program, sharding, layout = two
    assert sharding.shard_shape((4, 8))[0] == len(driver._column_specs())  # a row a column, one array
    assert layout.type is mesh_step.SiteStepOutput
    assert len(mesh_step.SITE_ROUND_TALLIES) + len(mesh_step.SITE_ROUND_GAUGES) == 15


@pytest.mark.parametrize("protocol, shard_count, key_width", [
    ("atlas", 4, 2), ("epaxos", 4, 2), ("epaxos", 1, 2), ("atlas", 1, 1), ("epaxos", 2, 1),
])
def test_a_hello_with_a_site_is_served_on_a_sharded_server_of_several_keys(
        protocol, shard_count, key_width):
    """Janus* as the benchmark runs it (`--protocol atlas -f 1 --shard-count 4
    --device-key-width 2`), and EPaxos's rule on one shard and four: the second
    program, made with the driver's own shard count, `f`, rule and key width,
    is ready before the hello of a site other than 0 is acknowledged; two
    sites' commands over the same two keys of two shards take their
    coordinators' dots, disagree, and execute."""
    obs.subscribe_recompiles()
    call = _call_shards

    def put(client, seq):
        return _put_keys(client, seq, shard_count, key_width)

    async def go():
        runtime, port = _runtime(protocol, shard_count=shard_count, key_width=key_width)
        await runtime.start()
        try:
            driver = runtime.driver
            assert driver.serves_sites and driver.rule == ("atlas" if protocol == "atlas" else "epaxos")
            rw0, writer0, ack = await _hello(port, ClientHi([1]))
            await call(rw0, put(1, 1))
            assert driver._site_program is None and driver.stages.n["precompile"] == 1
            one_site = driver.resolver
            rw3, writer3, ack = await _hello(port, ClientHi([2], site=3))
            # the ack came after the program: nothing was dispatched in between
            assert isinstance(ack, ClientHiAck)
            assert driver._site_program is not None and driver.stages.n["precompile"] == 2
            assert driver.precompiled_programs == 2 and driver.sites_registered == 2
            assert one_site == ("run_position" if key_width == 1 else "general")
            assert runtime.backend_report()["resolver"] == (
                "key_runs" if key_width == 1 else "general_components")
            compiled = obs.recompile_count() + obs.cache_hit_count()
            for seq in range(2, 8):
                await asyncio.gather(call(rw0, put(1, seq)), call(rw3, put(2, seq)))
            assert obs.recompile_count() + obs.cache_hit_count() == compiled  # nothing since
            assert driver.executed == 13 and driver.in_flight == 0
            tallies = runtime._tallies
            assert tallies["sites_registered"] == 2
            assert tallies["finisher_rows"] == 0 and "scc_span_rows" in tallies
            if protocol == "atlas":  # f = 1: the fast path is unconditional
                assert driver.slow_paths == 0
            assert tallies["cross_shard_executed"] == (
                13 if key_width > 1 and shard_count > 1 else 0)
            # the coordinators' dots: site 0 is the process itself, site 3 process 4
            gen0, gen3 = runtime.register_site(0), runtime.register_site(3)
            assert gen0() == Dot(1, 8) and gen3() == Dot(4, 7)
            writer0.close()
            writer3.close()
        finally:
            await runtime.stop()

    asyncio.run(go())


@pytest.mark.parametrize("protocol, shard_count", [("atlas", 4), ("epaxos", 4), ("epaxos", 1)])
def test_two_sites_two_shard_commands_take_their_coordinators_dots_and_one_component(
        protocol, shard_count):
    """Commands of sites 0 and 2 over the same two keys, one on the first shard
    and one on the last, in one round: each takes its coordinator's dot, the
    site program's rings disagree about their order (site 2's replica is in
    site 0's quorum and has its own command first), and they execute as one
    component that spans both keys and, on four shards, two shards."""
    runtime, _port = _runtime(protocol, shard_count=shard_count, key_width=2)
    gens = [runtime.register_site(site) for site in (0, 2)]
    driver = runtime.driver
    assert driver.sites_registered == 2 and driver.resolver == "general_components"
    op = (KVOp.put("v"),)
    batch = []
    for at in range(6):
        dot = gens[at % 2]()
        keys = {0: {"a": op}, shard_count - 1: {"b": op}} if shard_count > 1 else {0: {"a": op, "b": op}}
        batch.append((dot, Command(Rifl(1 + at, 1), keys)))
    assert [dot.source for dot, _ in batch] == [1, 3] * 3
    results = driver.serve([batch])
    assert len({(r.rifl.source, r.rifl.sequence) for r in results}) == 6
    tallies = driver.round_tallies
    assert tallies["scc_rows"] == tallies["scc_span_rows"] == 6 and tallies["scc_count"] == 1
    assert tallies["scc_shard_rows"] == (6 if shard_count > 1 else 0)
    assert tallies["finisher_rows"] == 0 and tallies["resolve_iters"] >= 1
    assert (driver.slow_paths == 0) == (protocol == "atlas")
    assert driver.executed == 6 and driver.in_flight == 0


@pytest.mark.parametrize("shard_count, key_width", [(1, 1), (4, 2)])
def test_a_server_under_atlas_at_f_two_acknowledges_a_hello_from_every_site(shard_count, key_width):
    """`--protocol atlas -f 2`, on one shard with one key a command and on four
    with two: the second site's hello makes the second program ready before its
    ack, every other site's is acknowledged on it, and five sites' writes of
    one key take both paths: the threshold's tallies reach the snapshot."""
    obs.subscribe_recompiles()

    async def go():
        runtime, port = _runtime("atlas", f=2, shard_count=shard_count, key_width=key_width)
        await runtime.start()
        try:
            driver = runtime.driver
            assert driver.serves_sites and (driver.rule, driver.f) == ("atlas", 2)
            assert (driver.fast_quorum, driver.write_quorum) == (4, 3)
            sessions = []
            for site in range(5):
                rw, writer, ack = await _hello(port, ClientHi([1 + site], site=site))
                assert isinstance(ack, ClientHiAck)
                # one program more at the second site's hello, none after it
                assert driver.stages.n["precompile"] == 1 + (site > 0)
                assert (driver._site_program is not None) == (site > 0)
                sessions.append((rw, writer))
            assert driver.sites_registered == 5 and driver.precompiled_programs == 2
            compiled = obs.recompile_count() + obs.cache_hit_count()
            for seq in range(1, 9):
                await asyncio.gather(*(
                    _call_shards(rw, _put_keys(1 + site, seq, shard_count, key_width))
                    for site, (rw, _) in enumerate(sessions)))
            assert obs.recompile_count() + obs.cache_hit_count() == compiled  # nothing since
            assert driver.executed == 40 and driver.in_flight == 0
            tallies = runtime._tallies
            assert tallies["sites_registered"] == 5
            assert 0 < tallies["slow_paths"] <= tallies["threshold_short_deps"]
            assert 0 < tallies["threshold_fast_split_rows"] < tallies["split_quorum_rows"]
            assert (tallies["split_quorum_rows"] - tallies["threshold_fast_split_rows"]
                    == tallies["slow_paths"])
            for _, writer in sessions:
                writer.close()
        finally:
            await runtime.stop()

    asyncio.run(go())


# --- Tempo: the Newt round with a coordinator at every site -----------------


def test_a_newt_driver_no_site_but_0_registered_at_dispatches_the_parents_programs():
    """`sites == 1` traces today's program: the round and the chained program
    of a Newt driver that no site but 0 registered at are built without the
    two arguments, its tallies read 0, and the programs of a second site take
    the state and the columns of the first."""
    driver = NewtDeviceDriver(5, f=2, batch_size=8, key_buckets=64, pending_capacity=8)
    assert driver.serves_sites and driver.sites_registered == 1
    for length in (1, 2):
        assert set(driver._jit_rounds(length).__wrapped__.keywords) == {
            "mesh", "f", "tiny_quorums", "live_replicas", "shard_count"}
    driver.register_site(0)
    batch = [(Dot(1, 1 + at), _put(1 + at, 1, "k")) for at in range(6)]
    assert len(driver.serve([batch[:3], batch[3:]])) == 6
    assert driver.precompiled_programs == len(driver._programs) == 1  # the chain of two
    assert not driver._one_site_programs and driver.slow_paths == 0
    assert driver.round_tallies == dict.fromkeys(mesh_step.NEWT_SITE_ROUND_TALLIES, 0)
    one_site = dict(driver._programs)
    driver.register_site(2)
    assert driver._one_site_programs == one_site and set(driver._programs) == {1}
    assert driver.precompiled_programs == 2 and driver.sites_registered == 2
    for length in (1, 2):
        keywords = driver._jit_rounds(length).__wrapped__.keywords
        assert keywords["sites"] == 5 and keywords["site_base"] == 1
    program, sharding, layout = driver._program(1)
    assert sharding.shard_shape((3, 8))[0] == len(driver._column_specs())  # a row a column, one array
    assert layout.type is mesh_step.NewtSiteStepOutput


def test_a_second_sites_hello_on_a_newt_server_makes_every_chain_length_ready_before_its_ack():
    """The tuner's ladder is compiled at start-up with one coordinator; the
    second site's hello compiles it again with five (each length lowered under
    a `precompile` span, compiled beside the others and waited for under a
    second one), before its ack; no dispatch after it compiles, whatever its length;
    two sites' commands on one key execute, some on the slow path at f = 2."""
    obs.subscribe_recompiles()

    async def go():
        runtime, port = _runtime("newt", f=2)
        await runtime.start()
        try:
            driver = runtime.driver
            ladder = runtime._chain_tuner.ladder()
            assert len(ladder) > 1 and driver._chain_lengths == ladder
            assert driver.precompiled_programs == driver.stages.n["precompile"] == len(ladder)
            one_site = dict(driver._programs)
            rw0, writer0, ack = await _hello(port, ClientHi([1]))
            await _call(rw0, _put(1, 1, "k"))
            assert driver.sites_registered == 1 and not driver._one_site_programs
            rw2, writer2, ack = await _hello(port, ClientHi([2], site=2))
            # the ack came after the programs: nothing was dispatched in between
            assert isinstance(ack, ClientHiAck)
            assert driver.stages.n["precompile"] == 3 * len(ladder)
            assert driver.precompiled_programs == 2 * len(ladder) and driver.sites_registered == 2
            assert set(driver._programs) == set(ladder) == set(one_site)
            assert all(driver._programs[S] is not one_site[S] for S in ladder)
            compiled = obs.recompile_count() + obs.cache_hit_count()
            rounds = driver.rounds
            for seq in range(2, 8):
                await asyncio.gather(_call(rw0, _put(1, seq, "k")), _call(rw2, _put(2, seq, "k")))
            # a chain of every length through the served driver, on the loop's
            # thread while no client sends: the programs are there
            dots = runtime.register_site(2)
            for S in ladder:
                chain = [[(dots(), _put(40 + S, 1 + at, "k"))] for at in range(S)]
                await asyncio.get_running_loop().run_in_executor(None, driver.serve, chain)
            assert driver.rounds > rounds
            assert obs.recompile_count() + obs.cache_hit_count() == compiled  # nothing since
            assert driver.stages.n["precompile"] == 3 * len(ladder)
            assert runtime._tallies["sites_registered"] == 2
            assert driver.in_flight == 0
            # a third site: the programs are there
            rw4, writer4, ack = await _hello(port, ClientHi([3], site=4))
            assert isinstance(ack, ClientHiAck) and driver.stages.n["precompile"] == 3 * len(ladder)
            await _call(rw4, _put(3, 1, "k"))
            assert driver.sites_registered == 3
            runtime._publish_tallies()
            assert {"site_clock_spread", "clock_ties", "arrival_reordered"} <= set(runtime._tallies)
            assert runtime._tallies["precompiled_programs"] == 2 * len(ladder)
            for writer in (writer0, writer2, writer4):
                writer.close()
        finally:
            await runtime.stop()
        return runtime

    runtime = asyncio.run(go())
    assert runtime.driver.store.execute("k", KVOp.get(), Rifl(9, 9)) is not None


def test_a_newt_chain_takes_each_rounds_batch_by_sites_in_turn():
    """Three rounds in one dispatch, each given as one site's stretch after
    another: every round of the chain is assembled with its sites' commands in
    turn, a site's own in order, and a driver with one site leaves a batch as
    it came."""
    driver = NewtDeviceDriver(5, f=2, batch_size=8, key_buckets=64, pending_capacity=8)
    sources = [1, 1, 1, 4, 4, 2]

    def chain(first):
        return [[(Dot(src, first + 10 * r + at), _put(src, first + 10 * r + at, f"k{at}"))
                 for at, src in enumerate(sources)] for r in range(3)]

    _keys, srcs, _seqs = driver._assemble_chain(chain(1))
    assert srcs[:, :6].tolist() == [sources] * 3
    driver._cmds.clear()
    driver.register_site(3)
    _keys, srcs, seqs = driver._assemble_chain(chain(100))
    assert srcs[:, :6].tolist() == [[1, 4, 2, 1, 4, 1]] * 3
    assert seqs[0, :6].tolist() == [100, 103, 105, 101, 104, 102]
    driver._cmds.clear()
    key, src, seq = driver._assemble(chain(200)[0])
    assert src[:6].tolist() == [1, 4, 2, 1, 4, 1]
    driver._cmds.clear()
    # ... and the chain serves: one dispatch, three rounds, every command once
    results = driver.serve(chain(300))
    assert driver.rounds == 3 and len(results) == 18 and driver.in_flight == 0
    assert len({(r.rifl.source, r.rifl.sequence) for r in results}) == 18


def test_dots_of_two_sites_never_collide_in_the_newt_registry():
    runtime, _port = _runtime("newt", f=2)
    driver = runtime.driver
    gens = [runtime.register_site(site) for site in (0, 3, 0, 3)]
    dots = [gen() for gen in gens for _ in range(3)]
    assert dots[:3] + dots[6:9] == [Dot(1, seq) for seq in range(1, 7)]
    assert dots[3:6] + dots[9:] == [Dot(4, seq) for seq in range(1, 7)]
    assert driver.site_base == 1 and driver.sites_registered == 2
    batch = [(dot, _put(1 + at, 1, "k")) for at, dot in enumerate(dots)]
    # the registry's keys while the round is assembled: one a dot
    _key, src, seq = driver._assemble(batch)
    assert len(driver._cmds) == 12
    assert set(driver._cmds) == {
        _DriverCore._packed(dot.source, dot.sequence) for dot in dots}
    driver._cmds.clear()
    # same sequences at two sites on one key: both coordinators' commands execute,
    # in one (clock, dot) order, some of them on the slow path
    results = driver.serve([batch])
    assert len(results) == 12 and driver.executed == 12 and driver.in_flight == 0
    assert driver.fast_paths + driver.slow_paths == 12 and driver.slow_paths > 0
    assert driver.round_tallies["site_clock_spread"] > 0


# --- Caesar: the round with a coordinator at every site ---------------------


def test_a_caesar_driver_no_site_but_0_registered_at_dispatches_the_parents_program():
    driver = CaesarDeviceDriver(7, batch_size=8, key_buckets=64, pending_capacity=8)
    assert driver.serves_sites and driver.site_base == 1
    assert set(driver._step.__wrapped__.keywords) == {"mesh", "num_replicas", "live_replicas"}
    batch = [(Dot(1, 1 + at), _put(1, 1 + at, "k")) for at in range(6)]
    assert len(driver.serve([batch])) == 6 and driver.slow_paths == 0
    assert driver._programs[1][2].type is mesh_step.CaesarStepOutput
    one_site = driver._programs[1]
    assert driver.round_tallies == dict.fromkeys(mesh_step.CAESAR_SITE_ROUND_TALLIES, 0)
    assert driver.round_gauges == {"wait_passes": 0}
    driver.register_site(6)
    assert driver.sites_registered == 2 and driver.precompiled_programs == 2
    assert driver.stages.n["precompile"] == 2 and driver._one_site_programs[1] is one_site
    assert driver._step.__wrapped__.keywords["sites"] == 7
    program, sharding, layout = driver._program(1)
    assert sharding.shard_shape((3, 8))[0] == len(driver._column_specs())
    assert layout.type is mesh_step.CaesarSiteStepOutput
    # one site's stretch after another: the round is given them in turn
    sources = [1, 1, 1, 7, 7, 2]
    batch = [(Dot(src, 10 + at), _put(src, 10 + at, "k")) for at, src in enumerate(sources)]
    _key, src, seq = driver._assemble(batch)
    assert src[:6].tolist() == [1, 7, 2, 1, 7, 1] and seq[:6].tolist() == [10, 13, 15, 11, 14, 12]
    driver._cmds.clear()


def test_a_caesar_server_takes_hellos_from_seven_sites_and_its_history_passes_the_check():
    """`--protocol caesar -n 7`: the second site's hello makes the second
    program ready before its ack, every other site's is acknowledged on it,
    every client of seven sites is answered, some commands of the one hot key
    are retried, the wait condition's tallies reach the snapshot, and the
    answers (every write returns the value it replaced) are one chain a key
    under `benchmark/check.py`."""
    import time

    from benchmark.check import check_history
    from benchmark.generators.kv_loop import MEASURED, NONE_VALUE, OK, PUT, RECORD_FIELDS

    obs.subscribe_recompiles()
    rows = []

    async def write(rw, client, seq, key):
        sent = time.monotonic()
        reply = await _call(rw, Command.from_single(
            Rifl(client, seq), 0, f"k{key}", KVOp.put(f"{client}:{seq}")))
        (result,), = reply.cmd_result.results.values()
        prev = (NONE_VALUE, NONE_VALUE) if result is None else map(int, result.split(":"))
        ret_client, ret_seq = prev
        rows.append(dict(client=client, seq=seq, key=key, op=PUT, phase=MEASURED, status=OK,
                         due=sent, sent=sent, acked=time.monotonic(),
                         ret_client=ret_client, ret_seq=ret_seq))

    async def go():
        runtime, port = _runtime("caesar", n=7)
        await runtime.start()
        try:
            driver = runtime.driver
            assert driver.serves_sites
            sessions = []  # three clients a site, a connection each
            for client in range(1, 22):
                site = (client - 1) // 3
                rw, writer, ack = await _hello(port, ClientHi([client], site=site))
                assert isinstance(ack, ClientHiAck)
                assert driver.stages.n["precompile"] == 1 + (site > 0)
                sessions.append((rw, writer, client))
            assert driver.sites_registered == 7 and driver.precompiled_programs == 2
            compiled = obs.recompile_count() + obs.cache_hit_count()
            rng = np.random.default_rng(59)
            for seq in range(1, 13):  # every client at a time in some order, half on key 0
                await asyncio.gather(*(
                    write(sessions[at][0], sessions[at][2], seq,
                          0 if rng.random() < 0.5 else sessions[at][2])
                    for at in rng.permutation(len(sessions))))
            assert obs.recompile_count() + obs.cache_hit_count() == compiled  # nothing since
            assert driver.executed == len(rows) == 7 * 3 * 12 and driver.in_flight == 0
            runtime._publish_tallies()
            tallies = runtime._tallies
            assert tallies["sites_registered"] == 7
            assert tallies["fast_paths"] + tallies["slow_paths"] == tallies["executed"]
            assert 0 < tallies["slow_paths"] <= tallies["wait_rows"]
            assert tallies["wait_acks"] >= tallies["reject_acks"] >= 5 * tallies["slow_paths"]
            assert tallies["retry_clock_lift"] >= tallies["slow_paths"]
            assert tallies["wait_passes"] >= 2
            for _, writer, _ in sessions:
                writer.close()
        finally:
            await runtime.stop()

    asyncio.run(go())
    records = {name: np.array([row[name] for row in rows], dtype) for name, dtype in RECORD_FIELDS}
    verdict = check_history(records)
    assert verdict["correct"], verdict["witnesses"]
    assert verdict["stats"]["acked_writes"] == len(rows)
    assert verdict["stats"]["longest_chain"] > 50  # the hot key's
