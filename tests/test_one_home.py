"""One home for a route and for a tuning value (PR 29).

A route is chosen by the code from what it observes; a tuning value is a
``Config`` field that one CLI flag sets.  Nothing reads a ``FANTOCH_*``
variable to choose either, and ``DeviceRuntime`` takes the serving values
from its ``Config`` alone.  The rule tests at the end hold the tree to
that, in the style of tests/benchmark_tests/contract_rules.py.
"""

import dataclasses
import os
import re

import pytest

from fantoch_tpu.bin import server as bin_server
from fantoch_tpu.bin.common import config_from_args
from fantoch_tpu.core import Command, Config, Dot, KVOp, Rifl
from fantoch_tpu.core.timing import RunTime
from fantoch_tpu.run.device_runner import DeviceRuntime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHARD = 0


def _runtime(config: Config) -> DeviceRuntime:
    # never started: the constructor binds nothing and compiles nothing
    return DeviceRuntime(
        config, ("127.0.0.1", 0), protocol="newt",
        batch_size=8, key_buckets=64, pending_capacity=8,
    )


def _batched_graph(**fields):
    from fantoch_tpu.executor.graph.batched import BatchedDependencyGraph

    config = Config(
        3, 1, host_native_resolver=False, batched_graph_executor=True,
        **fields,
    )
    return BatchedDependencyGraph(1, SHARD, config)


# --- what each removed name used to move, read with the name set ---


def _depth():
    runtime = _runtime(Config(3, 1))
    # on the CPU an unset depth does not opt the rig into pipelining
    return runtime.pipeline_depth, runtime.driver.pipeline_depth, runtime.pipeline


def _chain_max():
    runtime = _runtime(Config(3, 1))
    return runtime._chain_tuner.chain_max, runtime._batcher.max_target


def _ingest_target():
    return _runtime(Config(3, 1))._batcher.fixed_target


def _ingest_deadline():
    from fantoch_tpu.client import ConflictRateKeyGen, Workload
    from fantoch_tpu.core import Planet
    from fantoch_tpu.protocol import EPaxos
    from fantoch_tpu.sim import Runner

    runtime = _runtime(Config(3, 1))
    # the sim stays immediate unless the field asks for a deadline
    planet = Planet.new("gcp")
    regions = sorted(planet.regions())[:3]
    sim = Runner(
        EPaxos, planet, Config(3, 1, gc_interval_ms=100),
        Workload(
            shard_count=1, key_gen=ConflictRateKeyGen(50), keys_per_command=1,
            commands_per_client=1, payload_size=1,
        ),
        1, regions, regions, seed=1,
    )
    return (
        runtime.ingest_deadline_ms, runtime._batcher.deadline_ms,
        sim._ingest_deadline_ms,
    )


def _graph_plane():
    return _batched_graph()._plane


def _graph_threshold():
    return _batched_graph()._structure_threshold


def _table_threshold():
    from fantoch_tpu.executor.table import TableExecutor

    return TableExecutor(1, SHARD, Config(3, 1))._kernel_threshold


def _plane_programs():
    """The public names are the registered, jitted programs themselves:
    no router stands between a plane and its program."""
    from fantoch_tpu.core import compile_cache
    from fantoch_tpu.ops import graph_resolve, pred_resolve, table_ops

    programs = {
        "pred_plane_step": pred_resolve.resolve_pred_plane_step,
        "graph_plane_step": graph_resolve.resolve_graph_plane_step,
        "votes_commit": table_ops.fused_votes_commit,
        "table_round": table_ops.fused_table_round,
    }
    return all(
        compile_cache._programs[name] is fn and hasattr(fn, "lower")
        for name, fn in programs.items()
    )


def _general_route():
    """A multi-key backlog past the kernel-size gate resolves through the
    resident peeler, whatever the environment says."""
    from unittest import mock

    from fantoch_tpu.executor.graph import batched
    from fantoch_tpu.protocol.common.graph_deps import Dependency

    graph = _batched_graph(graph_kernel_threshold=4)
    adds = []
    for i in range(8):
        dot = Dot(1, i + 1)
        cmd = Command.from_keys(
            Rifl(1, i + 1), SHARD, {k: (KVOp.put(""),) for k in ("a", "b")}
        )
        deps = [Dependency(Dot(1, i), frozenset({SHARD}))] if i else []
        adds.append((dot, cmd, deps))
    # dependents first: arrival order is no execution order, so the
    # batch has to be resolved
    with mock.patch.object(
        batched, "resolve_general_resident",
        wraps=batched.resolve_general_resident,
    ) as resident:
        graph.handle_add_batch(adds[::-1], RunTime())
        # the backlog resolves lazily, when the order is asked for
        order = [c.rifl.sequence for c in graph.commands_to_execute()]
    return order == list(range(1, 9)) and resident.call_count >= 1


REMOVED = [
    # (variable, a value it used to accept, what it moved, the default)
    ("FANTOCH_SERVING_PIPELINE_DEPTH", "3", _depth, (1, 1, False)),
    ("FANTOCH_SERVING_CHAIN_MAX", "2", _chain_max, (8, 8 * 8)),
    ("FANTOCH_INGEST_TARGET", "32", _ingest_target, None),
    ("FANTOCH_INGEST_DEADLINE_MS", "7.5", _ingest_deadline, (2.0, 2.0, None)),
    ("FANTOCH_GRAPH_PLANE", "1", _graph_plane, None),
    ("FANTOCH_GRAPH_KERNEL_THRESHOLD", "123", _graph_threshold, 4096),
    ("FANTOCH_TABLE_KERNEL_THRESHOLD", "77", _table_threshold, 1 << 20),
    ("FANTOCH_PALLAS", "1", _plane_programs, True),
    ("FANTOCH_GENERAL_RESIDENT", "0", _general_route, True),
]


@pytest.mark.parametrize(
    "name,value,read,default", REMOVED, ids=[row[0] for row in REMOVED]
)
def test_removed_variable_moves_nothing(name, value, read, default, monkeypatch):
    monkeypatch.setenv(name, value)
    got = read()
    assert got == default, (name, got)


def test_removed_field_is_gone():
    names = {f.name for f in dataclasses.fields(Config)}
    assert "pallas_kernels" not in names and len(names) == 43
    with pytest.raises(TypeError):
        Config(3, 1, pallas_kernels=True)
    with pytest.raises((TypeError, ValueError)):
        Config(3, 1).with_(pallas_kernels=True)


# --- the one spelling that is left: flag -> field -> runtime ---


def _served(*flags):
    args = bin_server.build_parser().parse_args(
        ["--protocol", "newt", "-n", "3", "-f", "1", "--client-port", "0",
         "--device-step", *flags]
    )
    return _runtime(config_from_args(args))


FLAGS = [
    # (flag, value, where the runtime holds it, the constructor argument
    # that used to spell it a second time)
    ("--serving-pipeline-depth", 3, lambda r: r.pipeline_depth, "pipeline_depth"),
    ("--ingest-deadline", 7.5, lambda r: r._batcher.deadline_ms, "ingest_deadline_ms"),
    ("--ingest-target", 32, lambda r: r._batcher.fixed_target, "ingest_target"),
    ("--serving-chain-max", 4, lambda r: r._chain_tuner.chain_max, "serving_chain_max"),
]


@pytest.mark.parametrize(
    "flag,value,read,argument", FLAGS, ids=[row[0] for row in FLAGS]
)
def test_flag_sets_field_sets_runtime(flag, value, read, argument):
    assert read(_served(flag, str(value))) == value
    with pytest.raises(TypeError):
        DeviceRuntime(Config(3, 1), ("127.0.0.1", 0), **{argument: value})


def test_defaults_the_cells_run():
    """No tuning flag, as every cell of BENCHMARK.json: depth 1, a 2 ms
    deadline with the adaptive target, chain ceiling 8 and so the ladder
    that ``precompile_chains`` loads."""
    runtime = _served()
    assert runtime.pipeline_depth == runtime.driver.pipeline_depth == 1
    assert runtime.ingest_deadline_ms == runtime._batcher.deadline_ms == 2.0
    assert runtime._batcher.fixed_target is None
    assert runtime._batcher.max_target == 8 * runtime.driver.batch_size
    assert runtime._chain_tuner.chain_max == 8
    assert runtime._chain_tuner.ladder() == [1, 2, 4, 8]


# --- a driver runs rounds one way (PR 44) ---


def _driver_has(name):
    from fantoch_tpu.run import device_runner as dr
    from fantoch_tpu.run.pipeline import PipelineCore

    return any(
        hasattr(cls, name)
        for cls in (PipelineCore, dr._DriverCore, dr.DeviceDriver, dr.NewtDeviceDriver,
                    dr.CaesarDeviceDriver, dr.PaxosDeviceDriver)
    )


def _runtime_takes(argument):
    import inspect

    return argument in inspect.signature(DeviceRuntime.__init__).parameters


ONE_WAY = [
    # (what chose a route and is gone, whether anything still answers to it)
    ("step_chained", lambda: _driver_has("step_chained")),
    ("step_chained_pipelined", lambda: _driver_has("step_chained_pipelined")),
    ("step_pipelined", lambda: _driver_has("step_pipelined")),
    ("--device-pipeline",
     lambda: "--device-pipeline" in bin_server.build_parser()._option_string_actions),
    ("pipeline", lambda: _runtime_takes("pipeline")),
]


@pytest.mark.parametrize("name,answers", ONE_WAY, ids=[row[0] for row in ONE_WAY])
def test_rounds_are_run_one_way(name, answers):
    """``serve`` is the one entry and its ``overlap`` is the runtime's to
    work out (off the CPU, or a depth set): no mode of its own, no flag
    and no constructor argument chooses it."""
    assert not answers()
    assert _driver_has("serve") and _driver_has("step") and _driver_has("flush_pipeline")
    assert _served("--serving-pipeline-depth", "1").pipeline and not _served().pipeline


# --- rules over the tree ---


def _python_files(*roots):
    for root in roots:
        path = os.path.join(REPO, root)
        if os.path.isfile(path):
            yield path
            continue
        for base, _dirs, files in os.walk(path):
            for name in files:
                if name.endswith(".py"):
                    yield os.path.join(base, name)


def test_rule_the_variables_that_stay():
    """The ``FANTOCH_*`` names under ``fantoch_tpu/`` are a deployment's
    durability policy, a fault-injection spec, a recorder size and a
    script's budget: none chooses a route or tunes one."""
    found = set()
    for path in _python_files("fantoch_tpu"):
        with open(path) as fh:
            found.update(re.findall(r"FANTOCH_[A-Z_]+", fh.read()))
    assert found == {
        "FANTOCH_DEVICE_FAULT", "FANTOCH_FLIGHT_EVENTS",
        "FANTOCH_FUZZ_BUDGET_S", "FANTOCH_WAL_SYNC",
    }


def test_rule_no_module_imports_pallas():
    """The kernel route that did not lower on the chip is gone (PR 21's
    probe, PR 29's deletion): nothing imports the kernel language, and
    no program under ``ops/`` keeps the suffix that told it from one."""
    needle = "jax.experimental" + ".pallas"
    short = "import " + "pallas"
    offenders = []
    for path in _python_files(
        "fantoch_tpu", "tests", "scripts", "benchmark", "bench.py",
        "chip_smoke.py", "__graft_entry__.py",
    ):
        with open(path) as fh:
            text = fh.read()
        if needle in text or short in text:
            offenders.append(os.path.relpath(path, REPO))
    assert offenders == []
    suffixed = []
    for path in _python_files("fantoch_tpu/ops"):
        with open(path) as fh:
            suffixed += re.findall(r"\b\w+_xla\b", fh.read())
    assert suffixed == []
    assert not os.path.exists(
        os.path.join(REPO, "fantoch_tpu", "ops", "pallas" + "_resolve.py")
    )
