"""The served path's three layers, a module each (PR 57):
``run/device_runner.py`` (the runtime) -> ``run/device_session.py`` (the
session plane and its tallies) and -> ``run/device_drivers.py`` (the four
drivers, ``driver_for``) -> ``run/pipeline.py``.  Held here: the imports
point one way, neither upper layer reaches into the privates of the one
below, what ``device_runner`` re-exports is what its home defines,
``driver_for`` builds what the runtime's ``if/elif`` built before it, and the
snapshot kept its names and their order."""

import ast
import inspect

import jax
import pytest

# as tests/test_device_runner.py: the device drivers' scan bodies crash
# jaxlib 0.4.x flakily while tracing
if tuple(int(x) for x in jax.__version__.split(".")[:2]) < (0, 5):
    pytest.skip("jax<0.5: device-driver scan tracing segfaults flakily", allow_module_level=True)

from fantoch_tpu.core import Command, Config, KVOp, Rifl
from fantoch_tpu.core.ids import Dot
from fantoch_tpu.run import device_drivers, device_runner, device_session


def _tree(module):
    return ast.parse(inspect.getsource(module))


def _imports(module):
    """Every module a file imports, at its top or inside a function."""
    found = set()
    for node in ast.walk(_tree(module)):
        if isinstance(node, ast.Import):
            found.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            found.add(node.module)
            found.update(f"{node.module}.{alias.name}" for alias in node.names)
    return found


# --- (a) the arrows point one way ---

ARROWS = [
    (device_drivers,
     ("asyncio", "fantoch_tpu.run.device_session", "fantoch_tpu.run.device_runner")),
    (device_session, ("fantoch_tpu.run.device_runner",)),
]


@pytest.mark.parametrize("module,refused", ARROWS, ids=["drivers", "session"])
def test_a_lower_layer_imports_none_above_it(module, refused):
    imports = _imports(module)
    assert imports, "the file's imports were not read"
    assert not [name for name in imports if name in refused]
    # ... and the runtime does import both, so the three are one path
    assert {"fantoch_tpu.run.device_drivers", "fantoch_tpu.run.device_session"} <= _imports(
        device_runner
    )


# --- (b) no layer reaches into the privates of the one below ---


def _private_reads(module, names):
    """``<name>._x`` and ``self.<name>._x`` in a file's source, for the
    names a layer calls the one below by."""
    found = []
    for node in ast.walk(_tree(module)):
        if not isinstance(node, ast.Attribute):
            continue
        if not node.attr.startswith("_") or node.attr.startswith("__"):
            continue
        owner = node.value
        if isinstance(owner, ast.Attribute) and isinstance(owner.value, ast.Name):
            owner = owner.attr if owner.value.id == "self" else None
        elif isinstance(owner, ast.Name):
            owner = owner.id
        else:
            owner = None
        if owner in names:
            found.append(f"{owner}.{node.attr}:{node.lineno}")
    return found


PRIVATES = [
    (device_session, ("runtime",)),
    # ``d = self.driver`` where the tallies are published and the backend named
    (device_runner, ("driver", "d")),
]


@pytest.mark.parametrize("module,names", PRIVATES, ids=["session->runtime", "runtime->driver"])
def test_a_layer_uses_the_public_surface_of_the_one_below(module, names):
    source = inspect.getsource(module)
    assert any(f"{name}." in source for name in names)
    assert _private_reads(module, names) == []


def test_what_the_seams_go_through():
    """The three accessors the seams were given: the driver's mesh, the way
    back into its requeue, and the runtime's ring for the session that
    sheds."""
    driver = device_drivers.PaxosDeviceDriver(3, batch_size=8, key_buckets=64, pending_capacity=8)
    assert driver.mesh is driver._mesh
    first, second, third = (
        (Dot(1, seq), Command.from_single(Rifl(1, seq), 0, f"k{seq}", KVOp.put("v")))
        for seq in (1, 2, 3)
    )
    driver._requeue.append(third)
    driver.give_back([first, second])
    assert driver.has_requeue and driver.take_requeue() == [first, second, third]
    runtime = _runtime("fpaxos")
    assert runtime.submit_ring is runtime._submit_queue
    runtime.account.close()


# --- (c) what device_runner re-exports is what its home defines ---

REEXPORTS = ["DeviceDriver", "NewtDeviceDriver", "CaesarDeviceDriver", "PaxosDeviceDriver",
             "_DriverCore"]


@pytest.mark.parametrize("name", REEXPORTS)
def test_a_reexported_name_is_its_homes_object(name):
    assert getattr(device_runner, name) is getattr(device_drivers, name)
    assert getattr(device_runner, name).__module__ == device_drivers.__name__


def test_the_runtime_and_the_session_live_where_the_map_says():
    assert device_runner.DeviceRuntime.__module__ == device_runner.__name__
    assert device_session._DeviceClientSession.__module__ == device_session.__name__
    # the one private name kept by the old path is the class the drivers
    # derive from, and the store's one pass is taken while its
    # ``_execute_entry`` is the function kept by name beside it
    assert device_runner._DriverCore._execute_entry is device_drivers._EXECUTE_ENTRY
    for name in REEXPORTS[:-1]:
        assert issubclass(getattr(device_runner, name), device_runner._DriverCore)
    # nothing of the drivers or the session plane is defined in the runtime's file
    defined = [node.name for node in _tree(device_runner).body
               if isinstance(node, (ast.ClassDef, ast.FunctionDef))]
    assert defined == ["DeviceRuntime"]


# --- (d) driver_for builds what the runtime's if/elif built ---

SHARED = {"batch_size": 8, "key_buckets": 64, "pending_capacity": 16, "live_replicas": 3,
          "monitor_execution_order": True, "mesh": None}
SITED = {"site_base": 7, "key_width": 2}  # the rounds that order by key
SHARDED = {"f": 1, "shard_count": 2, **SITED}
# label -> (class, shard_count of its Config, the keywords beside SHARED)
FAMILIES = {
    "epaxos": ("DeviceDriver", 2, {**SHARDED, "rule": "epaxos"}),
    "atlas": ("DeviceDriver", 2, {**SHARDED, "rule": "atlas"}),
    "newt": ("NewtDeviceDriver", 2, {**SHARDED, "tiny_quorums": True}),
    "caesar": ("CaesarDeviceDriver", 1, SITED),  # a coordinator at every site since PR 59
    "fpaxos": ("PaxosDeviceDriver", 1, {"f": 1}),
}


def _driver_for(protocol, config, **over):
    asked = {**SHARED, "process_id": 7, "key_width": 2, **over}
    return device_drivers.driver_for(protocol, config, **asked)


@pytest.mark.parametrize("protocol", list(FAMILIES))
def test_driver_for_makes_the_parents_constructor_call(protocol, monkeypatch):
    cls_name, shard_count, own = FAMILIES[protocol]
    config = Config(3, 1, shard_count=shard_count, newt_tiny_quorums=True)
    calls = []
    for name in REEXPORTS[:-1]:
        monkeypatch.setattr(
            device_drivers, name,
            lambda *args, _name=name, **kwargs: calls.append((_name, args, kwargs)),
        )
    _driver_for(protocol, config)
    assert calls == [(cls_name, (3,), {**SHARED, **own})]
    monkeypatch.undo()
    # ... and made for real, on one shard: the class, and what it says of itself
    driver = _driver_for(protocol, Config(3, 1), live_replicas=None)
    assert type(driver) is getattr(device_drivers, cls_name)
    assert driver.shard_count == 1 and driver.batch_size == 8 and driver.key_buckets == 64
    assert driver.rule == own.get("rule")
    if "site_base" in own:
        assert driver.site_base == 7


@pytest.mark.parametrize("protocol", ["caesar", "fpaxos"])
def test_driver_for_refuses_the_single_shard_rounds_on_two_shards(protocol):
    with pytest.raises(ValueError) as refusal:
        _driver_for(protocol, Config(3, 1, shard_count=2))
    assert str(refusal.value) == (
        "device-step sharding serves the dep-commit and newt "
        f"rounds; {protocol} serving is single-shard"
    )
    # ... as the runtime over it does
    with pytest.raises(ValueError, match=f"{protocol} serving is single-shard"):
        device_runner.DeviceRuntime(
            Config(3, 1, shard_count=2), ("127.0.0.1", 0), protocol=protocol
        )


# --- (e) the snapshot kept its names and their order ---

# what a fresh runtime published at the parent (PR 56), by driver family:
# HEAD, the round's own tallies and gauges, TAIL
HEAD = ["submitted", "session_flat_admitted", "replied", "rounds", "executed",
        "executed_in_pass", "executed_off_wire", "drain_rows_walked", "requeued", "fast_paths",
        "slow_paths"]
ROUND = {
    "epaxos": ["deps_committed", "key_links", "read_links_commuted", "read_rows",
               "cross_shard_executed", "scc_rows", "scc_count", "resolve_iters", "finisher_rows",
               "scc_span_rows", "scc_shard_rows", "threshold_short_deps", "split_quorum_rows",
               "threshold_fast_split_rows", "scc_rows_max"],
    "newt": ["site_clock_spread", "clock_ties", "arrival_reordered"],
    "caesar": ["wait_rows", "wait_acks", "reject_acks", "retry_clock_lift", "wait_passes"],  # PR 59
    "fpaxos": [],
}
STAGES = ["idle_wait", "gate_wait", "collect", "handoff", "step", "assemble", "enqueue", "fetch",
          "execute", "resume", "deliver", "publish", "round", "snapshot", "loop_stall", "gc",
          "read", "precompile", "finish"]
COMPUTING = ["step", "enqueue", "fetch", "assemble", "execute", "collect", "deliver", "publish"]
# only where the kernel says how long a thread waited for a CPU
RUNQ = ["thread_loop_runq_ms", "thread_step_runq_ms", "host_runq_ms"]
TAIL = [
    "sites_registered", "in_flight", "stable_watermark", "queued", "queued_hwm",
    "queue_capacity", "shed_submissions", "device_dispatches", "device_transfers",
    "device_dispatched_rows", "device_batch_capacity", "dispatch_fill_frac",
    "serving_chain_len", "device_dispatch_ms", "device_drain_ms", "device_fetch_ms",
    "device_busy_ms", "device_span_ms", "device_idle_frac", "device_pipeline_depth",
    "device_pipelined_rounds", "device_overlapped_dispatches", "device_seq_epochs",
    "device_slot_epochs", "device_held_dispatches",
    *(f"stage_{name}_{unit}" for name in STAGES for unit in ("ms", "n")),
    *(f"stage_{name}_{unit}" for name in COMPUTING for unit in ("cpu_ms", "timed_ms")),
    "loop_stall_busy_ms", "loop_stall_gil_ms", "loop_stall_runq_ms", "loop_stall_blocked_ms",
    "loop_stopped_ms", "step_unnamed_ms", "thread_loop_cpu_ms", "thread_step_cpu_ms",
    "host_cpu_ms", "proc_cpu_ms", "proc_minflt", "proc_majflt", "proc_nivcsw", *RUNQ,
    "session_decode_ms", "session_decode_cpu_ms", "session_decode_timed_ms", "session_decoded",
    "session_plain_decoded", "session_reads", "session_admit_ms", "session_admit_cpu_ms",
    "session_admit_timed_ms", "stage_wait_ms", "queue_wait_ms", "queue_released",
    "collect_slices", "reply_flush_ms", "reply_flushes", "reply_writes", "reply_bytes",
    "shard_replies", "reply_plain_frames", "reply_flat_frames", "reply_partial_frames",
    "commands_completed", "multi_shard_completed", "gets_replied", "get_value_bytes",
    "store_records", "loop_lag_hwm_ms", "loop_stall_ms", "loop_stalls", "gc_frozen_objects",
    "gc_full_scheduled", "gc_full_unscheduled", "gc_collected", "ingest_arrivals",
    "ingest_releases", "ingest_released_rows", "ingest_releases_fast", "ingest_releases_size",
    "ingest_releases_deadline", "ingest_target", "ingest_rate_per_s", "serving_chain",
    "chain_adjustments", "precompiled_programs", "jax_recompiles", "jax_compile_ms",
    "jax_cache_hits", "jax_cache_misses",
]


def _runtime(protocol):
    return device_runner.DeviceRuntime(
        Config(3, 1), ("127.0.0.1", 0), protocol=protocol, batch_size=8, key_buckets=64,
        pending_capacity=8,
    )


@pytest.mark.parametrize("protocol", list(ROUND))
def test_a_fresh_runtime_publishes_the_parents_names_in_the_parents_order(protocol):
    runtime = _runtime(protocol)
    try:
        expected = HEAD + ROUND[protocol] + TAIL
        if not runtime.account.has_runq:
            expected = [name for name in expected if name not in RUNQ]
        assert list(runtime._tallies) == expected
        # the session plane's seventeen are read off its own object, which
        # every session of the runtime writes
        tallies = runtime.session_tallies
        assert len(tallies.__slots__) == 17 and not hasattr(tallies, "__dict__")
        for field in tallies.__slots__:
            assert getattr(tallies, field) == 0 and not hasattr(runtime, f"_{field}")
        tallies.reply_writes, tallies.flat_admitted, tallies.admit_ns = 5, 3, 2_000_000
        runtime._publish_tallies()
        published = runtime._tallies
        assert (published["reply_writes"], published["session_flat_admitted"]) == (5, 3)
        assert published["session_admit_ms"] == 2.0
    finally:
        runtime.account.close()
