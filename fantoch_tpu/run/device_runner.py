"""The TPU serving path: a TCP client plane feeding the device-resident
multi-replica protocol step.

The reference's runner *is* its serving story —
fantoch/src/run/mod.rs:105-445 boots protocol + executor tasks behind TCP
and the clients' commands flow through the state machine one message at a
time.  The TPU-native serving story inverts the altitude: the whole
protocol round (dependency collection, fast-path check, Synod accept,
SCC resolution, GC watermark) is ONE device program over a
(replica x batch) mesh (fantoch_tpu/parallel/mesh_step.py), state stays
device-resident across rounds (donated), and the host only

  * feeds command batches in (array columns assembled from client
    submissions), and
  * drains execution orders out (applying them to the host KVStore and
    routing results back to client sessions, one reply per touched
    shard — the client plane's wire contract, as the object runner's).

``DeviceDriver`` (run/device_drivers.py) is the host-side control loop
(usable without any networking: the driver dry-run and the simulator-style
tests call it directly); ``DeviceRuntime`` wraps it in the TCP client plane
(run/device_session.py: one session a connection) speaking the
exact wire protocol of fantoch_tpu/run/prelude.py, so ``bin/client.py``
and ``run_clients`` work unchanged against a device-step server.

The mesh models all replicas — on real TPU pods the replica axis spans
mesh slices wired by ICI, which is exactly the deployment the reference
reaches with one TCP mesh per geo-replica pair.

Partial replication (``Config.shard_count > 1``, epaxos-class and Newt):
ONE mesh carries every shard — shard s owns key buckets
``b % shard_count == s`` and replica rows ``[s*n, (s+1)*n)``; quorums
are per shard per key slot (mesh_step.protocol_step /
newt_protocol_step sharded modes).  Cross-shard dependencies resolve
inside the shared working set — the mesh-native answer to the
reference's cross-shard dep request RPCs
(fantoch_ps/src/executor/graph/mod.rs:279-408) — and a Newt multi-shard
command commits at the max of its shards' clocks (the MShardCommit
aggregation).  The client plane keeps
the per-shard-server wire contract: clients connect once per shard
(every shard maps to this server's address), Submit rides the target
shard's connection, and each touched shard answers with its own
CommandResult over that same connection.

The served path's three layers, a module each, imports pointing one way
(tests/test_served_layers.py holds the arrows):
  run/device_runner.py (this one): ``DeviceRuntime``, the serving loop
    -> run/device_session.py: the session plane and its tallies -> wire, command
    -> run/device_drivers.py: the four drivers, ``driver_for`` -> run/pipeline.py
       -> parallel/mesh_step.py
"""

from __future__ import annotations

import asyncio
import gc
import os
import select
from collections import defaultdict
from functools import partial
from time import monotonic, monotonic_ns
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from fantoch_tpu.core.command import Command
from fantoch_tpu.core.config import Config
from fantoch_tpu.core.ids import AtomicIdGen, Dot, ProcessId, Rifl
from fantoch_tpu.executor.base import ExecutorResult
from fantoch_tpu.observability.device import (
    AccountSample,
    ThreadAccount,
    TimedSelector,
    off_cpu_ns,
)
from fantoch_tpu.run.collector import CollectorSchedule
from fantoch_tpu.run.device_drivers import driver_for
from fantoch_tpu.run.device_session import SessionTallies, _DeviceClientSession
from fantoch_tpu.run.ingest import (
    AdaptiveIngestBatcher,
    ChainAutoTuner,
    resolve_ingest_deadline_ms,
    resolve_serving_chain_max,
)
from fantoch_tpu.run.pipeline import BoundedSubmitRing, resolve_pipeline_depth
from fantoch_tpu.run.rw import Rw
from fantoch_tpu.utils import logger

# The re-exports: what other code may take from here of the layers below,
# beside ``DeviceRuntime``.  The four drivers by name (bench.py, the tests,
# the benchmark's own tests), and one private name, ``_DriverCore``, kept
# because tests/benchmark_tests/broken_multi_server.py replaces
# ``fantoch_tpu.run.device_runner._DriverCore._execute_entry`` by this path.
# Everything else of the drivers and the session plane is imported from its
# home.
from fantoch_tpu.run.device_drivers import (  # noqa: F401
    CaesarDeviceDriver,
    DeviceDriver,
    NewtDeviceDriver,
    PaxosDeviceDriver,
    _DriverCore,
)

Address = Tuple[str, int]

# The share of one round's rows from which a dispatch is left in flight
# (``DeviceRuntime._driver_task``).  Read from the dispatch because the two
# regimes lie a factor of fifty apart there: a throughput round carries
# 2000-4096 rows of 4096 and its blocking fetch is dead time on both
# threads, an open-loop round carries about 37 and pays for the overlap
# with a step of delivery lag and the ingest gate's hold (with every
# dispatch overlapped the open cells' medians rose by 15-31%: PERF.md
# section 6, PR 58).  Half a round lies well inside that gap: the edge
# is not tuned to a cell.
OVERLAP_MIN_FILL = 0.5


class DeviceRuntime:
    """TCP serving front of the device protocol step.

    Same wire protocol as ``ProcessRuntime``'s client plane (ClientHi /
    ClientHiAck / Submit / ToClient), so ``run_clients`` and
    ``bin/client.py`` drive it unchanged.  One driver task loops:
    drain submissions -> one device step -> route results to sessions.
    The device dispatch runs in a thread-pool executor so the event loop
    keeps accepting connections and flushing replies during the (blocking)
    device round-trip; the sessions' sockets are read between steps, not
    during them (``_step_on_pool``).
    """

    def __init__(
        self,
        config: Config,
        client_addr: Address,
        *,
        protocol: str = "epaxos",
        process_id: ProcessId = 1,
        batch_size: int = 256,
        key_buckets: int = 4096,
        key_width: int = 1,
        pending_capacity: int = 256,
        live_replicas: Optional[int] = None,
        monitor_execution_order: bool = False,
        metrics_file: Optional[str] = None,
        metrics_interval_ms: int = 5000,
        mesh=None,
        telemetry_file: Optional[str] = None,
        metrics_port: Optional[int] = None,
        trace_file: Optional[str] = None,
        flight_dir: Optional[str] = None,
        loop_selector: Optional[TimedSelector] = None,
    ):
        self.config = config
        self.process_id = process_id
        self.client_addr = client_addr
        # which driver serves the label is the drivers' to know
        self.driver = driver_for(
            protocol,
            config,
            process_id=process_id,
            batch_size=batch_size,
            key_buckets=key_buckets,
            key_width=key_width,
            pending_capacity=pending_capacity,
            live_replicas=live_replicas,
            monitor_execution_order=monitor_execution_order,
            mesh=mesh,
        )
        # in-flight depth: Config.serving_pipeline_depth, else 1
        # (run/pipeline.py)
        self.pipeline_depth = resolve_pipeline_depth(config)
        self.driver.pipeline_depth = self.pipeline_depth
        # dispatch/drain overlap needs a compute resource besides the
        # host cores: on a CPU backend "device" rounds and the emit loop
        # share the same cores, so it is on off the CPU only — unless a
        # pipeline depth was configured, which IS the opt-in (depth > 1
        # is meaningless without overlap)
        device0 = np.asarray(self.driver.mesh.devices).flat[0]
        self.pipeline = (
            getattr(device0, "platform", "cpu") != "cpu"
            or config.serving_pipeline_depth is not None
        )
        # adaptive ingest batching (run/ingest.py): accumulate queued
        # submissions until the EWMA size target or the deadline budget
        # fills, so rounds dispatch full under load; the idle-system
        # fast path keeps the lone closed-loop command synchronous.
        # Deadline 0 turns the gate off (dispatch on anything)
        self.ingest_deadline_ms = resolve_ingest_deadline_ms(config)
        chain_max = resolve_serving_chain_max(config)
        self._batcher = AdaptiveIngestBatcher(
            self.ingest_deadline_ms,
            # the size target never exceeds what one release can carry:
            # a full chain of full rounds
            max_target=self.driver.batch_size * chain_max,
            fixed_target=config.ingest_target,
        )
        # chained-by-default serving: every call of PipelineCore.serve
        # may carry up to S rounds (Newt runs them as ONE device
        # program), with S auto-tuned from the measured per-round
        # dispatch overhead vs in-dispatch time
        self._chain_tuner = ChainAutoTuner(chain_max)
        # a dot per coordinator: the replica at site ``s`` is process
        # ``process_id + s``, one sequence a site (site 0: this process)
        self.dot_gen = AtomicIdGen(process_id)
        self._site_dot_gens = {0: self.dot_gen}
        self.metrics_file = metrics_file
        self.metrics_interval_ms = metrics_interval_ms
        # live telemetry plane (observability/timeseries.py): one writer,
        # one cadence (Config.telemetry_interval_ms beats the argument)
        # for the windowed series AND the legacy JSON tallies snapshot
        self.telemetry_interval_ms = (
            config.telemetry_interval_ms
            if config.telemetry_interval_ms is not None
            else metrics_interval_ms
        )
        from fantoch_tpu.core.timing import RunTime

        self.time = RunTime()
        self.telemetry = None
        if telemetry_file is not None:
            from fantoch_tpu.observability.timeseries import SeriesWriter

            self.telemetry = SeriesWriter(
                telemetry_file, self.time,
                window_ms=self.telemetry_interval_ms,
            )
        # lifecycle tracing at the serving edge: client-hop edges plus
        # payload/executed spans per command (the device rounds stay
        # batch-attributed through the per-dispatch counters), so
        # `bin/obs.py critpath` stitches device serving traces too
        from fantoch_tpu.observability.tracer import NOOP_TRACER, Tracer

        self.tracer = NOOP_TRACER
        if trace_file is not None and config.trace_sample_rate > 0:
            self.tracer = Tracer(
                self.time, trace_file, config.trace_sample_rate, clock="wall"
            )
        # failure flight recorder (observability/recorder.py): black box
        # dumped on fatal driver failures
        self.flight = None
        self.flight_dir = flight_dir
        if config.flight_recorder:
            from fantoch_tpu.observability.exposition import profile_output_dir
            from fantoch_tpu.observability.recorder import FlightRecorder

            if self.flight_dir is None:
                self.flight_dir = profile_output_dir(
                    trace_file, telemetry_file, metrics_file
                )
            self.flight = FlightRecorder(
                self.time, pid=process_id, inner=self.tracer
            )
            self.tracer = self.flight
        self.metrics_port = metrics_port
        self.metrics_server = None
        # serving-edge throughput tallies (the submit/reply rate series)
        self.submitted = 0
        self.replied = 0
        # results route to the session that submitted the rifl (a client
        # holds one connection per shard; only the target shard's carries
        # the Submit)
        self.rifl_sessions: Dict[Rifl, _DeviceClientSession] = {}
        # the sessions whose hello was acknowledged and that have not been
        # dropped: the sockets a step holds (_step_on_pool)
        self._sessions: set = set()
        # a hold is on
        self._reads_held = False
        # the sessions a release found with bytes waiting and whose read
        # has not come in yet; set while there is none (_driver_task)
        self._reads_due: set = set()
        self._reads_in = asyncio.Event()
        self._reads_in.set()
        # dispatches made while live sessions' sockets were held
        self._held_dispatches = 0
        # bounded submit ring (run/pipeline.py): the device serving
        # loop's admission edge.  Config.admission_limit bounds queued
        # submissions; past it sessions shed with a typed Overloaded
        # reply + retry-after hint (None = legacy unbounded)
        self._submit_queue: BoundedSubmitRing = BoundedSubmitRing(
            capacity=config.admission_limit
        )
        # where a round's host time goes (observability/device.py
        # StageRecorder): the driver's recorder, shared, so the loop's
        # stages and the step's land in one ring on one clock
        self.stages = self.driver.stages
        # the selector of the loop this runtime is made on, where the loop
        # was made over one that reads the clock (``bin/server``): from
        # here on each of the loop's visits to it is the recorder's, which
        # keeps the loop's thread's own account (loop_busy_ms and the rest);
        # without one the snapshot has none of those counters
        if loop_selector is not None:
            loop_selector.recorder = self.stages
        # who had the CPU meanwhile: this thread (the loop's) and the
        # pool's, sampled where the tallies are published and by the probe
        self.account = ThreadAccount()
        # the per-command boundaries: two clock reads each, no span
        # [ns, frames, reads, CPU ns, timed ns, due ns, frames of a kind]
        # of turning socket reads into messages, shared with every Rw
        self._decode_tally = [0, 0, 0, 0, 0, 0, 0]
        # what the sessions count (run/device_session.py), on one object
        # they all write and ``_publish_tallies`` reads
        self.session_tallies = SessionTallies()
        self._queue_wait_ms = 0.0  # sum over released commands, ring time
        self._queue_released = 0
        # the slices ``_collect`` took from the ring: a read's run, or
        # the part of one a round had room for
        self._collect_slices = 0
        # the loop's own lateness (_lag_task)
        self._loop_lag_hwm_ms = 0.0
        self._loop_stall_ms = 0.0
        self._loop_stalls = 0
        # full collections of the cyclic collector run on the driver
        # task's schedule, not on the allocator's (run/collector.py)
        self._collector = CollectorSchedule()
        from fantoch_tpu.parallel.mesh_step import shards_on_devices

        self._shards_on_device = shards_on_devices(
            self.driver.mesh,
            config.n * self.driver.shard_count,
            self.driver.shard_count,
        )
        self._tallies: Dict[str, int] = {}
        self._publish_tallies()
        self._work = asyncio.Event()
        self._tasks: set = set()
        self._servers: List[Any] = []
        self.failure: Optional[BaseException] = None
        self.failed = asyncio.Event()

    # --- lifecycle (mirrors ProcessRuntime's loud-failure contract) ---

    def spawn(self, coro, *, fatal: bool = True) -> asyncio.Task:
        """``fatal=True`` tasks (the driver loop, metrics) take the whole
        runtime down on crash; ``fatal=False`` tasks (per-client sessions)
        die alone — one misbehaving connection must not stop serving the
        others (fantoch/src/run/task/process.rs:320-325)."""
        task = asyncio.ensure_future(coro)
        task.add_done_callback(
            self._on_task_done if fatal else self._on_session_done
        )
        self._tasks.add(task)
        return task

    def _on_task_done(self, task: asyncio.Task) -> None:
        self._tasks.discard(task)
        if task.cancelled():
            return
        exc = task.exception()
        if exc is not None:
            logger.error("device runner task crashed: %r", exc)
            if self.failure is None:
                self.failure = exc
                self.failed.set()
                if self.flight is not None:
                    try:
                        self.flight.dump(
                            f"{self.flight_dir}/flight_p{self.process_id}.json",
                            f"{type(exc).__name__}: {exc}",
                        )
                    except OSError as dump_exc:
                        logger.error("flight dump failed: %r", dump_exc)
            self._teardown()

    def _on_session_done(self, task: asyncio.Task) -> None:
        self._tasks.discard(task)
        if task.cancelled():
            return
        exc = task.exception()
        if exc is not None:
            logger.warning("device client session closed with error: %r", exc)

    def _teardown(self) -> None:
        for task in list(self._tasks):
            task.cancel()
        for server in self._servers:
            server.close()

    async def start(self) -> None:
        from fantoch_tpu.core.compile_cache import ensure_compile_cache
        from fantoch_tpu.observability.device import subscribe_recompiles

        subscribe_recompiles()
        # persistent compile cache before the first plane dispatch:
        # restarted/rebuilt runners reload their programs from disk
        # instead of re-paying the compile wall
        ensure_compile_cache()
        # every chain length the tuner may pick, compiled or loaded here,
        # before a client can connect: the serving loop compiles nothing
        self._chain_tuner.limit_to(
            self.driver.precompile_chains(self._chain_tuner.ladder())
        )
        server = await asyncio.start_server(self._on_client, *self.client_addr)
        self._servers = [server]
        self.spawn(self._driver_task())
        self.spawn(self._lag_task())
        if self.metrics_file is not None or self.telemetry is not None:
            self.spawn(self._telemetry_task())
        if self.metrics_port is not None:
            from fantoch_tpu.observability.exposition import MetricsServer

            self.metrics_server = MetricsServer(
                self.telemetry_sample,
                self.metrics_port,
                labels={"pid": str(self.process_id)},
                profile_dir=self._profile_dir(),
            )
            await self.metrics_server.start()
            self.metrics_port = self.metrics_server.port
        # last, with everything start-up built alive and no client served
        # yet: jax, the programs, the driver's tables leave the collector
        self._collector.take_over()
        gc.callbacks.append(self._on_gc)

    def _publish_tallies(self) -> None:
        """Called on the event-loop thread between device rounds (never
        concurrently with driver.step, which runs to completion on the
        pool thread before the loop resumes): the snapshot task reads this
        consistent copy, not live counters mid-mutation."""
        from fantoch_tpu.observability.device import (
            cache_hit_count,
            cache_miss_count,
            compile_ms,
            recompile_count,
        )

        d = self.driver
        # the session plane's own counts (run/device_session.py)
        s = self.session_tallies
        decode_ns, decoded, reads, decode_cpu_ns, decode_timed_ns, _, plain_decoded = (
            self._decode_tally
        )
        self._tallies = {
            "submitted": self.submitted,
            # ... of them tracked by their rifl alone (one key, one shard)
            "session_flat_admitted": s.flat_admitted,
            "replied": self.replied,
            "rounds": d.rounds,
            "executed": d.executed,
            "executed_in_pass": d.executed_in_pass,
            "executed_off_wire": d.executed_off_wire,
            "drain_rows_walked": d.drain_rows_walked,
            "requeued": d.requeued,
            "fast_paths": d.fast_paths,
            "slow_paths": d.slow_paths,
            # the dep-commit round's tallies over its executed rows, and
            # its gauges; the sites clients have registered at
            **d.round_tallies,
            **d.round_gauges,
            "sites_registered": d.sites_registered,
            "in_flight": d.in_flight,
            "stable_watermark": d.stable_watermark,
            "queued": len(self._submit_queue),
            # overload plane: submit-ring bound, depth high-watermark,
            # and admission sheds (run/pipeline.BoundedSubmitRing)
            "queued_hwm": self._submit_queue.depth_hwm,
            "queue_capacity": self._submit_queue.capacity or 0,
            "shed_submissions": self._submit_queue.sheds,
            # per-dispatch device counters (observability/device.py)
            **d.device_counters(),
            # ... of the dispatches, those whose step had live sessions'
            # sockets held (_step_on_pool)
            "device_held_dispatches": self._held_dispatches,
            # a round's host time by stage: stage_<name>_ms / _n, for the
            # stages that compute _cpu_ms; the probe's stalls by class
            **self.stages.counters(),
            # the two served threads' CPU time and run-queue wait, the
            # process's CPU time, page faults and involuntary switches
            **self.account.counters(self.PUBLISH_SAMPLE_FRESH_NS),
            # the per-command boundaries around the rounds
            "session_decode_ms": round(decode_ns / 1e6, 3),
            "session_decode_cpu_ms": round(decode_cpu_ns / 1e6, 3),
            "session_decode_timed_ms": round(decode_timed_ns / 1e6, 3),
            "session_decoded": decoded,
            # ... of them the frames that said their kind in a byte and
            # went as plain values (a Submit; the handshake is a pickle)
            "session_plain_decoded": plain_decoded,
            "session_reads": reads,
            "session_admit_ms": round(s.admit_ns / 1e6, 3),
            "session_admit_cpu_ms": round(s.admit_cpu_ns / 1e6, 3),
            "session_admit_timed_ms": round(s.admit_timed_ns / 1e6, 3),
            # wall minus CPU of the stages that only compute and of the
            # two session counters: charged to a stage while its thread
            # was not running (from the spans and reads that took a CPU
            # pair: all of them where a round outlasts CPU_PAIR_EVERY_NS)
            "stage_wait_ms": round(
                (
                    self.stages.wait_ns()
                    + off_cpu_ns(decode_ns, decode_timed_ns, decode_cpu_ns)
                    + off_cpu_ns(s.admit_ns, s.admit_timed_ns, s.admit_cpu_ns)
                )
                / 1e6,
                3,
            ),
            "queue_wait_ms": round(self._queue_wait_ms, 3),
            "queue_released": self._queue_released,
            "collect_slices": self._collect_slices,
            "reply_flush_ms": round(s.flush_ns / 1e6, 3),
            "reply_flushes": s.flushes,
            "reply_writes": s.reply_writes,
            "reply_bytes": s.reply_bytes,
            # a command over several shards answers once per shard:
            # frames written, commands whose last shard replied, and
            # those of them that touched more than one shard
            "shard_replies": s.shard_replies,
            "reply_plain_frames": s.reply_plain_frames,
            "reply_flat_frames": s.reply_flat_frames,
            "reply_partial_frames": s.reply_partial_frames,
            "commands_completed": s.commands_completed,
            "multi_shard_completed": s.multi_shard_completed,
            # reads: read-only commands completed, the bytes of the values
            # their replies carried, and the records the store holds now
            "gets_replied": s.gets_replied,
            "get_value_bytes": s.get_value_bytes,
            "store_records": len(d.store),
            # the event loop's lateness: worst wake-up, and the sum and
            # count of wake-ups later than LOOP_STALL_MS (by class among
            # the stage counters: loop_stall_<class>_ms, loop_stopped_ms)
            "loop_lag_hwm_ms": round(self._loop_lag_hwm_ms, 3),
            "loop_stall_ms": round(self._loop_stall_ms, 3),
            "loop_stalls": self._loop_stalls,
            # the cyclic collector under the driver's schedule: beside
            # stage_gc_ms / _n, what was frozen out of it at start-up, the
            # full collections the driver ran, those it did not ask for
            # (expected 0) and the objects they found unreachable
            **self._collector.counters(),
            # adaptive ingest batcher tallies (run/ingest.py)
            **self._batcher.counters(),
            # where the chain tuner stands: its S (serving_chain_len is
            # what the last dispatch carried) and how often it has moved
            "serving_chain": self._chain_tuner.chain,
            "chain_adjustments": self._chain_tuner.adjustments,
            "precompiled_programs": d.precompiled_programs,
            "jax_recompiles": recompile_count(),
            "jax_compile_ms": compile_ms(),
            "jax_cache_hits": cache_hit_count(),
            "jax_cache_misses": cache_miss_count(),
        }

    def backend_report(self) -> Dict[str, Any]:
        """What serves: platform, device kind and count as jax reports
        them, plus the (replica x batch) mesh shape — carried by the
        "serving clients" banner and every metrics snapshot, so a parent
        script can tell a chip run from a CPU run."""
        from fantoch_tpu.hostenv import device_report

        d = self.driver
        # what the driver says of its round: the dep-commit round's
        # resolver, quorum rule and (fast, write) quorum sizes, the leader
        # round's name and accept quorum
        named = {
            "resolver": d.resolver,
            "rule": d.rule,
            "quorums": d.rule and [d.fast_quorum, d.write_quorum],
            "round": d.round_name,
            "accept_quorum": d.accept_quorum,
        }
        return {
            **device_report(),
            "mesh_shape": {
                axis: int(size) for axis, size in self.driver.mesh.shape.items()
            },
            # the shards whose replica rows each device holds, in the
            # mesh's device order
            "shards_on_device": self._shards_on_device,
            **{key: value for key, value in named.items() if value},
        }

    def _write_metrics_snapshot(self) -> None:
        """Crash-consistent JSON tallies of the device rounds (the
        metrics-logger analog for the serving mode — round/path counts
        instead of per-message histograms; NOTE the on-disk format is JSON,
        not the process runner's gzip+pickle ProcessMetrics), plus the
        ``backend`` that produced them."""
        from fantoch_tpu.run.observe import write_json_snapshot

        write_json_snapshot(
            self.metrics_file,
            {
                **self._tallies,
                "backend": self.backend_report(),
                # where captures and round_spans.json land
                "profile_dir": self._profile_dir(),
            },
        )

    def _profile_dir(self) -> str:
        from fantoch_tpu.observability.exposition import profile_output_dir

        return profile_output_dir(
            self.telemetry and self.telemetry.path, self.metrics_file
        )

    # gauge-natured tally keys: instantaneous values, not monotone
    # counters — the series and the exposition type them accordingly
    _GAUGE_TALLIES = frozenset({
        "in_flight", "stable_watermark", "queued", "queued_hwm",
        "queue_capacity", "device_idle_frac", "device_pipeline_depth",
        "dispatch_fill_frac", "serving_chain_len", "serving_chain", "ingest_target",
        "ingest_rate_per_s", "loop_lag_hwm_ms", "precompiled_programs",
        "gc_frozen_objects", "sites_registered", "scc_rows_max", "wait_passes",
    })

    def telemetry_sample(self):
        """The (counters, gauges, hists) triple for the series writer and
        the ``/metrics`` exposition, split out of the published tallies
        (names stay the bench/tally keys)."""
        counters: Dict[str, float] = {}
        gauges: Dict[str, float] = {}
        for name, value in self._tallies.items():
            (gauges if name in self._GAUGE_TALLIES else counters)[name] = value
        return counters, gauges, {}

    def _emit_telemetry(self) -> None:
        if self.telemetry is not None:
            counters, gauges, hists = self.telemetry_sample()
            self.telemetry.emit(
                f"p{self.process_id}", counters, gauges, hists
            )
            self.telemetry.flush()
        if self.metrics_file is not None:
            self._write_metrics_snapshot()

    def emit_final(self) -> None:
        """What a stopping server leaves behind (``stop()``, and the
        SIGTERM / Ctrl-C path of ``bin/server``): the last tallies as a
        snapshot, and the ring of round-stage spans as
        ``round_spans.json`` beside the captures.  Nothing without a
        metrics or telemetry file."""
        if self.metrics_file is None and self.telemetry is None:
            return
        self._publish_tallies()
        self._emit_telemetry()
        self.stages.dump(os.path.join(self._profile_dir(), "round_spans.json"))

    async def _telemetry_task(self) -> None:
        while True:
            await asyncio.sleep(self.telemetry_interval_ms / 1000)
            # a file written on the loop: under a span, so a stall of the
            # loop that falls here has a name
            with self.stages.span("snapshot"):
                self._emit_telemetry()
                self.tracer.flush()

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        """``gc.callbacks`` hook: a full (generation 2) collection stops
        every thread, under the GIL; it goes into the ring as a ``gc``
        entry so a late loop can be laid beside it, and its duration is
        what the collector's schedule reads."""
        span = self._collector.note(phase, info)
        if span is not None:
            self.stages.record("gc", *span)

    async def stop(self) -> None:
        if self.metrics_server is not None:
            await self.metrics_server.stop()
        tasks = list(self._tasks)
        self._teardown()
        await asyncio.gather(*tasks, return_exceptions=True)
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        self.emit_final()
        self.account.close()
        # the process's collector as start() found it
        self._collector.hand_back()
        if self.telemetry is not None:
            self.telemetry.close()
        self.tracer.close()

    # --- client plane ---

    async def _on_client(self, reader, writer) -> None:
        session = _DeviceClientSession(
            self, Rw(reader, writer, decode_tally=self._decode_tally, stages=self.stages)
        )
        self.spawn(session.run(), fatal=False)

    def register_site(self, site: int):
        """The site a hello names (``ClientHi.site``; 0 where it names
        none): the driver is told (it makes the round with a coordinator
        at every site ready at the first site but 0, and raises
        ``ValueError`` for a site it cannot serve), and the caller gets
        the coordinator's dot generator, ``next_id`` of
        ``AtomicIdGen(process_id + site)``."""
        if type(site) is not int:
            raise ValueError(f"a site is a replica's number, not {site!r}")
        self.driver.register_site(site)
        gen = self._site_dot_gens.get(site)
        if gen is None:
            gen = self._site_dot_gens[site] = AtomicIdGen(self.process_id + site)
        return gen.next_id

    @property
    def submit_ring(self) -> BoundedSubmitRing:
        """The bounded ring admitted commands wait in, for the session
        that sheds at its bound: the shed is counted on it and the reply
        carries its depth and limit."""
        return self._submit_queue

    def room(self) -> Optional[int]:
        """Admission check for sessions: how many more commands the
        submit ring takes before it sits at its bound (None: unbounded);
        past that the session sheds with a typed Overloaded reply
        instead of queueing.  Count-then-submit is race-free: sessions
        and the driver share one cooperative loop."""
        ring = self._submit_queue
        return None if ring.capacity is None else ring.capacity - len(ring)

    def retry_after_ms(self) -> int:
        """The shed reply's retry-after hint, scaled by how many rounds
        of drain the current backlog represents."""
        base = self.config.overload_retry_after_ms
        ring = self._submit_queue
        if ring.capacity is None:
            return base
        return base * max(1, len(ring) // max(1, self.driver.batch_size))

    def submit(self, dot: Dot, cmd: Command) -> None:
        self.submit_all([(dot, cmd)], monotonic() * 1000.0)

    def submit_all(
        self, admitted: List[Tuple[Dot, Command]], now_ms: float
    ) -> None:
        """The commands a session admitted from one socket read, in
        their order: one run of the ring, with the read's arrival time
        ``now_ms`` beside it (the ring wait of its commands is read off
        it at release, ``queue_wait_ms``).  One push, one note to the
        ingest batcher and one wake-up of the driver task for all of
        them; the list is the ring's from here on."""
        if not self._submit_queue.try_extend(admitted, now_ms):
            # unreachable via sessions (room() is counted on the same
            # cooperative tick, with no await between count and
            # submit) — a real exception, not an assert, so a future
            # caller that skips the check fails LOUDLY (the driver task
            # tears the runtime down) instead of silently dropping the
            # command under python -O
            from fantoch_tpu.errors import OverloadedError

            raise OverloadedError(
                len(self._submit_queue),
                self._submit_queue.capacity or 0,
                self.retry_after_ms(),
            )
        self.submitted += len(admitted)
        self._batcher.note_arrivals(now_ms, len(admitted))
        self._work.set()

    def add_session(self, session: "_DeviceClientSession") -> None:
        """A session whose hello was acknowledged: from here to
        ``drop_session`` its socket is among those a step holds, and one
        that joins during a hold joins the hold (its commands could be
        dispatched no sooner than the others')."""
        self._sessions.add(session)
        if self._reads_held:
            session.rw.hold_reading()

    def _read_in(self, session: "_DeviceClientSession") -> None:
        """The read a release found waiting on ``session``'s socket has
        returned (or the session has ended): the last of them lets the
        driver task collect."""
        due = self._reads_due
        due.discard(session)
        if not due:
            self._reads_in.set()

    def drop_session(self, session: "_DeviceClientSession") -> None:
        """Forget a closed session's in-flight rifls (their results have
        nowhere to go; the driver still executes them for the cluster)."""
        self._sessions.discard(session)
        self._read_in(session)
        stale = [
            rifl for rifl, s in self.rifl_sessions.items() if s is session
        ]
        for rifl in stale:
            del self.rifl_sessions[rifl]
        session.forget()

    def _deliver(self, results: List[ExecutorResult]) -> None:
        """The reply stage of a round: its results grouped by the
        session that submitted them, then one aggregate-encode-write
        pass per session."""
        rifl_sessions = self.rifl_sessions
        by_session: Dict[_DeviceClientSession, List[ExecutorResult]] = defaultdict(list)
        for result in results:
            session = rifl_sessions.get(result.rifl)
            if session is None:
                continue  # session closed mid-flight
            by_session[session].append(result)
        for session, batch in by_session.items():
            try:
                self.replied += session.deliver(batch)
            except (ConnectionError, OSError) as exc:
                # runs on the (fatal) driver task: a half-closed client
                # connection must cost only its own replies — but only
                # transport faults are session-scoped; logic errors
                # (aggregation invariants) still fail the runtime loudly
                logger.warning(
                    "dropping %d results of the round for clients %s "
                    "(dead session): %r",
                    len(batch), session.client_ids, exc,
                )

    # --- the serving loop ---

    # a wake-up later than this is a stall of the event loop: counted,
    # summed, and put into the span ring (what a round's spans say was
    # open on either thread at that time is the finding)
    LOOP_LAG_PROBE_MS = 10.0
    LOOP_STALL_MS = 20.0
    # the published tallies take the probe's last sample of the thread
    # account while it is no older than two of the probe's sleeps
    PUBLISH_SAMPLE_FRESH_NS = 20_000_000

    async def _lag_task(self) -> None:
        """The loop's own lateness: sleep ``LOOP_LAG_PROBE_MS``, measure
        how late the wake-up came, and read the thread account at every
        wake-up, so that a late one is classed by what the interval since
        the one before it cost the two served threads."""
        probe_ns = int(self.LOOP_LAG_PROBE_MS * 1e6)
        account, stages = self.account, self.stages
        before = account.sample()
        while True:
            due = monotonic_ns() + probe_ns
            await asyncio.sleep(self.LOOP_LAG_PROBE_MS / 1000.0)
            now = monotonic_ns()
            after = account.sample()
            late_ms = (now - due) / 1e6
            if late_ms > self._loop_lag_hwm_ms:
                self._loop_lag_hwm_ms = late_ms
            if late_ms > self.LOOP_STALL_MS:
                self._loop_stall_ms += late_ms
                self._loop_stalls += 1
                stages.stall(
                    due, now, AccountSample(*(b - a for a, b in zip(before, after)))
                )
            stages.settle_stalls(now)
            before = after

    async def _step_on_pool(self, round_id: int, step, *args):
        """One blocking driver call off the event loop (the listener and
        result flushes stay live during the round), between its two
        hand-offs: ``handoff`` from here to the first line on the pool
        thread, ``resume`` from the call's return there to this task
        running again.  Both share the GIL with whatever the loop does
        meanwhile.

        The live sessions' sockets are held while the step runs: the loop
        does not read them, so it takes the interpreter from the step's
        thread a reply flush at a time and not a read at a time, the kernel
        keeps what arrives, and at the release one read a socket brings it
        all, one run of the ring with one arrival time.  Nothing a read
        brings could be dispatched before the step returns (this task is
        the driver's only caller).  The release asks the kernel which
        sockets have bytes waiting (one ``poll``, beside the loop's own),
        and the driver task collects the next round once those sessions'
        reads are in (``_reads_in``)."""
        stages = self.stages
        driver = self.driver
        sessions = self._sessions
        dispatched = driver.dispatches
        self._reads_held = True
        for session in sessions:
            session.rw.hold_reading()
        called = stages.clock()

        def on_pool():
            stages.record("handoff", called, stages.clock(), round_id, "round")
            self.account.register("step")
            with stages.span("step", round_id, parent="round"):
                results = step(*args)
            return results, stages.clock()

        try:
            results, returned = await asyncio.get_running_loop().run_in_executor(
                None, on_pool
            )
            stages.record("resume", returned, stages.clock(), round_id, "round")
        finally:
            self._reads_held = False
            if sessions:
                self._held_dispatches += driver.dispatches - dispatched
                poller, by_fd = select.poll(), {}
                for session in sessions:
                    session.rw.release_reading()
                    fd = session.rw.fileno()
                    if fd >= 0:
                        by_fd[fd] = session
                        poller.register(fd, select.POLLIN)
                due = self._reads_due
                for fd, _ in poller.poll(0):
                    session = by_fd[fd]
                    due.add(session)
                    session.rw.on_read = partial(self._read_in, session)
                if due:
                    self._reads_in.clear()
        return results

    def _collect(self, round_id: int, chain: int) -> List[List[Tuple[Dot, Command]]]:
        """Up to ``chain`` rounds (the auto-tuned chain length) from the
        requeue and the released queue, canonicalised to the pow2 ladder
        of chain lengths.  A round's batch is the requeue's head, then
        the ring's runs whole while they fit, then the head of the run
        that does not: slices, each counted once (``collect_slices``),
        and no command is touched alone while the tracer is off."""
        driver = self.driver
        ring = self._submit_queue
        size = driver.batch_size
        take = ring.take
        tracer = self.tracer
        tracing = tracer.enabled
        batches: List[List[Tuple[Dot, Command]]] = []
        pending = driver.take_requeue()
        taken = 0  # of the requeue
        released = 0
        slices = 0
        arrived_ms = 0.0
        while (taken < len(pending) or ring) and len(batches) < chain:
            batch = pending[taken : taken + size]
            taken += len(batch)
            while ring and len(batch) < size:
                run, at_ms = take(size - len(batch))
                if tracing:
                    # batch release: payload->ingest is the queue +
                    # batching wait (critpath's ingest-batching
                    # bucket); the round says which `rs` slice it rode
                    for dot, cmd in run:
                        tracer.span(
                            "ingest", cmd.rifl, dot=dot, pid=self.process_id,
                            meta={"round": round_id},
                        )
                batch += run
                slices += 1
                released += len(run)
                arrived_ms += len(run) * at_ms
            batches.append(batch)
        pending = pending[taken:]
        if len(batches) > 1:
            # canonicalize the dispatched chain length to the pow2
            # ladder: each chain length is a program of its own, and
            # the ladder's are the ones start-up made ready, so
            # dispatching whatever 1..S rounds the queue happened to
            # fill would compile inside the serving loop — truncate to
            # the pow2 floor and requeue the remainder (it leads the
            # next chain)
            keep = 1
            while keep * 2 <= len(batches):
                keep *= 2
            for batch in reversed(batches[keep:]):
                pending[:0] = batch
            del batches[keep:]
        if pending:
            # overflow past S full rounds goes back to the requeue
            # (next iteration dispatches it first)
            driver.give_back(pending)
        if released:
            now_ms = monotonic() * 1000.0
            self._batcher.note_release(now_ms, released)
            self._queue_wait_ms += released * now_ms - arrived_ms
            self._queue_released += released
            self._collect_slices += slices
        return batches or [[]]  # nothing queued: a pending-buffer progress round

    async def _serve_round(self, whole, step, *args) -> List[ExecutorResult]:
        """The tail every round shares, inside its ``round`` span
        ``whole``: the step on the pool thread, delivery of what it
        executed, the published tallies.  The span keeps the reads among
        what the step executed, where the round tallies them."""
        stages = self.stages
        round_id = whole.round
        reads_before = self.driver.round_tallies.get("read_rows")
        results = await self._step_on_pool(round_id, step, *args)
        if reads_before is not None:
            whole.read_rows = self.driver.round_tallies["read_rows"] - reads_before
        with stages.span("deliver", round_id):
            self._deliver(results)
        with stages.span("publish", round_id):
            # feed the chain auto-tuner the cumulative overlap counters
            # (it rate-limits itself by dispatch count)
            driver = self.driver
            self._chain_tuner.observe(
                driver.dispatches,
                driver.dispatch_wall_ms,
                driver.device_counters()["device_busy_ms"],
                driver.rounds,
            )
            self._publish_tallies()
        return results

    def _trace_round(self, whole) -> None:
        """The finished round, for the operator's per-command trace."""
        if self.tracer.enabled:
            self.tracer.round_span(
                whole.name, whole.t0, whole.t1, whole.round, self.stages.clock(),
                pid=self.process_id,
            )

    async def _driver_task(self) -> None:
        driver = self.driver
        stages = self.stages
        # dispatch/drain overlap: under saturation round k+1's device
        # dispatch overlaps round k's host emit loop
        can_pipeline = self.pipeline
        batcher = self._batcher
        tuner = self._chain_tuner
        idle_rounds = 0  # empty-input rounds yielding no results
        while True:
            # between rounds, where no step runs on the pool thread (the
            # pause then stops one thread, not two, and the pipelined
            # round's program stays on the device), and before an idle
            # wait, where no command is alive to be traversed
            self._collector.run_if_due()
            # what the kernel kept through the last step comes in before
            # the next round is collected, whatever the ring already holds
            # (a read that landed in the turn the hold began in was
            # admitted during the step): else that round would carry a
            # straggler or two and hold the sockets, full by now, for a
            # second step
            await self._reads_in.wait()
            # a round is named by the number of the dispatch it makes
            round_id = driver.dispatches + 1
            if not self._submit_queue and can_pipeline and driver.has_outstanding:
                # the queue went quiet with a round still in flight:
                # retire it directly — its results must not strand, and
                # dispatching a padding-only round just to drain it would
                # waste a full device round.  A submission landing while
                # flush_pipeline runs on the pool thread is safe: this
                # task is the driver's only caller, so the flush retires
                # each in-flight round exactly once and the next loop
                # iteration re-evaluates the queue from scratch — the
                # arrival simply waits one flush, it can never interleave
                # a dispatch into the flushing pipeline
                with stages.span("round", round_id) as whole:
                    await self._serve_round(whole, driver.flush_pipeline)
                self._trace_round(whole)
                continue
            # (rounds a truncated chain handed back wait in the requeue,
            # registered nowhere yet: they are work too)
            if (
                not self._submit_queue
                and driver.in_flight == 0
                and not driver.has_requeue
            ):
                self._work.clear()
                with stages.span("idle_wait"):
                    await self._work.wait()
            # adaptive ingest gate (run/ingest.py): hold a part-empty
            # round while arrivals fill it toward the EWMA size target,
            # for at most the deadline budget.  Requeued overflow is
            # never held (it was admitted a round ago), nor are
            # pending-buffer progress rounds (empty queue, in_flight>0).
            # The idle-system fast path releases a lone closed-loop
            # command immediately, so sync latency never regresses.
            if (
                self._submit_queue
                and not driver.has_requeue
                and batcher.deadline_ms > 0
            ):
                release, wait_ms = batcher.poll(
                    monotonic() * 1000.0,
                    len(self._submit_queue),
                    idle_system=(
                        driver.in_flight == 0 and not driver.has_outstanding
                    ),
                )
                if not release:
                    self._work.clear()
                    # a submit that landed since the poll set _work
                    # before the clear — the wait returns immediately
                    with stages.span("gate_wait", round_id, parent="round"):
                        try:
                            await asyncio.wait_for(
                                self._work.wait(), timeout=wait_ms / 1000.0
                            )
                        except asyncio.TimeoutError:
                            pass
                    continue
            with stages.span("round", round_id) as whole:
                with stages.span("collect", round_id):
                    batches = self._collect(round_id, tuner.chain)
                # a dispatch left in flight is fetched when its results
                # are next wanted (the next dispatch, or the quiet-ring
                # retire above), not when it was made: the host goes on
                # to deliver, read and collect while the device runs it
                # and copies it back, at the price of one step of
                # delivery lag.  What the dispatch carries says which
                # regime it is in (OVERLAP_MIN_FILL); a lone closed-loop
                # command and every part-full round keep the immediate
                # round.  With a round in flight the path stays on: a
                # straggler behind it is no reason to block on its fetch.
                rows = sum(map(len, batches))
                pipeline = can_pipeline and (
                    driver.has_outstanding
                    or rows >= OVERLAP_MIN_FILL * driver.batch_size
                )
                # (a chain is one fused device program on Newt, S plain
                # rounds elsewhere: the driver's own business)
                results = await self._serve_round(
                    whole, driver.serve, batches, pipeline
                )
            self._trace_round(whole)
            # commands stuck in the device pending buffer (degraded quorum)
            # with no new submissions would otherwise hot-spin device
            # rounds — including overflow-requeue cycles, whose batches are
            # non-empty but commit nothing; back off whenever a round made
            # no progress and no fresh submissions wait — interruptibly,
            # so a submit arriving mid-backoff starts the next round
            # immediately
            if (
                not results
                and not self._submit_queue
                and not (can_pipeline and driver.has_outstanding)
            ):
                idle_rounds += 1
                backoff = min(0.001 * (2 ** min(idle_rounds, 6)), 0.05)
                self._work.clear()
                # a submit that landed while driver.step ran set _work
                # before the clear — check the queue itself, not the event
                if not self._submit_queue:
                    with stages.span("idle_wait"):
                        try:
                            await asyncio.wait_for(
                                self._work.wait(), timeout=backoff
                            )
                        except asyncio.TimeoutError:
                            pass
            else:
                idle_rounds = 0
