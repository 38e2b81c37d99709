"""Server side of the real runner: one process = a TCP mesh endpoint plus
worker / executor / client-session asyncio tasks.

Reference: fantoch/src/run/task/{process,executor,client}.rs and
fantoch/src/run/mod.rs:105-445.  Same architecture, asyncio-idiomatic:

* a peer listener accepts inbound connections; a reader task per inbound
  connection routes messages to workers by ``Protocol.message_index``
  (process.rs:292-326);
* outbound connections are opened to every peer (connect_to_all,
  process.rs:21-111) with a writer task per peer draining a send queue;
* ``workers`` protocol tasks pull tagged items from their own queue —
  submits, peer messages, periodic events, executed notifications — call
  into the (shared, cooperatively-scheduled) protocol state machine and
  drain its outputs (the hot ``process_task`` select loop,
  process.rs:467-678);
* ``executors`` executor clones route execution infos by key hash
  (executor.rs:14-120) and push per-key results to the client sessions
  that own each client id;
* client sessions perform the ClientHi handshake, assign dots for
  leaderless protocols (AtomicDotGen, client.rs:221-223), aggregate
  per-key results into CommandResults and stream them back.

Intra-process parallelism note: the reference guards shared protocol state
with Sequential/Atomic/Locked structure variants; here worker tasks share
one protocol object under cooperative scheduling (handlers never await), so
every variant's semantics collapse to the sequential one — the real
parallelism axis on TPU is the batched device step, not threads.
"""

from __future__ import annotations

import asyncio
from typing import Any, Dict, List, Optional, Set, Tuple

from fantoch_tpu.core.config import Config
from fantoch_tpu.core.ids import AtomicIdGen, ClientId, ProcessId, ShardId
from fantoch_tpu.core.timing import RunTime
from fantoch_tpu.errors import PeerLostError, QuorumLostError
from fantoch_tpu.observability.tracer import edge_dot
from fantoch_tpu.executor.aggregate import AggregatePending
from fantoch_tpu.executor.base import ExecutorResult
from fantoch_tpu.protocol.base import Protocol, ToForward, ToSend
from fantoch_tpu.run.links import (
    ACK_EVERY,
    KIND_ACK,
    KIND_DATA,
    LinkState,
    PeerLinks,
    ReconnectPolicy,
)
from fantoch_tpu.run.backpressure import (
    DEFAULT_QUEUE_CAPACITY,
    DEFAULT_UNACKED_CAP,
)
from fantoch_tpu.run.prelude import (
    ClientHi,
    ClientHiAck,
    DigestKeyReply,
    DigestKeyRequest,
    Overloaded,
    PingReply,
    PingReq,
    POEExecutor,
    POEProtocol,
    ProcessHi,
    Register,
    Submit,
    ToClient,
    ToPool,
    Unregister,
    WarnQueue,
)
from fantoch_tpu.run.ingest import AdaptiveIngestBatcher
from fantoch_tpu.run.routing import worker_dot_index_shift
from fantoch_tpu.run.rw import Rw, connect_with_retry, deserialize, serialize
from fantoch_tpu.utils import key_hash, logger

Address = Tuple[str, int]


def _peek_is_submit(queue: "asyncio.Queue") -> bool:
    """True when the queue's head item is a submit, without dequeuing.
    Peeks CPython's asyncio.Queue internals behind a guard: if the
    implementation detail ever changes we degrade to per-command submits
    (correct, just unbatched) instead of crashing the worker."""
    inner = getattr(queue, "_queue", None)
    if inner is None or not queue.qsize():
        return False
    try:
        return inner[0][0] == "submit"
    except (IndexError, KeyError, TypeError):
        return False


def _info_commit_dots(info: Any) -> List[Any]:
    """The commit dots a logged execution info carries (WAL replay uses
    them to advance the restored committed horizon).  Per-command infos
    expose ``.dot``; the array batches carry dot columns; dotless infos
    (detached votes, requests, slot infos) contribute none."""
    from fantoch_tpu.core.ids import Dot

    dot = getattr(info, "dot", None)
    if isinstance(dot, Dot):
        return [dot]
    dot_src = getattr(info, "dot_src", None)
    dot_seq = getattr(info, "dot_seq", None)
    if dot_src is not None and dot_seq is not None:
        return [
            Dot(int(source), int(sequence))
            for source, sequence in zip(dot_src, dot_seq)
        ]
    return []


def executor_index(info: Any, size: int) -> Optional[int]:
    """Executor routing: by key hash when the info names a key
    (fantoch/src/executor/mod.rs:161-166), else executor 0.  A ``key``
    attribute that is not a string (GraphAddBatch carries the whole key
    *array*) is not a routing key — batches go to the main executor."""
    key = getattr(info, "key", None)
    if isinstance(key, str):
        return key_hash(key) % size
    return 0


class _StampingQueue(WarnQueue):
    """Queue whose items carry their entry time — the delay line's source
    (delay.rs timestamps messages on entry, :6-39).  Inherits the
    warn-on-depth overload signal and the bounded watermark gate
    (delayed links back up first)."""

    def __init__(
        self,
        name: str,
        loop: asyncio.AbstractEventLoop,
        capacity: Optional[int] = None,
    ):
        super().__init__(name, capacity=capacity)
        self._stamp_loop = loop

    def put_nowait(self, item: Any) -> None:  # type: ignore[override]
        super().put_nowait((self._stamp_loop.time(), item))


class _ClientSession:
    """Server side of one client connection (client.rs:79-260)."""

    def __init__(self, runtime: "ProcessRuntime", rw: Rw):
        self.runtime = runtime
        self.rw = rw
        # buffer_early: on a non-target shard the server-side forward can
        # execute the command before this connection's Register arrives
        self.pending = AggregatePending(
            runtime.process.id, runtime.process.shard_id, buffer_early=True
        )
        self.client_ids: List[ClientId] = []
        self._flush_needed = asyncio.Event()

    def deliver(self, result: ExecutorResult) -> None:
        self._emit(self.pending.add_executor_result(result))

    def _shed(self, rifl, depth: int, limit: int) -> None:
        """Admission control: reject a submission with a typed Overloaded
        reply + retry-after hint instead of queueing past the bound —
        warn-then-shed where the reference warn-then-blocks (chan.rs:
        36-58); blocking is the *reader pause* below, reserved for depths
        between the admission limit and the hard queue capacity."""
        runtime = self.runtime
        runtime.shed_submissions += 1
        retry_after = runtime.config.overload_retry_after_ms * max(
            1, depth // max(1, limit)
        )
        from fantoch_tpu.run.backpressure import log_per_doubling

        if log_per_doubling(runtime.shed_submissions):
            logger.warning(
                "p%s: shedding submission %s (edge depth %d >= admission "
                "limit %d; retry after %dms; %d sheds total)",
                runtime.process.id, rifl, depth, limit, retry_after,
                runtime.shed_submissions,
            )
        self.rw.write(Overloaded(rifl, retry_after, depth, limit))
        self._flush_needed.set()

    def _emit(self, cmd_result) -> None:
        if cmd_result is not None:
            self.runtime.replied += 1
            tracer = self.runtime.tracer
            if tracer.enabled:
                # the send half of the coordinator->client hop: with the
                # client's own `reply` span event this brackets the
                # return network flight (critpath's reply_net split)
                tracer.edge(
                    "s", "Reply", self.runtime.process.id, 0, 0,
                    rifl=cmd_result.rifl,
                )
            self.rw.write(ToClient(cmd_result))
            self._flush_needed.set()  # single per-session flusher picks it up

    async def _flush_loop(self) -> None:
        while True:
            await self._flush_needed.wait()
            self._flush_needed.clear()
            try:
                await self.rw.flush()
            except (ConnectionError, OSError):
                return  # session torn down by run()'s recv seeing EOF

    async def run(self) -> None:
        hi = await self.rw.recv()
        if hi is None:
            return  # client vanished before the handshake
        assert isinstance(hi, ClientHi)
        self.client_ids = hi.client_ids
        for client_id in self.client_ids:
            self.runtime.client_sessions[client_id] = self
        flusher = None
        try:
            # ack AFTER registration: the client holds submissions until
            # every shard acks, so a partial can never arrive before its
            # session is routable (the ClientHi-vs-execution race)
            await self.rw.send(ClientHiAck())
            flusher = self.runtime.spawn(self._flush_loop())
            while True:
                msg = await self.rw.recv()
                if msg is None:
                    break
                if isinstance(msg, Register):
                    # non-target shard of a multi-shard command: start
                    # result aggregation for our part, but do not submit
                    # (the target shard's MForwardSubmit drives our
                    # protocol instance)
                    self.pending.wait_for(msg.cmd)
                    self._emit(self.pending.drain_early(msg.cmd.rifl))
                    continue
                if isinstance(msg, Unregister):
                    # the client deadline-shed a multi-shard command the
                    # target shard never admitted: drop our aggregation
                    # entry or it leaks for the session's life
                    self.pending.cancel(msg.rifl)
                    continue
                assert isinstance(msg, Submit)
                cmd = msg.cmd
                self.runtime.submitted += 1
                tracer = self.runtime.tracer
                if tracer.enabled:
                    # ingress edge: the recv half of the client->server
                    # hop — splits submit->payload into network flight
                    # vs coordinator ingest queue in the critpath report
                    tracer.edge(
                        "r", "Submit", 0, self.runtime.process.id, 0,
                        rifl=cmd.rifl,
                    )
                limit = self.runtime.config.admission_limit
                if limit is not None:
                    depth = self.runtime.admission_depth()
                    if depth >= limit:
                        # shed BEFORE wait_for: a rejected command must
                        # leave no aggregation state (the retry re-runs
                        # the full submit path)
                        self._shed(cmd.rifl, depth, limit)
                        continue
                self.pending.wait_for(cmd)
                self._emit(self.pending.drain_early(cmd.rifl))
                dot = (
                    self.runtime.next_dot()
                    if self.runtime.protocol_cls.leaderless()
                    else None
                )
                index = (
                    worker_dot_index_shift(dot)
                    if dot is not None
                    else (0, 0)  # leader-based: submit handled by any worker
                )
                self.runtime.workers.forward(index, ("submit", dot, cmd))
                if self.runtime.workers.gated:
                    # cooperative backpressure at the client edge: stop
                    # reading this client's socket until the worker pool
                    # drains below its low watermark — the client's TCP
                    # stream stalls instead of our heap growing
                    self.runtime.backpressure_pauses += 1
                    await self.runtime.workers.wait_for_credit()
        except (ConnectionError, OSError) as exc:
            # a lost client is the client's problem, not the cluster's:
            # unregister and keep serving everyone else
            logger.warning(
                "client session %s lost mid-run: %r", self.client_ids, exc
            )
        finally:
            if flusher is not None:
                flusher.cancel()
            for client_id in self.client_ids:
                self.runtime.client_sessions.pop(client_id, None)


class ProcessRuntime:
    def __init__(
        self,
        protocol_cls: type,
        process_id: ProcessId,
        shard_id: ShardId,
        config: Config,
        listen_addr: Address,
        client_addr: Address,
        peers: Dict[ProcessId, Address],
        sorted_processes: List[Tuple[ProcessId, ShardId]],
        workers: int = 1,
        executors: int = 1,
        multiplexing: int = 1,
        peer_delays: Optional[Dict[ProcessId, int]] = None,
        ping_sort: bool = False,
        metrics_file: Optional[str] = None,
        metrics_interval_ms: int = 5000,
        execution_log: Optional[str] = None,
        tracer_show_interval_ms: Optional[int] = None,
        reconnect_policy: Optional[ReconnectPolicy] = None,
        send_timeout_s: float = 30.0,
        heartbeat_interval_s: Optional[float] = 1.0,
        heartbeat_misses: int = 8,
        trace_file: Optional[str] = None,
        wal_dir: Optional[str] = None,
        wal_snapshot_interval_ms: int = 2000,
        telemetry_file: Optional[str] = None,
        metrics_port: Optional[int] = None,
        flight_dir: Optional[str] = None,
    ):
        self.protocol_cls = protocol_cls
        self.config = config
        self.listen_addr = listen_addr
        self.client_addr = client_addr
        self.peers = peers
        self.sorted_processes = sorted_processes
        self.time = RunTime()

        self.process: Protocol
        self.process, self.periodic_events = protocol_cls.new(process_id, shard_id, config)
        # sanity: non-parallel components can't be split across tasks
        # (run/mod.rs:191-209)
        if not protocol_cls.parallel():
            workers = 1
        if not protocol_cls.Executor.parallel():
            executors = 1
        # multi-shard graph executors answer peer-shard dependency requests
        # on the secondary executor (executor.rs:242-262): fail fast here
        # rather than hang when a GraphRequest cannot be routed
        if config.shard_count > 1 and hasattr(protocol_cls.Executor, "executor_index_of"):
            assert executors >= 2, (
                "shard_count > 1 needs executors >= 2 (main + secondary "
                "request-serving executor)"
            )
        # overload-control plane (run/backpressure.py): every run-layer
        # queue is bounded with a watermark credit gate (None in the
        # config = the built-in default; an explicit 0 = legacy
        # unbounded warn-only queues), socket readers pause on closed
        # gates, and the client edge sheds past Config.admission_limit
        self.queue_capacity: Optional[int] = (
            DEFAULT_QUEUE_CAPACITY
            if config.queue_capacity is None
            else (config.queue_capacity or None)
        )
        self.link_unacked_cap = (
            DEFAULT_UNACKED_CAP
            if config.link_unacked_cap is None
            else config.link_unacked_cap
        )
        self.shed_submissions = 0
        self.backpressure_pauses = 0
        # consistency-audit plane (core/audit.py): per-key chained
        # execution digests live in the executors' KVStores when
        # Config.execution_digests is on; the heartbeat piggybacks
        # summaries so replicas cross-audit each other online
        self.digest_checks = 0
        self.digest_mismatches = 0
        self.workers = ToPool("workers", workers, capacity=self.queue_capacity)
        self.executor_pool = ToPool(
            "executors", executors, capacity=self.queue_capacity
        )
        if executors > 1:
            # batched array commit seams (Newt's TableVotesArrays) span
            # keys, but a multi-executor pool routes infos per key — fall
            # back to per-command infos so key ownership stays intact
            set_commit_arrays = getattr(self.process, "set_commit_arrays", None)
            if set_commit_arrays is not None:
                set_commit_arrays(False)
        self.executors = [
            protocol_cls.Executor(process_id, shard_id, config) for _ in range(executors)
        ]
        for index, executor in enumerate(self.executors):
            executor.set_executor_index(index)
        # restart plane (run/wal.py): durable command log + snapshots.
        # Recovery runs HERE — before executor state sharing and tracer
        # wiring — so everything downstream operates on restored objects.
        self.wal = None
        self.incarnation = 0
        self._recovered = False
        self._dot_lease = 0
        self._lease_gap_dots: List[Any] = []
        self._wal_snapshot_interval_ms = wal_snapshot_interval_ms
        if wal_dir is not None:
            from fantoch_tpu.run.wal import Wal, resolve_wal_sync

            self.wal = Wal(wal_dir, sync=resolve_wal_sync(config.wal_sync))
            self._recover_from_wal()
        # secondary request-serving executors share the primary's vertex
        # index (the reference's SharedMap across clones, index.rs:19-22):
        # peer-shard requests must be answerable from *pending* vertices or
        # cross-shard dependency cycles deadlock
        share = getattr(type(self.executors[0]), "share_state_from", None)
        if share is not None:
            for executor in self.executors[1:]:
                executor.share_state_from(self.executors[0])
        # accelerator fault tolerance: arm the pool's device planes
        # (deadline/shadow knobs travel in the config; FANTOCH_DEVICE_FAULT
        # env specs rehearse deterministic failures on a live rig) and
        # dump the flight ring on every failover.  After a WAL restore
        # this re-attaches the live handles the pickled planes dropped.
        self._arm_device_faults()
        self.dot_gen = AtomicIdGen(process_id)
        if self._dot_lease:
            # never re-issue a pre-crash sequence (the WAL dot lease)
            self.dot_gen.resume_after(self._dot_lease)
        self.client_sessions: Dict[ClientId, _ClientSession] = {}
        assert multiplexing >= 1
        self.multiplexing = multiplexing
        self._peer_writers: Dict[ProcessId, PeerLinks] = {}
        # crash tolerance (run/links.py): reconnect schedule, per-send
        # timeout, heartbeat failure detector, quorum-aware degradation
        self.reconnect_policy = reconnect_policy or ReconnectPolicy()
        self.send_timeout_s = send_timeout_s
        self.heartbeat_interval_s = heartbeat_interval_s
        self.heartbeat_misses = heartbeat_misses
        self.dead_peers: Set[ProcessId] = set()
        # failure detector state: last loop-time any frame arrived from a
        # peer (readers update it; the heartbeat task judges silence)
        self._last_heard: Dict[ProcessId, float] = {}
        self._shard_of: Dict[ProcessId, ShardId] = dict(sorted_processes)
        # receiver-side dedup state, keyed (peer, link) so it survives
        # reconnects of the underlying TCP connection
        self._link_recv_seq: Dict[Tuple[ProcessId, int], int] = {}
        # last seen WAL incarnation per peer: a bumped incarnation means
        # the peer RESTARTED (fresh seq space) and its dedup state resets;
        # same-life reconnects keep it (run/wal.py)
        self._peer_incarnations: Dict[ProcessId, int] = {}
        # live peer-connection rws -> peer id, for the chaos hook
        self._chaos_rws: Dict[Rw, ProcessId] = {}
        # per-connection artificial delay in ms (delay.rs:6-39): outbound
        # frames to these peers pass through a FIFO delay line
        self.peer_delays = peer_delays or {}
        # latency-sort peers at startup via in-band ping (ping.rs:13-78)
        self.ping_sort = ping_sort
        self._ping_waiters: Dict[int, asyncio.Future] = {}
        self._ping_nonce = 0
        # observability (metrics_logger.rs / execution_logger.rs / tracer.rs)
        self.metrics_file = metrics_file
        self.metrics_interval_ms = metrics_interval_ms
        # live telemetry plane (observability/timeseries.py): ONE periodic
        # writer covers both the windowed series and the legacy pickle
        # snapshot, on ONE cadence — Config.telemetry_interval_ms when
        # set, else the metrics_interval_ms argument
        self.telemetry_interval_ms = (
            config.telemetry_interval_ms
            if config.telemetry_interval_ms is not None
            else metrics_interval_ms
        )
        self.telemetry = None
        if telemetry_file is not None:
            from fantoch_tpu.observability.timeseries import SeriesWriter

            self.telemetry = SeriesWriter(
                telemetry_file, self.time, window_ms=self.telemetry_interval_ms
            )
        # Prometheus-text exposition endpoint + on-demand profile trigger
        # (observability/exposition.py); started in start()
        self.metrics_port = metrics_port
        self.metrics_server = None
        # client-edge throughput tallies: submissions seen (pre-shed) and
        # command results streamed back — the submit/reply rate series
        self.submitted = 0
        self.replied = 0
        self.tracer_show_interval_ms = tracer_show_interval_ms
        self.execution_logger = None
        if execution_log is not None:
            from fantoch_tpu.run.observe import ExecutionLogger

            self.execution_logger = ExecutionLogger(execution_log)
        # per-runtime prof registry (utils/prof.py): installed into the
        # context before tasks spawn, so several runtimes sharing one
        # Python process (the localhost harness) never blend histograms
        from fantoch_tpu.core.metrics import Metrics as _Metrics

        self.prof_registry = _Metrics()
        # per-dot lifecycle tracing (fantoch_tpu/observability): wall-clock
        # spans into this runtime's own JSONL log
        from fantoch_tpu.observability.tracer import NOOP_TRACER, Tracer

        self.tracer = NOOP_TRACER
        if trace_file is not None and config.trace_sample_rate > 0:
            self.tracer = Tracer(
                self.time, trace_file, config.trace_sample_rate, clock="wall"
            )
        # message-edge sequence for cross-process span stitching: one
        # monotone counter per sender, carried as POEProtocol.edge so the
        # receiver's recv event pairs with our send event.  Offset by the
        # WAL incarnation so a restarted life's seqs never collide with
        # the previous life's edges still present in PEERS' trace logs
        # (our own log truncates on reopen; theirs does not)
        self._edge_seq = self.incarnation << 32
        # per-peer wall-clock offsets from heartbeat RTT brackets — the
        # correlator's skew table (run/links.ClockOffsetEstimator)
        from fantoch_tpu.run.links import ClockOffsetEstimator

        self._clock_offsets = ClockOffsetEstimator()
        # failure flight recorder (observability/recorder.py): a bounded
        # ring of UNSAMPLED events teed off the same tracer seam, dumped
        # as flight_p<pid>.json on fatal failures / WAL-restart boots /
        # SIGUSR1 — every failure ships its own black box
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
        self.process.set_tracer(self.tracer)
        for executor in self.executors:
            executor.set_tracer(self.tracer)
        self._tasks: Set[asyncio.Task] = set()
        self._servers: List[asyncio.base_events.Server] = []
        self._connected = asyncio.Event()
        # set during stop()/_teardown(): reconnect loops and the failure
        # detector must stand down — a peer vanishing because the operator
        # is shutting the cluster down is not a fault (and a cancellation
        # surfacing as wait_for's TimeoutError inside the writer must not
        # resurrect the task into a reconnect loop)
        self._stopping = False
        # first task failure; .failed is awaited by harnesses so a crashed
        # worker tears the cluster down loudly instead of stalling it
        self.failure: Optional[BaseException] = None
        self.failed = asyncio.Event()

    # --- restart plane (run/wal.py) ---

    def _recover_from_wal(self) -> None:
        """Boot-time restart: load the latest snapshot, replay the log
        tail into the executors, resume the dot lease, and bump the
        incarnation.  ``start()`` triggers the rejoin sync (MSync
        catch-up past our horizon) once the mesh is connected."""
        state = self.wal.recover()
        self.incarnation = self.wal.incarnation
        self._dot_lease = state.dot_lease
        snap = state.snapshot
        replayed = 0
        if snap is not None:
            self.process = self.protocol_cls.restore(snap["protocol"])
            blobs = snap["executors"]
            assert len(blobs) == len(self.executors), (
                "executor pool size changed across restart"
            )
            from fantoch_tpu.executor.base import Executor as _Executor

            self.executors = [_Executor.restore(blob) for blob in blobs]
            for index, executor in enumerate(self.executors):
                executor.set_executor_index(index)
            if self.executor_pool.size > 1:
                # re-apply the per-key-pool arrays opt-out to the
                # restored protocol instance
                set_commit_arrays = getattr(self.process, "set_commit_arrays", None)
                if set_commit_arrays is not None:
                    set_commit_arrays(False)
            # infos queued but unconsumed at snapshot time ride the
            # snapshot (they predate the log position the tail starts at)
            for info in snap.get("queued_infos", ()):
                self._replay_info(info)
                replayed += 1
        for kind, payload in state.tail:
            if kind == "info":
                self._replay_info(payload)
                replayed += 1
        # fold every replayed commit dot into the restored protocol's
        # committed clock: the rejoin horizon (MSync) must cover the
        # tail, or peers would re-stream commits whose effects the
        # executor replay already applied — a second application would
        # execute them twice (exactly-once across restart)
        tail_dots = sorted(
            {
                dot
                for _kind, payload in state.tail
                if _kind == "info"
                for dot in _info_commit_dots(payload)
            }
            | {
                dot
                for payload in ((snap or {}).get("queued_infos", ()))
                for dot in _info_commit_dots(payload)
            }
        )
        if tail_dots:
            self.process.note_durable_commits(tail_dots)
        # slot-ordered protocols (FPaxos): the replayed infos carry slots,
        # not dots — fold them so the rejoin MSlotSync floor covers the
        # tail (re-streaming would execute the slots twice)
        tail_slot_records: Dict[int, Any] = {}
        for payload in (snap or {}).get("queued_infos", ()):
            if hasattr(payload, "slot"):
                tail_slot_records[payload.slot] = payload.cmd
        for _kind, payload in state.tail:
            if _kind == "info" and hasattr(payload, "slot"):
                tail_slot_records[payload.slot] = payload.cmd
        if tail_slot_records:
            self.process.note_durable_chosen(sorted(tail_slot_records.items()))
        # the dot lease's unissued remainder: [last-committed-own-seq+1,
        # lease] sequences may never be issued again, and GC stability
        # is a meet of CONTIGUOUS frontiers — an unfilled gap would
        # freeze the whole mesh's stable frontier for this source
        # forever.  Rejoin nudges the hole dots into recovery consensus
        # (they commit as noops where nobody ever saw them; in-flight
        # ones resolve to their real value), restoring contiguity.
        self._lease_gap_dots = self._compute_lease_gap()
        self.wal_replayed_infos = replayed
        self._recovered = snap is not None or bool(state.tail)
        if self._recovered:
            logger.warning(
                "p%s: recovered from WAL (incarnation %d, snapshot=%s, "
                "%d replayed commit infos); rejoin sync runs after connect",
                self.process.id,
                self.incarnation,
                snap is not None,
                replayed,
            )

    def _compute_lease_gap(self) -> List[Any]:
        """Own-source dots at or below the recovered lease that are not
        in the committed clock: never-issued remainder of the last lease
        batch plus pre-crash in-flight dots.  Bounded by
        DOT_LEASE_BATCH + the in-flight window."""
        if not self._dot_lease:
            return []
        clock = getattr(self.process, "_gc_track", None)
        if (
            clock is None
            or not hasattr(clock, "my_clock")  # slot-watermark GC (FPaxos)
            or self.config.shard_count != 1
        ):
            return []
        from fantoch_tpu.core.ids import Dot

        me = self.process.id
        mine = clock.my_clock().get(me)
        return [
            Dot(me, sequence)
            for sequence in range(1, self._dot_lease + 1)
            if mine is None or not mine.contains(sequence)
        ]

    def _replay_info(self, info: Any) -> None:
        """Re-feed one logged commit info into its executor.  Results are
        discarded — their client sessions died with the previous life
        (clients reconnect and the rifl-dedup seams make re-submission
        exactly-once); KVStore effects are deterministic re-applies in
        the original order, so the store converges to the crash state."""
        executor = self.executors[self._executor_position(info)]
        executor.handle_batch([info], self.time)
        for _result in executor.to_clients_iter():
            pass
        for _out in executor.to_executors_iter():
            pass

    def _write_wal_snapshot(self) -> None:
        """One crash-consistent snapshot: protocol + executors + the
        infos currently queued toward the executor pool (logged before
        the snapshot's position but not yet applied — without them the
        tail would skip their effects).  Runs between task steps on the
        cooperative loop, so the capture is atomic w.r.t. handlers."""
        queued: List[Any] = []
        for position in range(self.executor_pool.size):
            inner = getattr(self.executor_pool.queue(position), "_queue", None)
            if inner:
                queued.extend(inner)
        self.wal.save_snapshot(
            {
                "protocol": self.process.snapshot(),
                "executors": [executor.snapshot() for executor in self.executors],
                "queued_infos": queued,
                "dot_lease": self._dot_lease,
            }
        )

    async def _wal_task(self) -> None:
        """Periodic WAL tick: fsync appends (the ``interval`` policy's
        loss bound) and take rotation-bounded snapshots so restart is
        snapshot + a short tail, and the log stays finite."""
        loop = asyncio.get_running_loop()
        snap_interval = self._wal_snapshot_interval_ms / 1000
        tick = min(1.0, snap_interval)
        last_snapshot = loop.time()
        while True:
            await asyncio.sleep(tick)
            self.wal.sync()
            if loop.time() - last_snapshot >= snap_interval:
                self._write_wal_snapshot()
                last_snapshot = loop.time()

    def next_dot(self):
        """Dot allocation with the WAL lease: the generator's high
        watermark is persisted (fsync'd regardless of policy) in
        DOT_LEASE_BATCH strides ahead of use, so a restarted process can
        never re-issue a live sequence."""
        dot = self.dot_gen.next_id()
        if self.wal is not None and dot.sequence > self._dot_lease:
            from fantoch_tpu.run.wal import DOT_LEASE_BATCH

            self._dot_lease = dot.sequence + DOT_LEASE_BATCH
            self.wal.append_lease(self._dot_lease)
        return dot

    # --- lifecycle ---

    def spawn(self, coro) -> asyncio.Task:
        task = asyncio.ensure_future(coro)
        task.add_done_callback(self._on_task_done)
        self._tasks.add(task)
        return task

    def _on_task_done(self, task: asyncio.Task) -> None:
        # a dead worker/reader/executor silently stalls the whole process
        # (the reference logs and exits the task, process.rs:320-325); make
        # failures loud: record the exception and actively tear down.
        # (Raising here would only reach the loop exception handler.)
        # Connection-level failures are NOT fatal anymore: writer tasks
        # reconnect with backoff and surface PeerLostError through the
        # quorum check (_declare_peer_lost) instead of escaping here.
        self._tasks.discard(task)
        if task.cancelled():
            return
        exc = task.exception()
        if exc is not None:
            logger.error("runner task crashed: %r", exc)
            self._fail(exc)

    def _arm_device_faults(self) -> None:
        """Wire the accelerator fault plane into every device plane the
        executor pool drives: re-apply the config knobs (per-dispatch
        deadline, shadow-check rate), install any ``FANTOCH_DEVICE_FAULT``
        env-spec injector (sim/device_faults.py — the live rehearsal of
        the sim nemesis), and attach a failure listener that dumps the
        flight ring.  A failover is NOT fatal: the plane keeps serving
        bit-for-bit from its host twin and cuts back after rebuild — the
        dump is the black box, not a teardown."""
        from fantoch_tpu.sim.device_faults import install_env_faults

        planes = [
            plane
            for executor in self.executors
            for plane in executor.device_planes()
        ]
        if not planes:
            return
        pid = self.process.id
        for plane in planes:
            plane.configure_faults(self.config, process_id=pid)

        def record(plane_name, kind, dispatch, detail):
            logger.warning(
                "p%s: injected device fault %s on %s plane at dispatch %d (%s)",
                pid, kind, plane_name, dispatch, detail,
            )

        install_env_faults(planes, process_id=pid, record=record)

        def on_failure(plane, exc):
            logger.warning(
                "p%s: %s plane failed over (%r); serving from host twin",
                pid, plane.plane_name, exc,
            )
            self._dump_flight(
                f"device-failover: {plane.plane_name}: {type(exc).__name__}",
                suffix=f"_{plane.plane_name}",
            )

        for plane in planes:
            plane.attach_failure_listener(on_failure)

    def _fail(self, exc: BaseException) -> None:
        """Record the first fatal failure and tear the runtime down.
        The flight recorder dumps FIRST — the ring's recent unsampled
        events are the black box that explains the typed failure
        (DivergenceError, StalledExecutionError, QuorumLostError, ...)."""
        if self.failure is None:
            self.failure = exc
            self.failed.set()
            self._dump_flight(f"{type(exc).__name__}: {exc}")
        self._teardown()

    def _dump_flight(self, reason: str, suffix: str = "") -> Optional[str]:
        """Write the flight ring (no-op without a recorder); dump
        failures must never mask the failure being recorded."""
        if self.flight is None:
            return None
        path = f"{self.flight_dir}/flight_p{self.process.id}{suffix}.json"
        try:
            self.flight.dump(path, reason)
        except OSError as exc:
            logger.error("flight dump to %s failed: %r", path, exc)
            return None
        logger.warning(
            "p%s: flight recorder dumped %d event(s) to %s (%s)",
            self.process.id, len(self.flight.events()), path, reason,
        )
        return path

    async def _boot_flight_dump(self) -> None:
        """WAL-restart boot trigger: give the rejoin exchange one
        snapshot interval to land in the ring, then dump the new life's
        replay/rejoin black box (its own file — a later failure dump
        must not overwrite the boot record)."""
        await asyncio.sleep(
            min(1.0, self._wal_snapshot_interval_ms / 1000)
        )
        self._dump_flight(
            f"wal-restart-boot (incarnation {self.incarnation})",
            suffix="_boot",
        )

    def _teardown(self) -> None:
        self._stopping = True
        for task in list(self._tasks):
            task.cancel()
        for server in self._servers:
            server.close()

    async def start(self) -> None:
        """Listen, connect to all peers, then start worker/executor loops."""
        # scope the prof registry to this runtime BEFORE any task spawns:
        # every spawned task snapshots the context and records here (when
        # start() runs as its own task — the harness pattern — the caller's
        # context is untouched)
        from fantoch_tpu.utils import prof

        prof.set_registry(self.prof_registry)
        # count XLA recompiles for the metrics snapshot when any device
        # plane can compile (the hook is process-global and idempotent)
        if self.config.dispatches_to_device():
            from fantoch_tpu.core.compile_cache import ensure_compile_cache
            from fantoch_tpu.observability.device import subscribe_recompiles

            subscribe_recompiles()
            # persistent compile cache before the first dispatch:
            # restarted processes reload programs from disk instead of
            # re-paying the compile wall
            ensure_compile_cache()
        peer_server = await asyncio.start_server(self._on_peer, *self.listen_addr)
        client_server = await asyncio.start_server(self._on_client, *self.client_addr)
        self._servers = [peer_server, client_server]

        # connect to every peer — `multiplexing` reliable links each,
        # retrying while they boot (process.rs:71-111).  The links object
        # is only registered once its first connection is up: the reader
        # task's wait-guard keys on _peer_writers membership, and an empty
        # links would crash its random pick
        for peer_id, addr in self.peers.items():
            links = PeerLinks()
            for index in range(self.multiplexing):
                rw = await connect_with_retry(addr)
                await rw.send(
                    ProcessHi(
                        self.process.id, self.process.shard_id, index,
                        self.incarnation,
                    )
                )
                link = LinkState(
                    peer_id, addr, index, rw,
                    unacked_cap=self.link_unacked_cap,
                )
                self._chaos_rws[rw] = peer_id
                delay_ms = self.peer_delays.get(peer_id)
                if delay_ms:
                    # FIFO delay line between the enqueue side and the
                    # writer (delay.rs:6-39): frames leave `delay_ms` after
                    # entering, so entry times are stamped at put (a burst
                    # still leaves one delay later, not serialized at one
                    # frame per delay)
                    queue = _StampingQueue(
                        f"delay->p{peer_id}[{index}]",
                        asyncio.get_running_loop(),
                        capacity=self.queue_capacity,
                    )
                    delayed: asyncio.Queue = WarnQueue(
                        f"writer->p{peer_id}[{index}]",
                        capacity=self.queue_capacity,
                    )
                    self.spawn(self._delay_task(queue, delayed, delay_ms))
                    link.queue = delayed
                else:
                    queue = WarnQueue(
                        f"writer->p{peer_id}[{index}]",
                        capacity=self.queue_capacity,
                    )
                    link.queue = queue
                link.writer_task = self.spawn(self._peer_writer_task(link))
                self.spawn(self._ack_reader_task(link, rw))
                links.queues.append(queue)
                links.links.append(link)
                self._peer_writers[peer_id] = links

        if self.ping_sort:
            self.sorted_processes = await self._ping_sorted_processes()
        connect_ok, self.closest_shard_process = self.process.discover(
            self.sorted_processes
        )
        assert connect_ok, "discover must succeed with a full process list"

        for position in range(self.workers.size):
            self.spawn(self._worker_task(position))
        for position in range(self.executor_pool.size):
            self.spawn(self._executor_task(position))
        for event, interval_ms in self.periodic_events:
            self.spawn(self._periodic_task(event, interval_ms))
        interval = self.config.executor_executed_notification_interval_ms
        if interval is not None:
            self.spawn(self._executed_notification_task(interval))
        cleanup = self.config.executor_cleanup_interval_ms
        if cleanup is not None and self.config.shard_count > 1:
            self.spawn(self._executor_cleanup_task(cleanup))
        if self.heartbeat_interval_s is not None and self.peers:
            self.spawn(self._heartbeat_task())
        if self.metrics_file is not None or self.telemetry is not None:
            # one telemetry writer, one cadence: the windowed series and
            # the legacy pickle snapshot share the periodic task
            self.spawn(self._telemetry_task())
        if self.metrics_port is not None:
            from fantoch_tpu.observability.exposition import MetricsServer

            self.metrics_server = MetricsServer(
                self.telemetry_sample,
                self.metrics_port,
                labels={"pid": str(self.process.id)},
                profile_dir=self._obs_dir(),
            )
            await self.metrics_server.start()
            self.metrics_port = self.metrics_server.port
        if self.execution_logger is not None:
            self.spawn(self._execution_log_flush_task())
        if self.tracer.enabled:
            self.spawn(self._trace_flush_task())
        if self.tracer_show_interval_ms is not None:
            # the span-subscriber analog: enabling the tracer installs
            # latency spans over the hot paths automatically
            # (fantoch_prof/src/lib.rs:78-136 via utils/prof.py)
            from fantoch_tpu.utils import prof

            prof.auto_instrument()
            self.spawn(self._tracer_task())
        if self.wal is not None:
            self.spawn(self._wal_task())
        if self._recovered:
            # rejoin: now that the mesh is connected, broadcast MSync so
            # live peers stream the commits we missed while down
            self.workers.forward_to(0, ("rejoin", None))
            if self.flight is not None:
                self.spawn(self._boot_flight_dump())
        self._connected.set()

    async def stop(self) -> None:
        self._stopping = True
        tasks = list(self._tasks)
        self._teardown()
        # bounded re-cancel: asyncio.wait_for can swallow a cancellation
        # (inner future completes in the cancel's tick), leaving a task
        # parked with no cancel pending — re-cancel instead of hanging
        for _round in range(3):
            if not tasks:
                break
            _done, pending = await asyncio.wait(tasks, timeout=5)
            if not pending:
                break
            for task in pending:
                task.cancel()
            tasks = list(pending)
        if self.metrics_server is not None:
            await self.metrics_server.stop()
        if self.execution_logger is not None:
            self.execution_logger.close()
        if self.metrics_file is not None or self.telemetry is not None:
            # final window + snapshot so short runs always leave one behind
            self._emit_telemetry()
        if self.telemetry is not None:
            self.telemetry.close()
        if self.wal is not None:
            # flush, no final snapshot: every recovery is crash-shaped
            # (last periodic snapshot + tail), so the restart path the
            # tests exercise is the one production would take
            self.wal.close()
        self.tracer.close()

    # --- connection handlers ---

    async def _on_peer(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter) -> None:
        rw = Rw(reader, writer)
        hi = await rw.recv()
        if hi is None:
            return  # dialer gave up (e.g. crashed mid-handshake)
        assert isinstance(hi, ProcessHi), f"unexpected handshake {hi}"
        incarnation = getattr(hi, "incarnation", 0)
        known = self._peer_incarnations.get(hi.process_id)
        if known is not None and incarnation != known:
            # the peer RESTARTED: its links number frames from 1 again —
            # reset per-link dedup or every new frame would be swallowed
            # as a duplicate of the previous life
            for key in list(self._link_recv_seq):
                if key[0] == hi.process_id:
                    self._link_recv_seq[key] = 0
            logger.warning(
                "p%s: peer p%s handshake with new incarnation %d "
                "(was %d): link dedup reset",
                self.process.id, hi.process_id, incarnation, known,
            )
        self._peer_incarnations[hi.process_id] = incarnation
        if hi.process_id in self.dead_peers:
            self._declare_peer_up(hi.process_id)
        self._chaos_rws[rw] = hi.process_id
        self.spawn(
            self._reader_task(
                hi.process_id, hi.shard_id, rw, (hi.process_id, getattr(hi, "link", 0))
            )
        )

    async def _on_client(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter) -> None:
        await self._connected.wait()
        session = _ClientSession(self, Rw(reader, writer))
        self.spawn(session.run())

    # --- tasks ---

    async def _reader_task(
        self,
        from_: ProcessId,
        from_shard: ShardId,
        rw: Rw,
        dedup_key: Tuple[ProcessId, int],
    ) -> None:
        """Route peer messages to workers by message index, and peer
        executor infos (cross-shard dependency traffic) to the executor
        pool (process.rs:292-326).

        Frames arrive sequence-numbered (run/links.py): after a sender
        reconnect it resends its unacked window, so frames at or below the
        last seen sequence are dropped here (exactly-once delivery across
        connection loss); the ack written back — immediately on connect,
        then every ACK_EVERY frames — trims the sender's window."""
        last_seq = self._link_recv_seq.setdefault(dedup_key, 0)
        try:
            await self._reader_loop(from_, from_shard, rw, dedup_key, last_seq)
        finally:
            # drop the chaos-hook registration with the connection, or a
            # flapping link accumulates one dead Rw per reconnect
            self._chaos_rws.pop(rw, None)

    async def _reader_loop(
        self,
        from_: ProcessId,
        from_shard: ShardId,
        rw: Rw,
        dedup_key: Tuple[ProcessId, int],
        last_seq: int,
    ) -> None:
        try:
            rw.write_link_frame(KIND_ACK, last_seq, b"")
            await rw.flush()
        except (ConnectionError, OSError):
            return
        received = 0
        loop = asyncio.get_running_loop()
        while True:
            frame = await rw.recv_link_frame()
            if frame is None:
                return
            self._last_heard[from_] = loop.time()
            if from_ in self.dead_peers:
                # frames from a peer we declared dead: it is back (wrong
                # call, or it restarted and reconnected) — revive it
                self._declare_peer_up(from_)
            kind, seq, payload = frame
            if kind != KIND_DATA:
                continue
            if seq <= self._link_recv_seq[dedup_key]:
                continue  # duplicate from a reconnect resend
            self._link_recv_seq[dedup_key] = seq
            received += 1
            if received % ACK_EVERY == 0:
                try:
                    rw.write_link_frame(KIND_ACK, seq, b"")
                    await rw.flush()
                except (ConnectionError, OSError):
                    return
            msg = deserialize(payload)
            if isinstance(msg, PingReq):
                # our outbound writer to this peer may still be connecting
                # (pings fly during start); wait for it rather than crash
                while from_ not in self._peer_writers:
                    await asyncio.sleep(0.01)
                t_send = getattr(msg, "t_send_us", None)
                self._peer_writers[from_].put_nowait(
                    serialize(
                        PingReply(
                            msg.nonce,
                            req_t_send_us=t_send,
                            t_reply_us=(
                                self.time.micros() if t_send is not None else None
                            ),
                        )
                    )
                )
                digest = getattr(msg, "digest", None)
                if digest is not None:
                    self._check_peer_digest(from_, digest)
            elif isinstance(msg, DigestKeyRequest):
                while from_ not in self._peer_writers:
                    await asyncio.sleep(0.01)
                self._peer_writers[from_].put_nowait(
                    serialize(DigestKeyReply(msg.key, self._digest_entries(msg.key)))
                )
            elif isinstance(msg, DigestKeyReply):
                self._resolve_divergence(from_, msg.key, msg.entries)
            elif isinstance(msg, PingReply):
                waiter = self._ping_waiters.pop(msg.nonce, None)
                if waiter is not None and not waiter.done():
                    waiter.set_result(None)
                # clock-offset bracket: fold the echoed stamps into the
                # per-peer estimate; an improved (lower-RTT) sample rides
                # the trace so the correlator sees the best-known skew
                req_t = getattr(msg, "req_t_send_us", None)
                if req_t is not None and msg.t_reply_us is not None:
                    improved = self._clock_offsets.sample(
                        from_, req_t, msg.t_reply_us, self.time.micros()
                    )
                    if improved is not None and self.tracer.enabled:
                        rtt, off = improved
                        self.tracer.offset(self.process.id, from_, off, rtt)
            elif isinstance(msg, POEExecutor):
                position = self._executor_position(msg.info)
                self.executor_pool.forward_to(position, msg.info)
            else:
                assert isinstance(msg, POEProtocol)
                edge_seq = getattr(msg, "edge", None)
                if edge_seq is not None and self.tracer.enabled:
                    # the recv half of a stitched message edge: pairs
                    # with the sender's (src, seq) send event
                    dot = edge_dot(msg.msg)
                    if dot is not None:
                        self.tracer.edge(
                            "r", type(msg.msg).__name__, from_,
                            self.process.id, edge_seq, dot=dot,
                        )
                index = self.protocol_cls.message_index(msg.msg)
                self.workers.forward(index, ("msg", from_, from_shard, msg.msg))
            if self.workers.gated or self.executor_pool.gated:
                # cooperative backpressure: a downstream queue crossed its
                # high watermark — stop draining this peer's socket until
                # it falls below the low one.  The pause propagates to
                # the sending peer via TCP flow control (its writer task
                # blocks on flush), which is how pressure crosses process
                # boundaries without unbounded buffering on either side
                self.backpressure_pauses += 1
                await self.workers.wait_for_credit()
                await self.executor_pool.wait_for_credit()

    @staticmethod
    async def _delay_task(
        source: "_StampingQueue", sink: asyncio.Queue, delay_ms: int
    ) -> None:
        """FIFO delay line (delay.rs:6-39): each frame is released
        ``delay_ms`` after it *entered* the queue (entry time stamped by
        the _StampingQueue at put), preserving order.  The delay task is
        an asynchronous producer, so it CAN honor the sink's credit gate:
        a backed-up writer pauses the line instead of growing the sink."""
        loop = asyncio.get_running_loop()
        while True:
            entered, frame = await source.get()
            remaining = entered + delay_ms / 1000 - loop.time()
            if remaining > 0:
                await asyncio.sleep(remaining)
            sink.put_nowait(frame)
            if getattr(sink, "gated", False):
                await sink.wait_for_credit()

    async def _ping_sorted_processes(self) -> List[Tuple[ProcessId, ShardId]]:
        """Latency-sort same-shard peers by measured RTT (ping.rs:13-78,
        sort_by_distance :144); self always leads at 0ms, other-shard
        entries keep their closest-process role."""
        shard_peers = [
            (pid, s) for pid, s in self.sorted_processes
            if s == self.process.shard_id and pid != self.process.id
        ]
        # peers are probed concurrently: total ping time ~= samples RTTs of
        # the slowest peer, not the sum over peers
        measured = await asyncio.gather(
            *(self._ping_peer(pid) for pid, _s in shard_peers)
        )
        rtts: Dict[ProcessId, float] = {
            pid: rtt for (pid, _s), rtt in zip(shard_peers, measured)
        }
        ordered = sorted(shard_peers, key=lambda e: rtts[e[0]])
        others = [
            (pid, s) for pid, s in self.sorted_processes
            if s != self.process.shard_id
        ]
        return [(self.process.id, self.process.shard_id)] + ordered + others

    async def _ping_peer(
        self, peer_id: ProcessId, samples: int = 3, timeout: float = 10.0
    ) -> float:
        """Median RTT to a peer over the live connection, ms."""
        loop = asyncio.get_running_loop()
        times = []
        for _ in range(samples):
            self._ping_nonce += 1
            nonce = self._ping_nonce
            fut: asyncio.Future = loop.create_future()
            self._ping_waiters[nonce] = fut
            t0 = loop.time()
            self._peer_writers[peer_id].put_nowait(serialize(PingReq(nonce)))
            try:
                await asyncio.wait_for(fut, timeout=timeout)
            finally:
                self._ping_waiters.pop(nonce, None)
            times.append((loop.time() - t0) * 1000)
        times.sort()
        return times[len(times) // 2]

    async def _peer_writer_task(self, link: LinkState) -> None:
        """Drains pre-serialized frames onto one reliable peer link
        (serialization happens at enqueue time: a message may also be
        self-delivered, and the local handler can mutate it in place
        before this task would run).

        Crash tolerance: every data frame is sequence-numbered and kept in
        the link's unacked window until the peer acks it; a send error or
        per-send timeout triggers reconnect-with-backoff-and-jitter, after
        which the window is resent (the peer's reader dedups by seq).
        When the reconnect budget is exhausted the peer goes through the
        quorum check instead of tearing the whole process down."""
        queue = link.queue
        # the _stopping check also reaps a cancellation that wait_for
        # swallowed (inner future completed in the same tick the cancel
        # landed — asyncio returns the result and loses the cancel); the
        # task must still exit promptly or stop()'s gather hangs on it
        while not link.dead and not self._stopping:
            rw = link.rw
            try:
                if link.resend:
                    for seq, frame in link.unacked:
                        rw.write_link_frame(KIND_DATA, seq, frame)
                    link.resend = False
                    await asyncio.wait_for(rw.flush(), self.send_timeout_s)
                    continue
                frame = await queue.get()
                rw.write_link_frame(KIND_DATA, link.next_seq(), frame)
                link.note_sent(link.seq, frame)
                # batch whatever accumulated while writing (flush
                # coalescing, process.rs:329-385)
                while not queue.empty():
                    frame = queue.get_nowait()
                    rw.write_link_frame(KIND_DATA, link.next_seq(), frame)
                    link.note_sent(link.seq, frame)
                await asyncio.wait_for(rw.flush(), self.send_timeout_s)
                if link.over_unacked_cap():
                    # the peer reads frames (TCP accepts them) but never
                    # acks: a live-but-wedged consumer.  Buffering more
                    # resend state only converts its overload into our
                    # OOM — declare the peer lost through the existing
                    # typed path (quorum check decides degrade vs fail)
                    self._declare_peer_lost(
                        link.peer_id,
                        PeerLostError(
                            link.peer_id,
                            0,
                            BufferError(
                                f"unacked resend window overflow "
                                f"({len(link.unacked)} > {link.unacked_cap})"
                            ),
                        ),
                    )
                    return
            except (ConnectionError, OSError, asyncio.TimeoutError):
                # NB: a cancellation hitting inside wait_for can surface
                # as TimeoutError (the classic asyncio footgun) — the
                # _stopping check keeps a shutdown from resurrecting this
                # task into a reconnect loop that outlives stop()
                if link.dead or self._stopping:
                    return
                try:
                    await self._reconnect_link(link)
                except PeerLostError as exc:
                    self._declare_peer_lost(link.peer_id, exc)
                    return

    async def _ack_reader_task(self, link: LinkState, rw: Rw) -> None:
        """Reads ack frames the peer's reader writes back on our outbound
        connection, trimming the link's resend window.  Ends silently on
        EOF — the writer owns reconnects (one per connection incarnation;
        a reconnect spawns a fresh one on the new rw)."""
        loop = asyncio.get_running_loop()
        try:
            while True:
                frame = await rw.recv_link_frame()
                if frame is None:
                    return
                self._last_heard[link.peer_id] = loop.time()
                kind, seq, _payload = frame
                if kind == KIND_ACK:
                    link.ack(seq)
        finally:
            # dead connection: only the live rw should stay registered for
            # the chaos hook (the writer re-registers on reconnect)
            if rw is not link.rw:
                self._chaos_rws.pop(rw, None)

    async def _reconnect_link(self, link: LinkState) -> None:
        """Re-dial one peer link with exponential backoff + full jitter;
        raises PeerLostError once the policy's attempts are exhausted."""
        link.rw.abort()
        last: Optional[BaseException] = None
        attempts = 0
        for delay in self.reconnect_policy.delays():
            if link.dead or self._stopping:
                raise PeerLostError(link.peer_id, attempts, last)
            attempts += 1
            await asyncio.sleep(delay)
            try:
                rw = await asyncio.wait_for(
                    connect_with_retry(link.addr, attempts=1),
                    self.send_timeout_s,
                )
                await rw.send(
                    ProcessHi(
                        self.process.id, self.process.shard_id, link.index,
                        self.incarnation,
                    )
                )
            except (ConnectionError, OSError, asyncio.TimeoutError) as exc:
                last = exc
                continue
            self._chaos_rws.pop(link.rw, None)
            self._chaos_rws[rw] = link.peer_id
            link.rw = rw
            link.resend = True
            self.spawn(self._ack_reader_task(link, rw))
            logger.warning(
                "p%s: reconnected link %d to p%s after %d attempt(s), "
                "resending %d unacked frame(s)",
                self.process.id,
                link.index,
                link.peer_id,
                attempts,
                len(link.unacked),
            )
            return
        raise PeerLostError(link.peer_id, attempts, last)

    async def _heartbeat_task(self) -> None:
        """Peer failure detector: every interval, ping each peer (so even
        an idle link generates traffic whose replies refresh
        ``_last_heard``), and declare a peer lost only after
        ``heartbeat_misses`` intervals of *total silence* — no frame of
        any kind heard from it.  Judging silence rather than ping RTTs
        keeps a congested-but-alive cluster (many processes sharing one
        cooperative loop or core) from false-positive amputations; a
        wedged or unreachable peer still trips the quorum check
        (ping.rs:13-78 machinery, promoted from boot-time sort to a
        liveness monitor)."""
        loop = asyncio.get_running_loop()
        silence_window = self.heartbeat_interval_s * self.heartbeat_misses
        for peer_id in self.peers:
            self._last_heard.setdefault(peer_id, loop.time())
        while True:
            await asyncio.sleep(self.heartbeat_interval_s)
            if self._stopping:
                return
            # divergence detection rides the heartbeat: piggyback our
            # per-key digest summary so every peer cross-audits us at
            # detector cadence (serialized once per tick, not per peer)
            digest = (
                self._digest_summary()
                if self.config.execution_digests
                else None
            )
            for peer_id in self.peers:
                if peer_id in self.dead_peers:
                    continue
                # fire-and-forget probe: any reply (or any other frame)
                # refreshes _last_heard via the reader.  The send stamp
                # turns each probe into a clock-offset bracket (the
                # reply echoes it plus the replier's clock)
                self._ping_nonce += 1
                self._peer_writers[peer_id].put_nowait(
                    serialize(
                        PingReq(
                            self._ping_nonce, digest,
                            t_send_us=self.time.micros(),
                        )
                    )
                )
                silent_for = loop.time() - self._last_heard[peer_id]
                if silent_for > silence_window:
                    self._declare_peer_lost(
                        peer_id,
                        PeerLostError(
                            peer_id,
                            self.heartbeat_misses,
                            TimeoutError(f"silent for {silent_for:.1f}s"),
                        ),
                    )

    # --- online divergence detection (core/audit.py digests) ---

    def _digest_summary(self) -> Optional[Dict[str, Any]]:
        """Merged per-key (count, chain digest) summary across the
        executor pool (executors own disjoint key sets); None when
        digests are off or nothing executed yet."""
        merged: Dict[str, Any] = {}
        for executor in self.executors:
            digest = executor.digest()
            if digest is not None:
                digest.merge_summary_into(merged)
        return merged or None

    def _digest_entries(self, key: str):
        for executor in self.executors:
            digest = executor.digest()
            if digest is not None:
                entries = digest.entries(key)
                if entries:
                    return entries
        return []

    def _check_peer_digest(self, peer_id: ProcessId, summary: Dict[str, Any]) -> None:
        """Verify a peer's heartbeat digest summary against our chains:
        for every key where we reach the peer's write count, our digest
        at that position must match (a hash chain authenticates the whole
        prefix).  On mismatch, request the peer's full chain so the
        DivergenceError can name the FIRST diverging write."""
        self.digest_checks += 1
        mismatched = []
        for executor in self.executors:
            digest = executor.digest()
            if digest is not None:
                mismatched.extend(digest.mismatched_keys(summary))
        for key in mismatched:
            self.digest_mismatches += 1
            logger.error(
                "p%s: execution digest mismatch with p%s on key %r — "
                "requesting its chain to locate the fork",
                self.process.id, peer_id, key,
            )
            self._peer_writers[peer_id].put_nowait(
                serialize(DigestKeyRequest(key))
            )

    def _resolve_divergence(self, peer_id: ProcessId, key: str, entries) -> None:
        """A peer answered our drill-down with its full chain: find the
        first diverging write and fail with the typed error.  A clean
        prefix means the mismatch healed (e.g. we advanced past a stale
        summary) — nothing to report then."""
        from fantoch_tpu.core.audit import DigestEntry, ExecutionDigest
        from fantoch_tpu.core.ids import Rifl
        from fantoch_tpu.errors import DivergenceError

        theirs = [DigestEntry(*entry) for entry in entries]
        divergence = ExecutionDigest.first_divergence(
            self._digest_entries(key), theirs
        )
        if divergence is None:
            return
        position, mine, other = divergence
        mine_rifl = Rifl(mine.src, mine.seq) if mine is not None else None
        theirs_rifl = Rifl(other.src, other.seq) if other is not None else None
        # name the diverging command's dot when the audit commit log can
        # resolve it (Config.audit_log_commits)
        dot = None
        log = self.process.audit_commit_log()
        if log is not None:
            dot = next(
                (
                    ident
                    for ident, (rifl, _value) in log.items()
                    if rifl == mine_rifl
                ),
                None,
            )
        self._fail(
            DivergenceError(
                key, position, mine_rifl, theirs_rifl,
                self.process.id, peer_id, dot=dot,
            )
        )

    def _declare_peer_lost(self, peer_id: ProcessId, cause: BaseException) -> None:
        """Graceful degradation: a lost peer stops the cluster only when
        the survivors can no longer form a quorum (alive < n - f); above
        that the runtime keeps serving and drops frames to the dead peer."""
        if peer_id in self.dead_peers or self._stopping:
            return
        self.dead_peers.add(peer_id)
        links = self._peer_writers.get(peer_id)
        if links is not None:
            links.mark_dead()
        my_shard = self.process.shard_id
        same_shard = [
            pid for pid in self.peers if self._shard_of.get(pid) == my_shard
        ]
        alive = 1 + sum(1 for pid in same_shard if pid not in self.dead_peers)
        needed = self.config.n - self.config.f
        if alive < needed:
            self._fail(QuorumLostError(alive, needed, self.dead_peers))
        else:
            logger.warning(
                "p%s: peer p%s lost (%r); degrading gracefully with "
                "%d/%d same-shard processes alive (quorum needs %d)",
                self.process.id,
                peer_id,
                cause,
                alive,
                self.config.n,
                needed,
            )
            # tell the protocol (worker 0 owns leadership state): FPaxos
            # uses this to elect a new leader without waiting out its own
            # protocol-level silence timeout
            self.workers.forward_to(0, ("peer_down", peer_id))

    def _declare_peer_up(self, peer_id: ProcessId) -> None:
        """The detector hook symmetric to ``_declare_peer_lost``: a peer
        we declared dead is demonstrably reachable again (a frame
        arrived, or it re-handshook after a restart).  Frames flow to it
        again, its writer tasks respawn (reconnecting and resending the
        unacked window), and the protocol hears ``on_peer_up`` so
        recovery-ring / pending-forward targets stop routing around it."""
        if peer_id not in self.dead_peers or self._stopping:
            return
        self.dead_peers.discard(peer_id)
        links = self._peer_writers.get(peer_id)
        if links is not None:
            links.mark_alive()
            for link in links.links:
                # a writer parked on queue.get() at declare-lost time
                # never observed dead=True and would wake into a second
                # life alongside the revival writer, interleaving one
                # seq window across two tasks — retire it first
                if link.writer_task is not None and not link.writer_task.done():
                    link.writer_task.cancel()
                # reconnect BEFORE resuming the writer: the old rw was
                # locally aborted, and asyncio silently discards writes
                # to a closed transport (flush does not raise), so a
                # writer resumed on it would drop frames forever
                link.writer_task = self.spawn(self._revive_link(link))
        self._last_heard[peer_id] = asyncio.get_event_loop().time()
        logger.warning(
            "p%s: peer p%s is back (%d/%d same-shard processes alive)",
            self.process.id,
            peer_id,
            1 + sum(
                1
                for pid in self.peers
                if self._shard_of.get(pid) == self.process.shard_id
                and pid not in self.dead_peers
            ),
            self.config.n,
        )
        self.workers.forward_to(0, ("peer_up", peer_id))

    async def _revive_link(self, link: LinkState) -> None:
        """Revival path: dial the returned peer fresh (resending the
        unacked window), then resume the writer task on the new rw."""
        try:
            await self._reconnect_link(link)
        except PeerLostError as exc:
            self._declare_peer_lost(link.peer_id, exc)
            return
        await self._peer_writer_task(link)

    def inject_link_failure(self, peer_id: Optional[ProcessId] = None) -> int:
        """Chaos hook for tests: hard-kill the live peer-link sockets (all
        of them, or only those to/from ``peer_id``), simulating the
        network dropping connections while every process stays up.
        Returns the number of aborted connections."""
        count = 0
        for rw, rw_peer in list(self._chaos_rws.items()):
            if peer_id is not None and rw_peer != peer_id:
                continue
            rw.abort()
            self._chaos_rws.pop(rw, None)
            count += 1
        return count

    async def _worker_task(self, position: int) -> None:
        queue = self.workers.queue(position)
        process = self.process
        # protocols with a batched submit seam (Newt's kernel-batched clock
        # proposals) take runs of queued submits in one call
        submit_batch = getattr(process, "submit_batch", None)
        while True:
            item = await queue.get()
            kind = item[0]
            if kind == "msg":
                _, from_, from_shard, msg = item
                process.handle(from_, from_shard, msg, self.time)
            elif kind == "submit":
                _, dot, cmd = item
                if submit_batch is not None:
                    # drain the run of consecutive submits queued behind us
                    pairs = [(dot, cmd)]
                    while _peek_is_submit(queue):
                        _, d2, c2 = queue.get_nowait()
                        pairs.append((d2, c2))
                    submit_batch(pairs, self.time)
                    if self.tracer.enabled:
                        # ingest = the worker handing the command to the
                        # protocol; no batching gate on this runner's
                        # submit edge yet, so payload->ingest is ~0 (the
                        # canonical chain stays complete either way).
                        # Stamped AFTER submit: the protocol's payload
                        # stamp runs inside it, and payload <= ingest
                        # must hold on the wall clock
                        for _d, c in pairs:
                            self.tracer.span(
                                "ingest", c.rifl, pid=self.process.id
                            )
                else:
                    process.submit(dot, cmd, self.time)
                    if self.tracer.enabled:
                        self.tracer.span(
                            "ingest", cmd.rifl, pid=self.process.id
                        )
            elif kind == "event":
                process.handle_event(item[1], self.time)
            elif kind == "executed":
                process.handle_executed(item[1], self.time)
            elif kind == "peer_down":
                process.on_peer_down(item[1], self.time)
            elif kind == "peer_up":
                process.on_peer_up(item[1], self.time)
            elif kind == "rejoin":
                process.rejoin(self.time)
                if self._lease_gap_dots:
                    # lease-gap healing: recovery commits the hole dots
                    # (noops where never issued) so the mesh's contiguous
                    # committed frontier for this source does not freeze
                    process.nudge_recovery(self._lease_gap_dots, self.time)
            else:
                raise AssertionError(f"unknown worker item {item}")
            self._drain_protocol()

    def _drain_protocol(self) -> None:
        """Ship protocol outputs (the send_to_processes_and_executors analog,
        process.rs:580-654)."""
        process = self.process
        tracer = self.tracer
        for action in process.to_processes_iter():
            if isinstance(action, ToSend):
                # serialize once, NOW: the self-delivered copy is handled by
                # a worker that may mutate the message in place (e.g. Newt
                # strips MCommit votes), so peers must get bytes captured
                # before any local handling.  When the message's dot is
                # trace-sampled, each peer frame instead carries its own
                # edge sequence (one send event per hop, paired with the
                # receiver's recv event) — per-target serialization, same
                # capture-before-local-handling discipline
                e_dot = None
                if tracer.enabled:
                    e_dot = edge_dot(action.msg)
                    if e_dot is not None and not tracer.sample(e_dot):
                        e_dot = None
                seq = None
                mtype = None
                if e_dot is not None:
                    # ONE edge seq per broadcast, shared by every target
                    # (the hop key is (src, seq, dst) — dst disambiguates)
                    # so the frame still serializes exactly once
                    self._edge_seq += 1
                    seq = self._edge_seq
                    mtype = type(action.msg).__name__
                frame = None
                for target in sorted(action.target):
                    if target != process.id and frame is None:
                        frame = serialize(POEProtocol(action.msg, edge=seq))
                for target in sorted(action.target):
                    if target == process.id:
                        index = self.protocol_cls.message_index(action.msg)
                        self.workers.forward(
                            index, ("msg", process.id, process.shard_id, action.msg)
                        )
                    else:
                        if seq is not None:
                            tracer.edge(
                                "s", mtype, process.id, target, seq,
                                dot=e_dot,
                            )
                        self._peer_writers[target].put_nowait(frame)
            elif isinstance(action, ToForward):
                index = self.protocol_cls.message_index(action.msg)
                self.workers.forward(
                    index, ("msg", process.id, process.shard_id, action.msg)
                )
            else:
                raise AssertionError(f"unknown action {action}")
        for info in process.to_executors_iter():
            if self.wal is not None:
                # durability point: every commit info is logged before it
                # can reach an executor — restart replays exactly the
                # records past the snapshot (append-then-apply order)
                self.wal.append("info", info)
            position = executor_index(info, self.executor_pool.size)
            self.executor_pool.forward_to(position, info)

    def _executor_position(self, info: Any) -> int:
        """Position in the executor pool for an info: the Executor's own
        routing when it defines one (GraphExecutor's main/secondary split,
        executor.rs:242-262), else key/0 routing."""
        index_of = getattr(self.protocol_cls.Executor, "executor_index_of", None)
        if index_of is not None:
            _reserved, index = index_of(info)
            assert index < self.executor_pool.size, (
                f"info {type(info).__name__} routes to executor {index} but the "
                f"pool has {self.executor_pool.size}; multi-shard graph "
                "executors need the main/secondary split (executors >= 2)"
            )
            return index
        pos = executor_index(info, self.executor_pool.size)
        return 0 if pos is None else pos

    def _ship_executor_outputs(self, executor: Any) -> None:
        """Deliver an executor's (shard, info) outputs: same-shard infos go
        to the local pool, cross-shard ones to the closest process of the
        target shard (executor.rs:220-260 fetch_info_to_executors)."""
        for to_shard, xinfo in executor.to_executors_iter():
            if to_shard == self.process.shard_id:
                self.executor_pool.forward_to(self._executor_position(xinfo), xinfo)
            else:
                target = self.closest_shard_process[to_shard]
                self._peer_writers[target].put_nowait(
                    serialize(POEExecutor(xinfo))
                )

    async def _executor_task(self, position: int) -> None:
        queue = self.executor_pool.queue(position)
        executor = self.executors[position]
        # adaptive ingest (run/ingest.py), opt-in: only when
        # Config.ingest_deadline_ms is set and positive does the drain
        # hold for a fuller batch — unset drains whatever is queued
        from time import monotonic

        deadline = self.config.ingest_deadline_ms
        batcher: Optional[AdaptiveIngestBatcher] = None
        if deadline:
            batcher = AdaptiveIngestBatcher(
                deadline,
                # no device round bound on a host executor drain; 1024
                # caps a hold at the batched-resolver sweet spot
                max_target=1024,
                fixed_target=self.config.ingest_target,
            )
        while True:
            # drain the whole queue: batch-oriented executors (the batched
            # graph resolver) amortize one device round-trip over the drain
            infos = [await queue.get()]
            while True:
                try:
                    infos.append(queue.get_nowait())
                except asyncio.QueueEmpty:
                    break
            if batcher is not None:
                now = monotonic() * 1000.0
                batcher.note_arrivals(now, len(infos))
                seen = len(infos)
                while True:
                    release, wait_ms = batcher.poll(now, len(infos))
                    if release or wait_ms is None:
                        break
                    # hold for the remaining budget, then sweep whatever
                    # landed; a size-target fill releases on the re-poll
                    await asyncio.sleep(wait_ms / 1000.0)
                    while True:
                        try:
                            infos.append(queue.get_nowait())
                        except asyncio.QueueEmpty:
                            break
                    now = monotonic() * 1000.0
                    batcher.note_arrivals(now, len(infos) - seen)
                    seen = len(infos)
                batcher.note_release(now, len(infos))
            if self.execution_logger is not None:
                self.execution_logger.log(infos)
            executor.handle_batch(infos, self.time)
            for result in executor.to_clients_iter():
                session = self.client_sessions.get(result.rifl.source)
                if session is not None:
                    session.deliver(result)
            self._ship_executor_outputs(executor)

    async def _executor_cleanup_task(self, interval_ms: int) -> None:
        """Periodic cleanup tick: retries buffered cross-shard requests on
        the secondary executor (executor.rs:279-293)."""
        while True:
            await asyncio.sleep(interval_ms / 1000)
            for executor in self.executors:
                executor.cleanup(self.time)
                self._ship_executor_outputs(executor)

    def admission_depth(self) -> int:
        """The client edge's congestion signal: the deepest queue across
        the worker and executor pools (the bottleneck queue is what
        grows latency — a sum would hide one wedged consumer behind many
        empty peers)."""
        return max(self.workers.max_depth(), self.executor_pool.max_depth())

    def queue_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-queue depth/high-watermark/pause gauges across every
        run-layer queue this process owns: worker + executor pools, the
        peer-writer queues, and each link's unacked resend window.  The
        snapshot the metrics plane exports (ProcessMetrics.queues) —
        what WarnQueue used to only *log* is now a gauge that survives
        into ``bin/obs.py summarize``."""
        stats: Dict[str, Dict[str, float]] = {}
        stats.update(self.workers.stats())
        stats.update(self.executor_pool.stats())
        for peer_id, links in self._peer_writers.items():
            for queue in links.queues:
                if hasattr(queue, "stats"):
                    stats[queue.name] = queue.stats()
            for link in links.links:
                # with a delay line, links.queues holds the pre-delay
                # stamping queue and link.queue the post-delay writer
                # queue — gauge both (same object without a delay line)
                queue = link.queue
                if queue is not None and hasattr(queue, "stats"):
                    stats[queue.name] = queue.stats()
                stats[f"unacked->p{peer_id}[{link.index}]"] = {
                    "depth": len(link.unacked),
                    "depth_hwm": link.unacked_hwm,
                    "capacity": link.unacked_cap,
                    "pauses": 0,
                    "overflows": 0,
                }
        return stats

    def overload_counters(
        self, stats: Optional[Dict[str, Dict[str, float]]] = None
    ) -> Dict[str, float]:
        """Running totals of the overload-control plane's activity —
        folded into metrics snapshots and (when tracing) the span log.
        Pass a ``queue_stats()`` result to avoid a second walk (and to
        keep one snapshot's ``.queues`` and ``.overload`` views of the
        same instant)."""
        if stats is None:
            stats = self.queue_stats()
        out = {
            "shed_submissions": self.shed_submissions,
            "backpressure_pauses": self.backpressure_pauses,
            "queue_depth_hwm": max(
                (row["depth_hwm"] for row in stats.values()), default=0
            ),
            "queue_depth": max(
                (row["depth"] for row in stats.values()), default=0
            ),
        }
        if self.config.execution_digests:
            # divergence-detection gauges ride the same snapshot/tracer
            # pipeline (bin/obs.py summarize prints the audit line)
            out["digest_checks"] = self.digest_checks
            out["digest_mismatches"] = self.digest_mismatches
            summary = self._digest_summary() or {}
            out["digest_keys"] = len(summary)
        return out

    def _write_metrics_snapshot(self, queues=None, overload=None, device=None) -> None:
        """The legacy crash-consistent pickle snapshot.  ``_emit_telemetry``
        passes its already-collected sources so one tick walks the
        queues/device counters exactly once (and the series window and
        the snapshot's ``.queues``/``.overload`` views agree on the same
        instant); absent args are collected here."""
        from fantoch_tpu.run.observe import ProcessMetrics, write_metrics_snapshot

        if device is None:
            device = self._device_counters()
        if device is not None and self.tracer.enabled:
            # counters ride the trace too, next to the spans of the
            # batches they carried.  jax_recompiles/jax_compile_ms are
            # host-process-global (module tallies in
            # observability/device.py), so they go out unattributed:
            # co-hosted runtimes (the localhost harness) overwrite one
            # (name, pid=None) observation instead of each claiming the
            # same compiles — summing per-pid would n-fold them
            for name, value in sorted(device.items()):
                self.tracer.counter(
                    name, value,
                    pid=(
                        None
                        if name in (
                            "jax_recompiles", "jax_compile_ms",
                            "jax_cache_hits", "jax_cache_misses",
                        )
                        else self.process.id
                    ),
                )
        if queues is None:
            queues = self.queue_stats()
        if overload is None:
            overload = self.overload_counters(queues)
        if self.tracer.enabled:
            # queue-depth gauges + shed/pause tallies ride the span log
            # too (running totals, counters_total last-wins semantics),
            # so `bin/obs.py summarize` shows the overload plane next to
            # the latency breakdown it explains
            for name, value in sorted(overload.items()):
                self.tracer.counter(name, value, pid=self.process.id)
        backend = None
        if device is not None:
            from fantoch_tpu.hostenv import device_report

            backend = {**device_report(), "mesh_shape": None}
        write_metrics_snapshot(
            self.metrics_file,
            ProcessMetrics(
                [self.process.metrics()],
                [e.metrics() for e in self.executors],
                device,
                queues,
                overload,
                backend,
            ),
        )

    def _device_counters(self):
        """Fold every executor's device-plane counters (plus the global
        recompile tally) into one per-process dict; None when no device
        plane contributed.  ``jax_recompiles`` is host-process-global
        (``observability/device.py`` module tally): every co-hosted
        runtime's snapshot carries the same total, so readers must not
        sum it across runtimes of one host."""
        from fantoch_tpu.observability.device import (
            cache_hit_count,
            cache_miss_count,
            compile_ms,
            derive_idle_frac,
            merge_counters,
            recompile_count,
        )

        device: Dict[str, float] = {}
        for executor in self.executors:
            merge_counters(device, executor.device_counters())
        if device:
            # dispatch/drain overlap instrument: idle frac from the
            # folded busy/span walls (frac itself never sums)
            derive_idle_frac(device)
            device["jax_recompiles"] = recompile_count()
            device["jax_compile_ms"] = compile_ms()
            device["jax_cache_hits"] = cache_hit_count()
            device["jax_cache_misses"] = cache_miss_count()
            return device
        return None

    def _obs_dir(self) -> str:
        """Directory profiling artifacts land in (one rule for every
        trigger spelling: observability/exposition.profile_output_dir)."""
        from fantoch_tpu.observability.exposition import profile_output_dir

        return profile_output_dir(
            self.telemetry and self.telemetry.path, self.metrics_file
        )

    def telemetry_sample(self, stats=None, overload=None, device=None):
        """One consistent (counters, gauges, histograms) sample — the
        shared source of the windowed series, the legacy snapshot's
        tracer counters, and the ``/metrics`` exposition.  Counter and
        gauge names match the bench/tally keys so a dashboard query and
        a BENCH row key agree.  ``_emit_telemetry`` passes precollected
        sources so one tick never walks them twice; the exposition
        endpoint calls with no args and collects fresh."""
        from fantoch_tpu.core.metrics import Metrics as _Metrics

        counters: Dict[str, float] = {
            "submitted": self.submitted,
            "replied": self.replied,
        }
        if stats is None:
            stats = self.queue_stats()
        # copy: the snapshot writer consumes the same overload dict, and
        # the gauge re-typing below pops keys out of it
        overload = dict(
            self.overload_counters(stats) if overload is None else overload
        )
        gauges: Dict[str, float] = {
            "queue_depth": overload.pop("queue_depth", 0),
            "queue_depth_hwm": overload.pop("queue_depth_hwm", 0),
        }
        if "digest_keys" in overload:
            gauges["digest_keys"] = overload.pop("digest_keys")
        counters.update(overload)
        if device is None:
            device = self._device_counters()
        if device:
            for name, value in device.items():
                if name in ("device_idle_frac", "device_pipeline_depth"):
                    gauges[name] = value
                else:
                    counters[name] = value
        hists: Dict[str, Any] = {}
        executor_metrics = _Metrics()
        for executor in self.executors:
            executor_metrics.merge(executor.metrics())
        for prefix, metrics in (
            ("protocol", self.process.metrics()),
            ("executor", executor_metrics),
        ):
            for kind, value in metrics.aggregated.items():
                counters[f"{prefix}_{getattr(kind, 'value', kind)}"] = value
            for kind, hist in metrics.collected.items():
                hists[f"{prefix}_{getattr(kind, 'value', kind)}"] = hist
        return counters, gauges, hists

    def _emit_telemetry(self) -> None:
        """One telemetry tick: a window line into the series (flushed, so
        a live ``obs watch`` sees it) and — when configured — the legacy
        crash-consistent pickle snapshot, from ONE walk of the queue /
        overload / device sources (so both views describe one instant)."""
        stats = self.queue_stats()
        overload = self.overload_counters(stats)
        device = self._device_counters()
        if self.telemetry is not None:
            counters, gauges, hists = self.telemetry_sample(
                stats, overload, device
            )
            self.telemetry.emit(
                f"p{self.process.id}", counters, gauges, hists
            )
            self.telemetry.flush()
        if self.metrics_file is not None:
            self._write_metrics_snapshot(
                queues=stats, overload=overload, device=device
            )

    async def _telemetry_task(self) -> None:
        """Periodic telemetry cadence (one knob:
        ``Config.telemetry_interval_ms``): windowed series emit + the
        crash-consistent metrics snapshot (metrics_logger.rs:75-87),
        unified on one writer."""
        while True:
            await asyncio.sleep(self.telemetry_interval_ms / 1000)
            self._emit_telemetry()

    async def _execution_log_flush_task(self) -> None:
        """1s execution-log flush (execution_logger.rs:8-29)."""
        while True:
            await asyncio.sleep(1.0)
            self.execution_logger.flush()

    async def _trace_flush_task(self) -> None:
        """Periodic span-log flush: keeps the on-disk JSONL prefix fresh
        (crash consistency — every flushed line is self-contained)."""
        while True:
            await asyncio.sleep(1.0)
            self.tracer.flush()

    async def _tracer_task(self) -> None:
        """Periodic function-latency histogram dump (tracer.rs:16-44).

        The prof registry is scoped to this runtime (utils/prof.py
        contextvar, installed in start() before tasks spawn), so the dump
        owns its samples even when several runtimes share one Python
        process in the localhost harness."""
        from fantoch_tpu.utils import prof

        while True:
            await asyncio.sleep(self.tracer_show_interval_ms / 1000)
            formatted = prof.format_snapshot()
            if formatted:
                logger.info(
                    "tracer (p%s registry):\n%s",
                    self.process.id,
                    formatted,
                )

    async def _periodic_task(self, event: Any, interval_ms: int) -> None:
        while True:
            await asyncio.sleep(interval_ms / 1000)
            index = self.protocol_cls.event_index(event)
            self.workers.forward(index, ("event", event))

    async def _executed_notification_task(self, interval_ms: int) -> None:
        """Collect executed clocks and notify the GC worker
        (executor.rs:295-313)."""
        while True:
            await asyncio.sleep(interval_ms / 1000)
            for executor in self.executors:
                executed = executor.executed(self.time)
                if executed is not None:
                    if self.wal is not None:
                        # the executor emit frontier rides the log too:
                        # a recovered tail shows how far execution got,
                        # next to the commit records that drove it
                        self.wal.append("frontier", executed)
                    self.workers.forward_to(0, ("executed", executed))
