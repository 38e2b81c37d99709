"""Wire messages and worker pools for the real runner.

Reference: fantoch/src/run/prelude.rs (handshakes, client wire protocol,
the POEMessage protocol/executor split) and fantoch/src/run/pool.rs
(``ToPool``: a vector of channels with reserved-index routing).  Channels
are asyncio queues; a pool's ``forward`` resolves a
:data:`fantoch_tpu.run.routing.WorkerIndex` exactly like the reference's
reserved-index arithmetic.
"""

from __future__ import annotations

import asyncio
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from fantoch_tpu.core.command import (
    Command,
    CommandResult,
    _off_wire,
    _restore_result,
)
from fantoch_tpu.core.ids import ClientId, ProcessId, Rifl, ShardId
from fantoch_tpu.run.backpressure import BoundedQueue
from fantoch_tpu.run.routing import WorkerIndex, resolve_index


class WarnQueue(BoundedQueue):
    """The analog of the reference's bounded channels
    (fantoch/src/run/task/chan.rs:36-58, warn-then-block on full), now
    riding the overload-control plane (run/backpressure.BoundedQueue):
    producers here are synchronous handlers on one cooperative loop, so
    blocking them would deadlock the consumer; instead the queue warns
    (once per doubling, so a runaway queue keeps shouting but doesn't
    spam), tracks depth gauges, and — when bounded — closes a credit
    gate the socket-reader tasks pause on, so pressure propagates
    peer-to-peer via TCP instead of as unbounded heap."""

    def __init__(
        self,
        name: str,
        warn_size: int = 8192,
        capacity: Optional[int] = None,
    ):
        super().__init__(name, capacity=capacity, warn_size=warn_size)


# --- handshakes (prelude.rs:38-50) ---


@dataclass
class ProcessHi:
    """Peer-link handshake.  ``link`` identifies which of the sender's
    ``multiplexing`` links this connection carries: the receiver keys its
    dedup state on (process_id, link) so a reconnected link resumes where
    its predecessor stopped (run/links.py).  ``incarnation`` is the
    sender's WAL boot counter (run/wal.py): a *restarted* process starts
    a fresh sequence space, so the receiver resets its per-link dedup
    when the incarnation changes — same-life reconnects keep it."""

    process_id: ProcessId
    shard_id: ShardId
    link: int = 0
    incarnation: int = 0


@dataclass
class ClientHi:
    """Client -> server: the logical clients of this connection and the
    site they are at.  A command's coordinator is the replica at its
    client's site; a hello that names none is at site 0 (every client
    attached to replica 0: the deployment a one-coordinator round is).  A
    device-step server refuses a site that is none of its replicas', or
    not 0 where its round has one coordinator (run/device_drivers.py
    ``register_site``)."""

    client_ids: List[ClientId]
    site: int = 0


@dataclass
class ClientHiAck:
    """Server -> client: the session is registered for result delivery.
    Clients must not submit before every shard acks — a partial executed
    on a non-target shard before its session registration would be
    unrouteable and silently dropped (the ClientHi-vs-execution race)."""


# --- client wire protocol (prelude.rs:52-69) ---


@dataclass
class Register:
    """Multi-shard registration: a client sends the command to every
    non-target shard it touches so that shard's result aggregation knows
    the rifl (fantoch/src/run/prelude.rs:52, mod.rs:757-764)."""

    cmd: Any


@dataclass
class Unregister:
    """Client -> non-target shard: withdraw a multi-shard command's
    Register (the command was shed past its deadline and will never be
    submitted again).  Without it, each deadline-shed multi-shard command
    would leak one aggregation entry per non-target shard for the life
    of the session — the unbounded-state class the overload plane
    exists to close."""

    rifl: Rifl


@dataclass
class Submit:
    """Client -> server: one command to order and execute.  The hot
    frame of the way in, so it goes as the command's plain values
    (``Command.__reduce__``: rifl source and sequence, then shard, key,
    kind code and value of a one-key one-op command, 140-odd bytes at a
    100-byte value; or the nested ``shard -> key -> ops`` tuples of any
    other shape), with no class path or attribute name: on a client
    connection under a kind byte (run/rw.py ``KIND_SUBMIT``), in a
    generic pickle (a message that carries one, a deepcopy) under the
    one callable :func:`_submit`, the form a sender before PR 39 framed
    and a receiver still reads.  The sender's class, and what
    ``rw.deserialize`` and ``Rw.recv`` give; the device server's way in
    (``Rw.recv_all``) hands on the command of the frame and makes none."""

    cmd: Command

    def __reduce__(self):
        return _submit, self.cmd.__reduce__()[1]


def _submit(*values) -> Submit:
    """A :class:`Submit` from its ``Command``'s values: what unpickles
    one, and what ``rw`` calls on a ``KIND_SUBMIT`` frame's tuple."""
    return Submit(_off_wire(values))


@dataclass
class ToClient:
    cmd_result: CommandResult

    def __reduce__(self):
        # the reply is the hot frame of the client plane: the result's
        # plain values, instead of two class paths, the attribute names
        # and a BUILD each; under a kind byte on a client connection
        # (run/rw.py KIND_TO_CLIENT), under this one callable in a
        # generic pickle
        return _to_client, self.cmd_result.__reduce__()[1]


def _to_client(*values) -> ToClient:
    """A :class:`ToClient` from its ``CommandResult``'s values: what
    unpickles one, and what ``rw`` calls on a ``KIND_TO_CLIENT`` frame's
    tuple."""
    return ToClient(_restore_result(*values))


@dataclass
class Overloaded:
    """Server -> client: the submission was shed by admission control
    (the edge queue depth crossed ``Config.admission_limit``) — the wire
    form of :class:`fantoch_tpu.errors.OverloadedError`.  The client
    plane retries with capped exponential backoff floored by
    ``retry_after_ms`` (run/backpressure.Backoff) or sheds the command
    itself once its deadline budget expires.  No reference counterpart:
    the reference's channels block the whole connection instead of
    rejecting a single command."""

    rifl: Rifl
    retry_after_ms: int
    depth: int = 0
    limit: int = 0

    def to_error(self):
        """The typed client-side form of this frame."""
        from fantoch_tpu.errors import OverloadedError

        return OverloadedError(self.depth, self.limit, self.retry_after_ms)


# --- process wire protocol: protocol/executor split (prelude.rs:71-77) ---


@dataclass
class DigestKeyRequest:
    """Divergence drill-down (Config.execution_digests): a peer's
    heartbeat digest summary mismatched ours on ``key`` — send back the
    full hash chain so the FIRST diverging write can be named (the typed
    DivergenceError carries key + position + both commands)."""

    key: str


@dataclass
class DigestKeyReply:
    """One key's full executed-write hash chain:
    [(rifl_src, rifl_seq, digest), ...] (core/audit.DigestEntry rows)."""

    key: str
    entries: List[Any]


@dataclass
class PingReq:
    """Peer RTT probe (the localhost analog of the reference's `ping -c 1`
    shell-out, fantoch/src/run/task/ping.rs:71-78).

    ``digest`` piggybacks the sender's per-key execution-digest summary
    ({key: (write count, chain digest at that count)}) when
    ``Config.execution_digests`` is on: the receiver verifies every key
    where it is at least as far along — replicas cross-audit each other
    on the heartbeat cadence, and a fork surfaces as a typed
    DivergenceError instead of silently serving diverged reads.

    ``t_send_us`` (the sender's wall clock at send) turns the heartbeat
    into a clock-offset probe: the reply echoes it plus the replier's
    own clock, and the sender folds the bracket into its per-peer
    offset estimate (run/links.ClockOffsetEstimator) — what the
    critical-path correlator uses to compare timestamps across
    processes."""

    nonce: int
    digest: Optional[Dict[str, Any]] = None
    t_send_us: Optional[int] = None


@dataclass
class PingReply:
    nonce: int
    # clock-offset echo: the request's send stamp plus the replier's
    # clock at reply time (None on pings that did not carry a stamp)
    req_t_send_us: Optional[int] = None
    t_reply_us: Optional[int] = None


@dataclass
class POEProtocol:
    """A protocol message frame.  ``edge`` carries the sender's
    message-edge sequence number when the dot is trace-sampled
    (observability/tracer.py ``k == "edge"`` events): the receiver
    emits the matching recv edge so the critical-path correlator can
    stitch the hop causally.  None (the overwhelmingly common case)
    costs nothing on the wire beyond the field."""

    msg: Any
    edge: Optional[int] = None


@dataclass
class POEExecutor:
    info: Any


class ToPool:
    """Vector of queues with WorkerIndex routing (pool.rs:11-138).

    ``capacity`` bounds each queue with the watermark credit gate
    (run/backpressure.py): socket readers feeding the pool await
    :meth:`wait_for_credit` between frames, pausing their TCP stream
    while any destination queue sits above its high watermark."""

    def __init__(self, name: str, size: int, capacity: Optional[int] = None):
        self.name = name
        self._queues: List[WarnQueue] = [
            WarnQueue(f"{name}[{i}]", capacity=capacity) for i in range(size)
        ]

    @property
    def size(self) -> int:
        return len(self._queues)

    def queue(self, position: int) -> asyncio.Queue:
        return self._queues[position]

    @property
    def gated(self) -> bool:
        """True while any member queue's credit gate is closed."""
        return any(queue.gated for queue in self._queues)

    async def wait_for_credit(self) -> None:
        """Pause point for reader tasks: returns once every member queue
        is back below its low watermark (consumers share the loop, so
        awaiting here is what drains them)."""
        for queue in self._queues:
            if queue.gated:
                await queue.wait_for_credit()

    def max_depth(self) -> int:
        """The deepest member queue right now — the admission-control
        depth signal (the bottleneck queue, not the sum: one wedged
        worker is what collapses latency)."""
        return max(queue.qsize() for queue in self._queues)

    def stats(self) -> Dict[str, Dict[str, float]]:
        return {queue.name: queue.stats() for queue in self._queues}

    def forward(self, index: WorkerIndex, item: Any) -> None:
        """Route `item` by worker index.

        A None index means broadcast in the reference (each worker owns a
        partition of protocol state, pool.rs:92); here worker tasks share
        one protocol object, so broadcast messages need exactly one
        handling — deliver to queue 0.
        """
        position = resolve_index(index, len(self._queues))
        if position is None:
            position = 0
        self._queues[position].put_nowait(item)

    def forward_to(self, position: int, item: Any) -> None:
        self._queues[position % len(self._queues)].put_nowait(item)

    def broadcast(self, item: Any) -> None:
        for queue in self._queues:
            queue.put_nowait(item)
