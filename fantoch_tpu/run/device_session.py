"""The session plane of the served path: the server side of one client
connection against a device-step runtime.

What this module owns: ``_DeviceClientSession`` (the handshake, the pass
that turns a socket read's frames into admitted commands, what a command
in flight is owed, the frames a round's partials complete and their one
write a connection) and ``SessionTallies``, what that work counts: one
instance a runtime, handed to every session, published by
``DeviceRuntime._publish_tallies`` under the snapshot's names.

What it may not import: ``run/device_runner.py``.  The arrows point one
way, runtime -> session plane -> (the wire: ``run/rw.py``,
``run/prelude.py``; the command: ``core/command.py``), and a session uses
the runtime's public surface alone (tests/test_served_layers.py holds
both).  Of the drivers' module it takes one name, ``_buckets``: what a
command's key buckets are is defined once, beside the key column.
"""

from __future__ import annotations

import asyncio
import sys
from operator import itemgetter
from time import monotonic_ns, thread_time_ns
from typing import Any, Dict, List, Optional, Tuple

from fantoch_tpu.core.command import FLAT, Command
from fantoch_tpu.core.ids import ClientId, Dot, Rifl
from fantoch_tpu.executor.base import ExecutorResult
from fantoch_tpu.observability.device import CPU_PAIR_EVERY_NS, LOOP_ROW_NS
from fantoch_tpu.run.device_drivers import _buckets
from fantoch_tpu.run.prelude import (
    ClientHi,
    ClientHiAck,
    Overloaded,
    Register,
    ToClient,
)
from fantoch_tpu.run.rw import ProtocolError, Rw, joined_reply_frame, partial_reply_frame
from fantoch_tpu.utils import logger

# the shard of a ``(shard, keys)`` entry of a command's nested wire form
_SHARD = itemgetter(0)


class SessionTallies:
    """What the sessions of one runtime count, summed over them: the
    per-command boundaries around the rounds (two clock reads each, no
    span).  Written by the session plane alone, read where the runtime
    publishes its tallies."""

    __slots__ = (
        # admitted commands of one key on one shard: tracked by rifl alone
        "flat_admitted",
        "admit_ns",  # a read's messages decoded -> its commands pushed
        # ... and of the passes that took a CPU pair: the loop's thread on
        # a CPU, their wall time, when the next pair is due
        "admit_cpu_ns",
        "admit_timed_ns",
        "admit_cpu_due",
        "flush_ns",  # awaits of rw.flush() in the sessions
        "flushes",
        "reply_writes",  # writes of a round's frames to a connection
        "reply_bytes",
        "shard_replies",  # CommandResult frames: one per touched shard
        "reply_plain_frames",  # ... encoded from their values (rw.*_reply_frame)
        # ... of them a one-key command's, from its one partial
        "reply_flat_frames",
        # ... made from one partial, whatever the command's shape (a key
        # alone on its shard)
        "reply_partial_frames",
        "commands_completed",  # a command's last shard replied
        "multi_shard_completed",  # ... of a command over several shards
        "gets_replied",  # read-only commands among the completed
        "get_value_bytes",  # bytes (UTF-8) of the values their replies carried
    )

    def __init__(self) -> None:
        for name in self.__slots__:
            setattr(self, name, 0)


class _DeviceClientSession:
    """Server side of one client connection against the device driver
    (the client.rs:79-260 role, minus dot routing — the driver orders).

    A command comes in as ``Rw.recv_all`` gives it: the tuple its frame
    unpickled to, kept as its ops (``Command._wire``), and ``_admit`` and
    ``track`` read that tuple in place; the dict form is built only where
    a command is refused in ``_validate``'s words.

    What the session keeps for a command in flight depends on the
    command's shape.  One key on one shard (the dominant shape): its
    ``runtime.rifl_sessions`` entry and nothing else (and its rifl in
    ``_reads`` where it is read-only); its one partial is its reply.
    Any other shape: beside that entry one record in ``_owed``, a dict
    of the keys whose partials are still owed.  A key alone on its shard
    maps to whether the command has several shards (a bool): its partial
    is that shard's whole reply.  The keys that share a shard map to the
    one list they share, ``[keys left, {key: op_results} in the order
    the partials land, several shards?]``: the shard's reply is framed
    when its last key lands.  A partial takes its key out; the command
    is answered when the record is empty.  (A partial names its key and
    no shard, so a command is taken to name a key once.)"""

    def __init__(self, runtime: "DeviceRuntime", rw: Rw):
        self.runtime = runtime
        # what the runtime's sessions count, all of them on one object
        self.tallies: SessionTallies = runtime.session_tallies
        self.rw = rw
        driver = runtime.driver
        # the shards this server has
        self._served = frozenset(
            range(driver.shard_count)
            if driver.shard_count > 1
            else (driver.shard_id,)  # single-shard may sit on any shard id
        )
        # rifl -> the keys still owed, for a command that is not one key
        # on one shard: a multi-shard command answers with one
        # CommandResult PER SHARD (the per-shard-server contract the
        # client plane counts on, run/client_runner.py submit()); the
        # unified mesh server emits them all over the submit connection.
        self._owed: Dict[Rifl, Dict[str, Any]] = {}
        # the read-only commands among them: what their replies carry is
        # counted apart (gets_replied, get_value_bytes)
        self._reads: set = set()
        self.client_ids: List[ClientId] = []
        # the next dot of the coordinator at the clients' site (site 0
        # until the hello says otherwise)
        self._next_dot = runtime.dot_gen.next_id
        self._flush_needed = asyncio.Event()

    def track(self, cmd: Command) -> None:
        """Register a submitted command as in flight: route its results
        here (``runtime.rifl_sessions``) and, unless it has one key on one
        shard, write its record of the keys owed (the class's docstring),
        in one pass over the shards of its wire form.  A one-key command
        is complete at its first and only partial, so its rifl's routing
        entry is all it keeps (``deliver`` frames its reply from that
        partial)."""
        rifl = cmd._rifl
        if cmd._read_only:
            self._reads.add(rifl)
        self.runtime.rifl_sessions[rifl] = self
        wire = cmd._wire
        several = len(wire) != FLAT and len(wire[2]) > 1
        if cmd._total_key_count == 1 and not several:
            self.tallies.flat_admitted += 1
            return
        owed: Dict[str, Any] = {}
        for _shard, keys in wire[2]:
            if len(keys) == 1:
                owed[keys[0][0]] = several
            else:
                shared = [len(keys), {}, several]
                for key, _ops in keys:
                    owed[key] = shared
        self._owed[rifl] = owed

    def forget(self) -> None:
        """Drop what the session holds of its commands in flight (the
        connection closed: ``DeviceRuntime.drop_session``)."""
        self._owed.clear()
        self._reads.clear()

    def deliver(self, results: List[ExecutorResult]) -> int:
        """Route one round's per-key partials of this session's commands,
        in the order the round executed them, and hand the connection
        every reply they complete in one write.  A partial whose rifl has
        no record is a one-key command's only one, and one whose key is
        alone on its shard is that shard's whole reply: the frame is made
        from the partial itself (``rw.partial_reply_frame``, the bytes
        ``rw.reply_frame`` gives for the ``CommandResult`` it would
        complete).  A key that shares its shard joins the shard's results,
        and the last of them frames the shard's reply from those
        (``rw.joined_reply_frame``: the same bytes, no ``CommandResult``
        built).  A key the record does not owe (the same rifl twice in one
        round) is skipped: a shard is answered once.  Returns how many
        rifls are now fully answered; those are gone from
        ``runtime.rifl_sessions`` whether or not the write went
        through."""
        runtime = self.runtime
        tallies = self.tallies
        rifl_sessions = runtime.rifl_sessions
        owed_by_rifl = self._owed
        reads = self._reads
        tracer = runtime.tracer
        tracing = tracer.enabled
        frames: List[bytes] = []
        flat = partial = completed = multi_shard = gets = get_bytes = 0
        for result in results:
            rifl = result.rifl
            is_read = bool(reads) and rifl in reads
            if is_read:
                for value in result.op_results:
                    if value is not None:
                        # what its frame carries: UTF-8, a byte a letter
                        # where all are ASCII (a flag of the string)
                        get_bytes += (
                            len(value) if value.isascii() else len(value.encode())
                        )
            owed = owed_by_rifl.get(rifl) if owed_by_rifl else None
            if owed is None:
                # one key on one shard: tracked by its rifl alone, complete
                # at this partial.  No entry: stale (the same rifl twice in
                # one round: answered at the first)
                if rifl_sessions.pop(rifl, None) is None:
                    continue
                frame = partial_reply_frame(result)
                flat += 1
                last = True
            else:
                key = result.key
                slot = owed.pop(key, None)
                if slot is None:
                    continue  # not owed: this shard's reply took it
                if slot.__class__ is list:
                    # one of its shard's several keys: the last frames them
                    joined = slot[1]
                    joined[key] = result.op_results
                    left = slot[0] - 1
                    if left:
                        slot[0] = left
                        continue
                    frame = joined_reply_frame(rifl, len(joined), joined)
                    several = slot[2]
                else:
                    frame = partial_reply_frame(result)
                    partial += 1
                    several = slot
                last = not owed
                if last:
                    del owed_by_rifl[rifl], rifl_sessions[rifl]
                    completed += 1
                    multi_shard += several
            if tracing:
                tracer.span("executed", rifl, pid=runtime.process_id)
                tracer.edge("s", "Reply", runtime.process_id, 0, 0, rifl=rifl)
            frames.append(frame)
            if is_read and last:
                reads.discard(rifl)
                gets += 1
        completed += flat
        partial += flat
        if frames:
            data = b"".join(frames)
            self.rw.write_frames(data)
            tallies.reply_writes += 1
            tallies.reply_bytes += len(data)
            tallies.shard_replies += len(frames)
            tallies.reply_plain_frames += len(frames)
            tallies.reply_flat_frames += flat
            tallies.reply_partial_frames += partial
            tallies.commands_completed += completed
            tallies.multi_shard_completed += multi_shard
            tallies.gets_replied += gets
            tallies.get_value_bytes += get_bytes
            self._flush_needed.set()
        return completed

    async def _flush_loop(self) -> None:
        tallies = self.tallies
        while True:
            await self._flush_needed.wait()
            self._flush_needed.clear()
            t0 = monotonic_ns()
            await self.rw.flush()
            tallies.flush_ns += monotonic_ns() - t0
            tallies.flushes += 1

    def _reject(self, cmd: Command, why: str) -> None:
        """Reply with an empty (zero-key) CommandResult — the client's
        bookkeeping keys on the rifl alone — instead of letting a
        malformed command reach the driver and trip an assert there."""
        from fantoch_tpu.core.command import CommandResult

        logger.warning(
            "rejecting command %s from client %s: %s",
            cmd.rifl, cmd.rifl.source, why,
        )
        self.rw.write(ToClient(CommandResult(cmd.rifl, 0)))
        self._flush_needed.set()

    def _shed(self, cmd: Command) -> None:
        """Admission-control shed: typed Overloaded reply + retry-after
        hint (run/backpressure.py plane; the client retries with capped
        backoff or sheds the command itself at its deadline)."""
        from fantoch_tpu.run.backpressure import log_per_doubling

        runtime = self.runtime
        ring = runtime.submit_ring
        ring.sheds += 1
        retry_after = runtime.retry_after_ms()
        if log_per_doubling(ring.sheds):
            logger.warning(
                "shedding submission %s from client %s: submit ring at its "
                "bound (%d >= %s); retry after %dms; %d sheds total",
                cmd.rifl, cmd.rifl.source, len(ring), ring.capacity,
                retry_after, ring.sheds,
            )
        self.rw.write(
            Overloaded(
                cmd.rifl, retry_after, depth=len(ring),
                limit=ring.capacity or 0,
            )
        )
        self._flush_needed.set()

    def _validate(self, cmd: Command) -> Optional[str]:
        """What the driver's key column asserts (``_key_column``: at
        least one bucket, no more than the key width, every shard the
        server's), decided at the session boundary for the command that
        fails ``_admit``'s quick test: returns the rejection reason for
        commands the compiled device state cannot carry, by the plain
        definition of a command's buckets (``_buckets``)."""
        driver = self.runtime.driver
        # sharded: a shard id outside the compiled range would alias
        # another shard's buckets on-device (safe_key clamping) — reject
        # it at the wire, like any other contract breakage
        if driver.shard_count > 1:
            for sid in cmd.shards():
                if not 0 <= sid < driver.shard_count:
                    return (
                        f"command names shard {sid} but the server is "
                        f"compiled for {driver.shard_count} shard(s)"
                    )
        elif cmd.shard_count > 1:
            return (
                "multi-shard command submitted to a single-shard "
                "device server"
            )
        buckets = _buckets(
            cmd, driver.shard_id, driver.key_buckets, driver.shard_count
        )
        if not buckets:
            return "command touches no keys"
        # key_width None = the driver needs no key rows (slot-ordered)
        if driver.key_width is not None and len(buckets) > driver.key_width:
            return (
                f"command touches {len(buckets)} key buckets but the device "
                f"state was compiled with key_width={driver.key_width}"
            )
        return None

    def _admit(self, msgs: List[Any]) -> None:
        """One pass over the messages of a socket read, in frame order
        (as ``Rw.recv_all`` gives them: a ``Submit``'s frame is its
        ``Command``).  A command is validated, checked against the ring's
        bound (shed with a typed Overloaded BEFORE tracking, so the retry
        re-runs the full path with no leftover aggregation state),
        tracked and given its dot; the read's admitted commands then
        enter the ring together.  A command of one key on one shard can
        only name the wrong shard (one bucket never exceeds the key
        width), so its shard is all that is checked, and what ``track``
        does for it is done in place.  Any other shape with at least one
        key, no more keys than the key width and every shard the server's
        is accepted as it stands too (distinct buckets never outnumber
        keys) and goes to ``track``; whatever fails that test goes to
        ``_validate``, which decides in its own words.  Any other message
        is taken where it stands: what was admitted before it is pushed
        whatever it raises."""
        t0 = monotonic_ns()
        # the read's one arrival time: it rides beside the read's run in
        # the ring
        now_ms = t0 / 1e6
        runtime = self.runtime
        tallies = self.tallies
        stages = runtime.stages
        # on the capture's clock too, while one runs
        note = stages.annotate("fantoch/admit") if stages.capturing else None
        # the thread's CPU time of the pass beside its wall time, at most
        # once in CPU_PAIR_EVERY_NS (as a stage's span takes it)
        timed = t0 >= tallies.admit_cpu_due
        if timed:
            cpu0 = thread_time_ns()
        room = runtime.room()
        tracer = runtime.tracer
        tracing = tracer.enabled
        rifl_sessions = runtime.rifl_sessions
        next_dot = self._next_dot
        validate, track = self._validate, self.track
        reads = self._reads
        served = self._served
        # key_width None = the driver needs no key rows: no bound
        key_width = runtime.driver.key_width
        if key_width is None:
            key_width = sys.maxsize
        flat = 0
        admitted: List[Tuple[Dot, Command]] = []
        try:
            for cmd in msgs:
                if cmd.__class__ is not Command:
                    self._not_a_submit(cmd)
                    continue
                if tracing:
                    # ingress edge: client->server network vs queue
                    # split in the critpath report
                    tracer.edge(
                        "r", "Submit", 0, runtime.process_id, 0, rifl=cmd.rifl,
                    )
                # what the frame carried (``Command._wire``), read in place
                wire = cmd._wire
                one_key = len(wire) == FLAT
                if one_key:
                    # a wrong shard: the reason in _validate's words
                    why = None if wire[2] in served else validate(cmd)
                elif (
                    0 < cmd._total_key_count <= key_width
                    and served.issuperset(map(_SHARD, wire[2]))
                ):
                    why = None
                else:
                    why = validate(cmd)
                if why is not None:
                    self._reject(cmd, why)
                    continue
                if room is not None and len(admitted) >= room:
                    # the ring fills inside this read: push what the
                    # read has admitted, so that the shed's reply reads
                    # the ring at its bound
                    if admitted:
                        runtime.submit_all(admitted, now_ms)
                        admitted = []
                        room = 0
                    self._shed(cmd)
                    continue
                if one_key:
                    # track(cmd), in place
                    rifl = cmd._rifl
                    if cmd._read_only:
                        reads.add(rifl)
                    rifl_sessions[rifl] = self
                    flat += 1
                else:
                    track(cmd)
                dot = next_dot()
                if tracing:
                    tracer.span(
                        "payload", cmd.rifl, dot=dot, pid=runtime.process_id,
                    )
                admitted.append((dot, cmd))
        finally:
            tallies.flat_admitted += flat
            if admitted:
                runtime.submit_all(admitted, now_ms)
            if timed:
                tallies.admit_cpu_ns += thread_time_ns() - cpu0
            end = monotonic_ns()
            took = end - t0
            if timed:
                tallies.admit_timed_ns += took
                tallies.admit_cpu_due = t0 + CPU_PAIR_EVERY_NS
            tallies.admit_ns += took
            if note is not None:
                note.__exit__(None, None, None)
            # the socket read's pass, from the first byte ``recv_all``
            # walked: its clock read and this pass's, no third
            read_t0 = self.rw.read_t0
            if read_t0:
                self.rw.read_t0 = 0
                stages.record("read", read_t0, end, row=end - read_t0 >= LOOP_ROW_NS)

    def _not_a_submit(self, msg: Any) -> None:
        if not isinstance(msg, Register):
            raise ProtocolError(f"unexpected message {msg!r}")
        # sharded: the unified mesh executes every shard's portion
        # behind the submit session; per-shard registration has nothing
        # to set up
        if self.runtime.driver.shard_count == 1:
            raise ProtocolError(
                "device-step serving is single-shard; Register "
                "(multi-shard partial registration) has no "
                "meaning here"
            )

    async def run(self) -> None:
        try:
            hi = await self.rw.recv()
            if hi is None:
                return  # clean close before handshake (port probe)
            if not isinstance(hi, ClientHi):
                raise ProtocolError(f"expected ClientHi, got {hi!r}")
            self.client_ids = hi.client_ids
            # the site its clients are at coordinates their commands: the
            # round is made ready for it before the hello is acknowledged,
            # and a site that cannot be served ends the session here
            try:
                self._next_dot = self.runtime.register_site(hi.site)
            except ValueError as exc:
                raise ProtocolError(str(exc)) from None
            await self.rw.send(ClientHiAck())
            # live from here on: its socket is the runtime's to hold
            self.runtime.add_session(self)
            flusher = self.runtime.spawn(self._flush_loop(), fatal=False)
            try:
                while True:
                    # what one socket read brought, every whole frame of it
                    msgs = await self.rw.recv_all()
                    if msgs is None:
                        break
                    self._admit(msgs)
            finally:
                flusher.cancel()
        finally:
            self.runtime.drop_session(self)
            # always close the transport: a session dying on ProtocolError
            # must leave the client an EOF, not a silent hang, and the
            # server must not leak the fd
            self.rw.close()

