"""Length-delimited framing over asyncio streams.

Reference: fantoch/src/run/rw/{mod,connection}.rs — the reference frames
with tokio's LengthDelimitedCodec + bincode; here a frame is a u32
big-endian length prefix, then a payload whose first byte says what it is.

- ``KIND_SUBMIT`` / ``KIND_TO_CLIENT`` (a byte below ``0x80``): the two hot
  messages of the client plane.  The rest of the payload is the pickle of
  the message's plain values as one tuple and names no callable: a
  ``Submit``'s command as ``Command.__reduce__`` gives it (the rifl's two
  numbers, then ``shard, key, kind code, value`` where it has one key and
  one op, nested ``shard -> key -> ops`` tuples otherwise; core/command.py),
  a ``ToClient``'s result as ``(source, sequence, key count, results)``.
  The receiver calls the kind's restorer on the tuple itself; the command
  keeps that tuple as its ops and builds nothing from it (``Command._wire``).
- ``0x80`` (how a pickle of protocol 2 and up starts): a pickle of the
  message, whatever it is.  Every other message goes so (the handshakes,
  ``Register``, ``Overloaded``, the peers' and links' messages), and so did
  the two hot ones until PR 39 (under one callable each, ``prelude._submit``
  and ``prelude._to_client``): a receiver still reads that form.

A sender writes the one form of a message and a receiver reads either by
the first byte; nothing chooses between them.  Any other first byte is a
:class:`ProtocolError`.  ``write`` queues without flushing, ``send`` queues
and flushes, mirroring the reference's explicit flush control
(rw/mod.rs:55-84) that lets writers batch small protocol messages into one
syscall; ``recv`` reads one frame, ``recv_all`` every whole frame a socket
read brought (the server's way in: a ``Submit``'s frame comes out as its
command, with no ``Submit`` around it).
"""

from __future__ import annotations

import asyncio
import pickle
import socket
import struct
from time import monotonic_ns, thread_time_ns
from typing import Any, List, Optional

from fantoch_tpu.core.command import CommandResult, _off_wire
from fantoch_tpu.observability.device import CPU_PAIR_EVERY_NS
from fantoch_tpu.run.prelude import Submit, ToClient, _submit, _to_client

_LEN = struct.Struct(">I")
# how a frame of a kind starts: its length (kind byte and pickle), its kind
_HEAD = struct.Struct(">IB")
KIND_SUBMIT = 0x01
KIND_TO_CLIENT = 0x02
# the first byte of a pickle of protocol 2 and up (``pickle.PROTO``): a
# kind is a byte below it
_PICKLE = 0x80
_SUBMIT, _TO_CLIENT = bytes((KIND_SUBMIT,)), bytes((KIND_TO_CLIENT,))
# kind -> what makes the message of the values its payload pickles
_RESTORERS = {KIND_SUBMIT: _submit, KIND_TO_CLIENT: _to_client}
_PROTOCOL = pickle.HIGHEST_PROTOCOL
# what one ``recv_all`` asks the stream for: more than a ``StreamReader``
# holds (it pauses its transport above twice its ``limit``), so a read
# takes all that is buffered and the reader's limit is the bound
_READ_ALL = 1 << 24
# link frames (peer connections after the handshake): u8 kind + u64 seq
# header inside the length-delimited frame; see run/links.py for the
# reliability protocol built on top
_LINK = struct.Struct(">BQ")


class ProtocolError(Exception):
    """A client broke the wire contract: kills only its session, never
    the runtime (the per-connection failure isolation of the reference's
    client task, fantoch/src/run/task/process.rs:320-325)."""


def serialize(value: Any) -> bytes:
    """A frame's payload: a ``Submit`` or a ``ToClient`` as its kind byte
    and the pickle of its plain values, anything else as its pickle."""
    cls = type(value)
    if cls is Submit:
        return _SUBMIT + pickle.dumps(value.cmd.__reduce__()[1], _PROTOCOL)
    if cls is ToClient:
        return _TO_CLIENT + pickle.dumps(value.cmd_result.__reduce__()[1], _PROTOCOL)
    return pickle.dumps(value, _PROTOCOL)


def deserialize(payload) -> Any:
    """The message of a frame's payload (``bytes`` or a view of them), by
    what its first byte says: a pickle, or the values of a kind."""
    if not payload:
        raise ProtocolError("empty frame")
    kind = payload[0]
    if kind == _PICKLE:
        return pickle.loads(payload)
    restore = _RESTORERS.get(kind)
    if restore is None:
        raise ProtocolError(f"unknown frame kind {kind:#04x}")
    return restore(*pickle.loads(memoryview(payload)[1:]))


def frame(value: Any) -> bytes:
    """One frame as it goes on the wire: u32 big-endian length, then the
    payload."""
    payload = serialize(value)
    return _LEN.pack(len(payload)) + payload


def reply_frame(result: CommandResult) -> bytes:
    """``frame(ToClient(result))`` from the result's values, with no
    ``ToClient`` built on the way."""
    return joined_reply_frame(result._rifl, result._key_count, result._results)


def partial_reply_frame(partial) -> bytes:
    """:func:`reply_frame` of the ``CommandResult`` that a one-key
    command's only partial (an ``ExecutorResult``) completes, byte for
    byte, with no ``CommandResult`` built on the way."""
    rifl = partial.rifl
    payload = pickle.dumps(
        (rifl[0], rifl[1], 1, {partial.key: partial.op_results}), _PROTOCOL
    )
    return _HEAD.pack(len(payload) + 1, KIND_TO_CLIENT) + payload


def joined_reply_frame(rifl, key_count: int, results: dict) -> bytes:
    """:func:`reply_frame` of the ``CommandResult`` of ``key_count`` keys
    that holds ``results`` (``key -> op_results`` in the order the keys'
    partials landed), byte for byte, with no ``CommandResult`` built on
    the way: a shard's reply where the command has several keys there."""
    payload = pickle.dumps((rifl[0], rifl[1], key_count, results), _PROTOCOL)
    return _HEAD.pack(len(payload) + 1, KIND_TO_CLIENT) + payload


async def connect_with_retry(
    addr: tuple, attempts: int = 120, backoff_s: float = 0.05
) -> "Rw":
    """Open a connection, retrying while the peer boots
    (process.rs:71-111; the client setup retries too, mod.rs:668-740).

    The backoff grows gently to ~1 s so the total budget is ~30 s: a
    freshly spawned server pays an interpreter + jax import before it
    can bind, which under a loaded single-core host exceeds a
    constant-50 ms budget (observed as suite-load flakes)."""
    last: Optional[OSError] = None
    delay = backoff_s
    for _ in range(attempts):
        try:
            reader, writer = await asyncio.open_connection(*addr)
            return Rw(reader, writer)
        except OSError as exc:
            last = exc
            await asyncio.sleep(delay)
            delay = min(delay * 1.2, 1.0)
    raise ConnectionError(f"could not connect to {addr}: {last!r}")


class Rw:
    """Framed reader/writer over one TCP connection."""

    def __init__(
        self,
        reader: asyncio.StreamReader,
        writer: asyncio.StreamWriter,
        decode_tally: Optional[List[int]] = None,
        stages=None,
    ):
        """``decode_tally``: ``[ns, frames, reads, CPU ns, timed ns, due
        ns, frames of a kind]``, which the owner shares among its
        connections; ``recv`` adds each frame's decode time to it,
        ``recv_all`` a read's walk and decodes and the read itself, and at
        most once in ``CPU_PAIR_EVERY_NS`` the thread's CPU time of the
        walk beside its wall time; both count the frames that said their
        kind in a byte and named no callable (the device runtime's
        ``session_decode_ms``, ``session_decoded``, ``session_reads``,
        ``session_decode_cpu_ms``, ``session_decode_timed_ms``,
        ``session_plain_decoded``).  ``stages``: the owner's
        ``StageRecorder``; while it says a profiler capture is running,
        ``recv_all`` annotates its walk as ``fantoch/decode``."""
        self._reader = reader
        self._writer = writer
        self._decode_tally = decode_tally
        self._stages = stages
        # when the walk of the last ``recv_all`` that gave frames began (its
        # own first clock read): where the session's ``read`` span starts
        self.read_t0 = 0
        self._tail = b""  # recv_all: the bytes of a frame not yet whole
        # the owner's hold on the socket's reads (``hold_reading``), and
        # whether it is the hold that paused the transport: the reader
        # pauses it too, for its own flow control, and that pause is not
        # the hold's to undo
        self._held = False
        self._hold_paused = False
        # the owner's to set: called once, when the next read of the
        # stream returns (``recv_all``: bytes, a part of a frame or the end)
        self.on_read = None
        sock = writer.get_extra_info("socket")
        if sock is not None:
            # TCP_NODELAY, as the reference's Connection (connection.rs:46-51)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    async def recv(self) -> Optional[Any]:
        """Read one frame; None on clean EOF."""
        try:
            header = await self._reader.readexactly(_LEN.size)
        except (asyncio.IncompleteReadError, ConnectionResetError):
            return None
        (length,) = _LEN.unpack(header)
        payload = await self._reader.readexactly(length)
        tally = self._decode_tally
        if tally is None:
            return deserialize(payload)
        t0 = monotonic_ns()
        value = deserialize(payload)
        tally[0] += monotonic_ns() - t0
        tally[1] += 1
        tally[6] += payload[0] != _PICKLE
        return value

    async def recv_all(self) -> Optional[List[Any]]:
        """Every whole frame the connection holds, decoded, in order:
        one read of the stream when no frame is whole yet, then a walk
        over the bytes; the incomplete tail waits for the next read.
        None on EOF.  A ``Submit``'s frame gives its ``Command`` (under
        ``KIND_SUBMIT`` the tuple it unpickled to, checked and kept:
        ``command._off_wire``), every other frame the message
        ``deserialize`` gives.  A connection that has called this stays
        with it (``recv`` does not see the tail)."""
        read, unpack_from, loads, size = (
            self._reader.read, _LEN.unpack_from, pickle.loads, _LEN.size,
        )
        restorers, off_wire = _RESTORERS.get, _off_wire
        stages = self._stages
        while True:
            try:
                data = await read(_READ_ALL)
            except ConnectionResetError:
                data = None
            if self.on_read is not None:
                # whatever the read brought; a task the call wakes runs no
                # sooner than this one next waits, so what the read holds
                # is with the owner by then
                on_read, self.on_read = self.on_read, None
                on_read()
            if not data:
                if data is not None and len(self._tail) >= size:
                    # EOF inside a payload: what readexactly raises
                    raise asyncio.IncompleteReadError(self._tail[size:], None)
                return None
            if self._held:
                # a read that emptied the reader has ended the reader's own
                # pause: the hold takes the transport over before the loop
                # polls it
                self._pause_for_hold()
            tally = self._decode_tally
            t0 = monotonic_ns()
            timed = tally is not None and t0 >= tally[5]
            if timed:
                cpu0 = thread_time_ns()
            # on the capture's clock too, while one runs (and only then: a
            # read is too frequent a span to annotate always)
            note = (
                stages.annotate("fantoch/decode")
                if stages is not None and stages.capturing
                else None
            )
            if self._tail:
                data = self._tail + data
            values: List[Any] = []
            view = memoryview(data)
            at, end, plain = 0, len(data), 0
            try:
                while end - at >= size:
                    start = at + size
                    stop = start + unpack_from(data, at)[0]
                    if stop > end:
                        break
                    # as deserialize, on the read's own bytes
                    if stop == start:
                        raise ProtocolError("empty frame")
                    kind = data[start]
                    if kind == KIND_SUBMIT:
                        # the server's hot frame first: its command, and no
                        # Submit made for _admit to take off again
                        values.append(off_wire(loads(view[start + 1 : stop])))
                        plain += 1
                    elif kind == _PICKLE:
                        value = loads(view[start:stop])
                        # a Submit as a sender before PR 39 framed it gives
                        # its command as well
                        values.append(value.cmd if value.__class__ is Submit else value)
                    else:
                        restore = restorers(kind)
                        if restore is None:
                            raise ProtocolError(f"unknown frame kind {kind:#04x}")
                        values.append(restore(*loads(view[start + 1 : stop])))
                        plain += 1
                    at = stop
            finally:
                if note is not None:
                    note.__exit__(None, None, None)
            self._tail = data[at:]
            if tally is not None:
                if timed:
                    tally[3] += thread_time_ns() - cpu0
                took = monotonic_ns() - t0
                if timed:
                    tally[4] += took
                    tally[5] = t0 + CPU_PAIR_EVERY_NS
                tally[0] += took
                tally[1] += len(values)
                tally[2] += bool(values)
                tally[6] += plain
            if values:
                self.read_t0 = t0
                return values

    # --- the owner's hold on the reads ---

    def hold_reading(self) -> None:
        """Leave what arrives from here on in the kernel's socket buffer
        until :meth:`release_reading`: the loop does not poll the socket,
        and the next read brings all of it at once.  What the reader
        already holds is still given out.  Writes go on.  The
        ``StreamReader`` pauses the same transport when it holds more than
        twice its limit and resumes it from ``read``; the two reasons are
        kept apart: a release leaves the reader's pause standing, and a
        ``read`` that ends the reader's pause during a hold hands the
        transport to the hold (``recv_all``)."""
        self._held = True
        self._pause_for_hold()

    def _pause_for_hold(self) -> None:
        transport = self._writer.transport
        if transport is not None and transport.is_reading():
            transport.pause_reading()
            self._hold_paused = True

    def release_reading(self) -> None:
        """End the hold: the socket is polled again, unless the reader has
        it paused for its own reasons.  Nothing on a connection not held,
        or closed meanwhile."""
        self._held = False
        if self._hold_paused:
            self._hold_paused = False
            self._writer.transport.resume_reading()

    def fileno(self) -> int:
        """The socket's descriptor, to poll it beside the loop; -1 where
        the transport has none, or has closed it."""
        sock = self._writer.get_extra_info("socket")
        return -1 if sock is None else sock.fileno()

    def write(self, value: Any) -> None:
        """Queue one frame without flushing."""
        self._writer.write(frame(value))

    def write_frames(self, frames: bytes) -> None:
        """Queue a run of frames (each as :func:`frame` makes it, joined)
        with one call of the transport, without flushing."""
        self._writer.write(frames)

    async def send(self, value: Any) -> None:
        """Queue one frame and flush."""
        self.write(value)
        await self.flush()

    # --- link framing (peer connections; run/links.py reliability) ---

    def write_link_frame(self, kind: int, seq: int, payload: bytes) -> None:
        """Queue one sequence-numbered frame without flushing."""
        header = _LINK.pack(kind, seq)
        self._writer.write(_LEN.pack(len(header) + len(payload)) + header + payload)

    async def recv_link_frame(self) -> Optional[tuple]:
        """Read one (kind, seq, payload) link frame; None on EOF/reset."""
        try:
            header = await self._reader.readexactly(_LEN.size)
            (length,) = _LEN.unpack(header)
            body = await self._reader.readexactly(length)
        except (asyncio.IncompleteReadError, ConnectionResetError, OSError):
            return None
        kind, seq = _LINK.unpack_from(body)
        return kind, seq, body[_LINK.size :]

    async def flush(self) -> None:
        await self._writer.drain()

    def close(self) -> None:
        self._writer.close()

    def abort(self) -> None:
        """Hard-kill the underlying transport (chaos hook: simulates the
        network dropping the connection while both processes stay up)."""
        transport = self._writer.transport
        if transport is not None:
            transport.abort()
