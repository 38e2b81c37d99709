"""Length-delimited framing over asyncio streams.

Reference: fantoch/src/run/rw/{mod,connection}.rs — the reference frames
with tokio's LengthDelimitedCodec + bincode; here frames are a u32
big-endian length prefix + pickled payload.  The two hot messages of the
client plane pickle as plain values under one callable each, with no
class path or attribute name per field: the reply (``ToClient``, run/
prelude.py) and the ``Submit`` (a command's rifl numbers, then ``shard,
key, kind code, value`` where it has one key and one op, nested ``shard
-> key -> ops`` tuples otherwise; core/command.py).  ``write`` queues
without flushing, ``send`` queues and flushes, mirroring the reference's
explicit flush control (rw/mod.rs:55-84) that lets writers batch small
protocol messages into one syscall; ``recv`` reads one frame, ``recv_all``
every whole frame a socket read brought.
"""

from __future__ import annotations

import asyncio
import pickle
import socket
import struct
from time import monotonic_ns, thread_time_ns
from typing import Any, List, Optional

from fantoch_tpu.observability.device import CPU_PAIR_EVERY_NS

_LEN = struct.Struct(">I")
# what one ``recv_all`` asks the stream for: more than a ``StreamReader``
# holds (it pauses its transport above twice its ``limit``), so a read
# takes all that is buffered and the reader's limit is the bound
_READ_ALL = 1 << 24
# link frames (peer connections after the handshake): u8 kind + u64 seq
# header inside the length-delimited frame; see run/links.py for the
# reliability protocol built on top
_LINK = struct.Struct(">BQ")


def serialize(value: Any) -> bytes:
    return pickle.dumps(value, protocol=pickle.HIGHEST_PROTOCOL)


def deserialize(payload: bytes) -> Any:
    return pickle.loads(payload)


def frame(value: Any) -> bytes:
    """One frame as it goes on the wire: u32 big-endian length, then the
    pickle."""
    payload = serialize(value)
    return _LEN.pack(len(payload)) + payload


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
    ):
        """``decode_tally``: ``[ns, frames, reads, CPU ns, timed ns, due
        ns]``, which the owner shares among its connections; ``recv`` adds
        each frame's unpickle time to it, ``recv_all`` a read's walk and
        unpickles and the read itself, and at most once in
        ``CPU_PAIR_EVERY_NS`` the thread's CPU time of the walk beside its
        wall time (the device runtime's ``session_decode_ms``,
        ``session_decoded``, ``session_reads``, ``session_decode_cpu_ms``,
        ``session_decode_timed_ms``)."""
        self._reader = reader
        self._writer = writer
        self._decode_tally = decode_tally
        self._tail = b""  # recv_all: the bytes of a frame not yet whole
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
            return pickle.loads(payload)
        t0 = monotonic_ns()
        value = pickle.loads(payload)
        tally[0] += monotonic_ns() - t0
        tally[1] += 1
        return value

    async def recv_all(self) -> Optional[List[Any]]:
        """Every whole frame the connection holds, decoded, in order:
        one read of the stream when no frame is whole yet, then a walk
        over the bytes; the incomplete tail waits for the next read.
        None on EOF.  A connection that has called this stays with it
        (``recv`` does not see the tail)."""
        read, unpack_from, loads, size = (
            self._reader.read, _LEN.unpack_from, pickle.loads, _LEN.size,
        )
        while True:
            try:
                data = await read(_READ_ALL)
            except ConnectionResetError:
                return None
            if not data:
                if len(self._tail) >= size:
                    # EOF inside a payload: what readexactly raises
                    raise asyncio.IncompleteReadError(self._tail[size:], None)
                return None
            tally = self._decode_tally
            t0 = monotonic_ns()
            timed = tally is not None and t0 >= tally[5]
            if timed:
                cpu0 = thread_time_ns()
            if self._tail:
                data = self._tail + data
            values: List[Any] = []
            at, end = 0, len(data)
            while end - at >= size:
                stop = at + size + unpack_from(data, at)[0]
                if stop > end:
                    break
                values.append(loads(data[at + size : stop]))
                at = stop
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
            if values:
                return values

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
