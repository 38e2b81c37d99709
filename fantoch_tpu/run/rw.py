"""Length-delimited framing over asyncio streams.

Reference: fantoch/src/run/rw/{mod,connection}.rs — the reference frames
with tokio's LengthDelimitedCodec + bincode; here frames are a u32
big-endian length prefix + pickled payload.  ``write`` queues without
flushing, ``send`` queues and flushes, mirroring the reference's explicit
flush control (rw/mod.rs:55-84) that lets writers batch small protocol
messages into one syscall.
"""

from __future__ import annotations

import asyncio
import pickle
import socket
import struct
from time import monotonic_ns
from typing import Any, List, Optional

_LEN = struct.Struct(">I")
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
        """``decode_tally``: a ``[ns, frames]`` pair the owner shares
        among its connections; ``recv`` adds each frame's unpickle time
        to it (the device runtime's ``session_decode_ms``)."""
        self._reader = reader
        self._writer = writer
        self._decode_tally = decode_tally
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
