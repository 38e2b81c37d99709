"""The way in of the device-step server (run/device_session.py
``_DeviceClientSession``): the ``Submit`` on the wire (since PR 39 its kind
byte and its command's plain values; tests/test_wire_codec.py has the codec
alone), ``Rw.recv_all``
turning a socket read into messages, and the one admit pass per read,
against a started ``DeviceRuntime`` on the CPU whose session reads from a
``StreamReader`` the test feeds, so that what a read holds is exact.
"""

import asyncio
import pickle

import jax
import pytest

# as tests/test_device_runner.py: the device drivers' scan bodies crash
# jaxlib 0.4.x flakily while tracing
if tuple(int(x) for x in jax.__version__.split(".")[:2]) < (0, 5):
    pytest.skip("jax<0.5: device-driver scan tracing segfaults flakily", allow_module_level=True)

from fantoch_tpu.core import Command, Config, KVOp, Rifl
from fantoch_tpu.run import rw
from fantoch_tpu.run.device_drivers import _bucket
from fantoch_tpu.run.device_runner import DeviceRuntime
from fantoch_tpu.run.device_session import _DeviceClientSession
from fantoch_tpu.run.prelude import ClientHi, ClientHiAck, Overloaded, Register, Submit, ToClient
from fantoch_tpu.run.rw import ProtocolError
from tests.test_command_forms import has_dicts
from tests.test_wire_codec import BROKEN

KEY_BUCKETS = 64
VALUE = "v" * 100  # the cells' payload


# --- the wire form ---


def _cmd(shape, rifl=Rifl(2**40 + 7, 2**33)):
    return Command(rifl, shape)


WIRE_SHAPES = {
    "one_key_get": {0: {"k": (KVOp.get(),)}},
    "one_key_put": {3: {"k": (KVOp.put(VALUE),)}},
    "one_key_delete": {0: {"k": (KVOp.delete(),)}},
    "several_keys_one_shard": {0: {"b": (KVOp.put("1"),), "a": (KVOp.put("2"),), "c": (KVOp.delete(),)}},
    "two_keys_two_shards": {2: {"905": (KVOp.put(VALUE),)}, 1: {"17": (KVOp.put(VALUE),)}},
    "several_ops_on_a_key": {0: {"k": (KVOp.put("1"), KVOp.delete(), KVOp.put("2"))}},
    "two_gets_two_shards": {1: {"x": (KVOp.get(),)}, 0: {"y": (KVOp.get(), KVOp.get())}},
    "non_ascii_key_and_1kb_value": {0: {"ключ-鍵-🔑": (KVOp.put("é" * 1024),)}},
}


@pytest.mark.parametrize("name", sorted(WIRE_SHAPES))
def test_the_compact_submit_round_trips(name):
    cmd = _cmd(WIRE_SHAPES[name])
    payload = rw.serialize(Submit(cmd))
    back = rw.deserialize(payload)
    assert isinstance(back, Submit) and back == Submit(cmd)
    got = back.cmd
    assert got.rifl == cmd.rifl and isinstance(got.rifl, Rifl)
    assert (got.read_only, got.total_key_count, got.shard_count) == (
        cmd.read_only, cmd.total_key_count, cmd.shard_count,
    )
    # the order of shards and of keys is the order of execution and of the reply
    assert list(got.all_keys()) == list(cmd.all_keys())
    assert [list(got.iter_ops(sid)) for sid in got.shards()] == [
        list(cmd.iter_ops(sid)) for sid in cmd.shards()
    ]
    assert got.single_key() == cmd.single_key()
    # a Command on its own (peer messages, the WAL) takes the same form
    alone = pickle.loads(pickle.dumps(cmd))
    assert alone == cmd and alone.read_only == cmd.read_only
    # values, not the classes' paths, the attribute names or an Enum by name
    for word in (b"KVOp", b"Command", b"_shard_to_ops", b"_rifl", b"fantoch_tpu.core.ids", b"cmd"):
        assert word not in payload, word
    assert rw.frame(Submit(cmd)) == rw._LEN.pack(len(payload)) + payload


def test_the_flat_frame_of_a_cells_command_is_under_150_bytes():
    put = Command.from_single(Rifl(8191, 123456), 0, "999999", KVOp.put(VALUE))
    assert put.single_key() == (0, "999999")
    assert len(rw.frame(Submit(put))) < 150  # 144: under 200 with PR 28's callable
    two = _cmd({2: {"905": (KVOp.put(VALUE),)}, 1: {"17": (KVOp.put(VALUE),)}})
    assert two.single_key() is None
    assert len(rw.frame(Submit(two))) < 190  # 180 (under 250 then): the shared value goes once


def test_a_mixed_command_is_refused_on_the_way_in_as_by_the_constructor():
    mixed = Command.__new__(Command)
    mixed._rifl, mixed._read_only, mixed._total_key_count = Rifl(1, 1), False, 2
    mixed._shard_to_ops = {0: {"a": (KVOp.get(),), "b": (KVOp.put("1"),)}}
    with pytest.raises(AssertionError, match="cannot contain Get"):
        pickle.loads(pickle.dumps(mixed))


# --- Rw.recv_all: a read's bytes to messages ---


def _submit(client, seq, key, value=VALUE, shard=0):
    return Submit(Command.from_single(Rifl(client, seq), shard, key, KVOp.put(value)))


def _collect(reads):
    """What ``recv_all`` returns, call by call, when the stream is fed
    ``reads`` one at a time (each consumed before the next arrives),
    then EOF."""

    async def go():
        reader = asyncio.StreamReader()
        tally = [0, 0, 0, 0, 0, 0, 0]
        conn = rw.Rw(reader, _Writer(), decode_tally=tally)
        out = []
        pending = asyncio.ensure_future(conn.recv_all())
        for data in reads:
            reader.feed_data(data)
            await asyncio.sleep(0)
            if pending.done():
                out.append(pending.result())
                pending = asyncio.ensure_future(conn.recv_all())
                await asyncio.sleep(0)
                assert not pending.done()  # a read is taken whole
        reader.feed_eof()
        out.append(await asyncio.wait_for(pending, 5))
        return out, tally

    return asyncio.run(go())


def test_recv_all_returns_every_whole_frame_of_a_read_and_keeps_the_tail():
    msgs = [_submit(1, seq, f"k{seq}") for seq in range(1, 6)] + [Register(None), ClientHi([4])]
    frames = [rw.frame(m) for m in msgs]
    whole = b"".join(frames)
    # a Submit's frame comes out as its command, with no Submit around it
    msgs = [m.cmd if isinstance(m, Submit) else m for m in msgs]
    assert [type(m) for m in msgs] == [Command] * 5 + [Register, ClientHi]
    assert _collect([whole])[0] == [msgs, None]
    cut = len(frames[0]) + len(frames[1]) + 9  # inside the third
    out, tally = _collect([whole[:cut], whole[cut:]])
    assert out == [msgs[:2], msgs[2:], None]
    assert tally[1:3] == [7, 2] and tally[0] >= tally[4] > 0  # the first read took the CPU pair
    assert tally[6] == 5  # the Submits went under their kind byte, the other two as pickles
    # a byte at a time: a frame comes out with its last byte, never before
    out, tally = _collect([whole[i:i + 1] for i in range(len(whole))])
    assert out == [[m] for m in msgs] + [None]
    assert tally[1:3] == [7, 7] and tally[6] == 5  # a read that completes no frame is not counted


@pytest.mark.parametrize("kept, error", [(0, None), (3, None), (4, asyncio.IncompleteReadError), (60, asyncio.IncompleteReadError)])
def test_recv_all_at_eof_is_recvs_eof(kept, error):
    """EOF on a frame boundary or inside a header is a clean close; EOF
    inside a payload raises what ``recv``'s ``readexactly`` raises."""
    frames = [rw.frame(_submit(1, 1, "a")), rw.frame(_submit(1, 2, "b"))]
    data = frames[0] + frames[1][:kept]
    if error is None:
        assert _collect([data])[0] == [[_submit(1, 1, "a").cmd], None]
    else:
        with pytest.raises(error):
            _collect([data])


# --- the admit pass, on a started runtime ---


class _Writer:
    """What ``Rw`` and the session's flusher need of a ``StreamWriter``;
    keeps what was written."""

    transport = None

    def __init__(self):
        self.data = bytearray()
        self.closed = False

    def get_extra_info(self, name):
        return None

    def write(self, data):
        self.data += data

    async def drain(self):
        pass

    def close(self):
        self.closed = True

    def replies(self):
        out, at = [], 0
        while at < len(self.data):
            (length,) = rw._LEN.unpack_from(self.data, at)
            out.append(rw.deserialize(bytes(self.data[at + 4:at + 4 + length])))
            at += 4 + length
        return out


class _Served:
    """A started runtime and one session of it over a fed reader."""

    def __init__(self, shard_count=1, key_width=1, monitor=True, **config):
        self.runtime = DeviceRuntime(
            Config(3, 1, shard_count=shard_count, **config), ("127.0.0.1", 0),
            batch_size=16, key_buckets=KEY_BUCKETS, key_width=key_width,
            monitor_execution_order=monitor,
        )

    async def __aenter__(self):
        await self.runtime.start()
        self.reader, self.writer = asyncio.StreamReader(), _Writer()
        self.session = _DeviceClientSession(
            self.runtime, rw.Rw(self.reader, self.writer, decode_tally=self.runtime._decode_tally)
        )
        self.task = self.runtime.spawn(self.session.run(), fatal=False)
        await self.read(rw.frame(ClientHi([1, 2, 3])))
        assert self.writer.replies() == [ClientHiAck()]
        self.writer.data.clear()
        return self

    async def __aexit__(self, *exc):
        self.reader.feed_eof()
        await asyncio.wait([self.task], timeout=5)
        await self.runtime.stop()

    async def read(self, data):
        """One socket read: ``data`` arrives, and the session takes all
        of it before this returns."""
        self.reader.feed_data(data)
        while len(self.reader._buffer) and not self.task.done():
            await asyncio.sleep(0)
        await asyncio.sleep(0)

    async def replies(self, n):
        for _ in range(2000):
            got = self.writer.replies()
            if len(got) >= n:
                assert len(got) == n, got
                self.writer.data.clear()
                return got
            assert self.runtime.failure is None
            await asyncio.sleep(0.005)
        raise AssertionError(f"{len(self.writer.replies())} of {n} replies")

    def tallies(self):
        self.runtime._publish_tallies()
        return self.runtime._tallies


def _told(replies, n=0):
    """What burst ``n``'s replies say, without the burst's own number:
    (client, its first or second command) -> key -> returned values.
    Replies come in the order of execution, which is frame order only
    along a key's chain: the returned previous values show that."""
    out = {}
    for reply in replies:
        assert isinstance(reply, ToClient) and reply.cmd_result.ready
        result = reply.cmd_result
        told = {key.split("#")[0]: values for key, values in result.results.items()}
        assert out.setdefault((result.rifl.source, result.rifl.sequence - 2 * n), told) is told
    return out


def _burst(n):
    """Six writes of three clients, four of them a chain on one key, whose
    returned previous values show the order of admission; keys and
    sequence numbers are burst ``n``'s own."""
    return [
        _submit(1, 2 * n + 1, f"hot#{n}", "a"), _submit(2, 2 * n + 1, f"hot#{n}", "b"),
        _submit(3, 2 * n + 1, f"cold#{n}", "c"), _submit(1, 2 * n + 2, f"hot#{n}", "d"),
        _submit(3, 2 * n + 2, f"cold#{n}", "e"), _submit(2, 2 * n + 2, f"hot#{n}", "f"),
    ]


def test_a_burst_cut_at_any_byte_of_a_frame_gives_the_replies_of_the_whole_burst():
    """Per-connection admission order is frame order, however the frames
    fall into reads: the same burst whole and cut at every byte offset
    of its third frame's header and payload."""

    async def go():
        async with _Served() as served:
            frames = [rw.frame(m) for m in _burst(0)]
            await served.read(b"".join(frames))
            whole = _told(await served.replies(6))
            # the chains on the two keys, in frame order
            assert whole == {
                (1, 1): {"hot": (None,)}, (2, 1): {"hot": ("a",)}, (3, 1): {"cold": (None,)},
                (1, 2): {"hot": ("b",)}, (3, 2): {"cold": ("c",)}, (2, 2): {"hot": ("d",)},
            }
            before = len(frames[0]) + len(frames[1])
            cuts = range(before, before + len(frames[2]) + 1)
            for n, cut in enumerate(cuts, start=1):
                data = b"".join(rw.frame(m) for m in _burst(n))
                await served.read(data[:cut])
                await served.read(data[cut:])
                assert _told(await served.replies(6), n) == whole, cut
            tallies = served.tallies()
            bursts = 1 + len(cuts)
            assert tallies["submitted"] == 6 * bursts == served.runtime.driver.executed
            assert tallies["session_decoded"] == 1 + 6 * bursts  # and the ClientHi
            assert tallies["session_plain_decoded"] == 6 * bursts  # the handshake is a pickle
            assert tallies["reply_plain_frames"] == tallies["shard_replies"] == 6 * bursts
            # each half of a cut burst completes a frame (the cut lies between
            # the end of the second frame and the end of the third)
            assert tallies["session_reads"] == 1 + 2 * len(cuts)
            assert tallies["session_decode_ms"] > 0 and tallies["session_admit_ms"] > 0

    asyncio.run(go())


@pytest.mark.parametrize("shard_count", [1, 2])
def test_a_register_between_two_submits_is_taken_in_its_place(shard_count):
    """On a sharded server a ``Register`` has nothing to set up and the
    read goes on; on a one-shard server it ends the session where it
    stands: the ``Submit`` before it is admitted, the one after is not."""

    async def go():
        async with _Served(shard_count=shard_count) as served:
            first, last = _submit(1, 1, "a"), _submit(1, 2, "b")
            await served.read(rw.frame(first) + rw.frame(Register(first.cmd)) + rw.frame(last))
            if shard_count == 2:
                got = await served.replies(2)
                assert [r.cmd_result.rifl for r in got] == [Rifl(1, 1), Rifl(1, 2)]
                assert not served.task.done()
            else:
                await asyncio.wait([served.task], timeout=5)
                assert isinstance(served.task.exception(), ProtocolError)
                assert "Register" in str(served.task.exception())
                assert served.writer.closed and not served.runtime.rifl_sessions
            return served.tallies()["submitted"], served.runtime.failure

    submitted, failure = asyncio.run(go())
    assert submitted == (2 if shard_count == 2 else 1) and failure is None


def test_an_unexpected_message_ends_the_session_after_the_submits_before_it():
    async def go():
        async with _Served() as served:
            await served.read(rw.frame(_submit(1, 1, "a")) + rw.frame(ClientHi([9])) + rw.frame(_submit(1, 2, "b")))
            await asyncio.wait([served.task], timeout=5)
            assert isinstance(served.task.exception(), ProtocolError)
            assert "unexpected message" in str(served.task.exception())
            return served.tallies()["submitted"]

    assert asyncio.run(go()) == 1


@pytest.mark.parametrize("kind", [0x00, 0x03, 0x7F, 0xFF])
def test_an_unknown_kind_byte_ends_the_session_and_closes_its_connection(kind):
    """The frames of a read before the one of no known kind are lost with
    the read (the walk raises before the admit pass), the read before it
    was admitted; the session's transport is closed, the runtime lives."""

    async def go():
        async with _Served() as served:
            await served.read(rw.frame(_submit(1, 1, "a")))
            assert [r.cmd_result.rifl for r in await served.replies(1)] == [Rifl(1, 1)]
            bad = bytes((kind,)) + rw.serialize(_submit(1, 3, "c"))[1:]
            await served.read(rw.frame(_submit(1, 2, "b")) + rw._LEN.pack(len(bad)) + bad)
            await asyncio.wait([served.task], timeout=5)
            assert isinstance(served.task.exception(), ProtocolError)
            assert f"unknown frame kind {kind:#04x}" in str(served.task.exception())
            assert served.writer.closed and not served.runtime.rifl_sessions
            return served.tallies()["submitted"], served.runtime.failure

    assert asyncio.run(go()) == (1, None)


def _old_frame(msg):
    """``msg`` as a sender before PR 39 framed it: the pickle of the
    message, which names the one callable that restores it."""
    payload = pickle.dumps(msg, protocol=pickle.HIGHEST_PROTOCOL)
    assert payload[0] == 0x80
    return rw._LEN.pack(len(payload)) + payload


def test_the_two_counters_count_the_frames_that_took_the_path():
    """A burst in today's frames, one in the frames of a sender before
    PR 39 and one with a rejection: the same replies; ``session_plain_decoded``
    grows by the first and third alone, ``reply_plain_frames`` with
    ``shard_replies`` by every executed command's reply and not by the
    rejection's, which goes through ``Rw.write``."""

    async def go():
        async with _Served() as served:
            before = served.tallies()
            assert (before["session_decoded"], before["session_plain_decoded"]) == (1, 0)
            assert (before["shard_replies"], before["reply_plain_frames"]) == (0, 0)
            await served.read(b"".join(rw.frame(m) for m in _burst(0)))
            new = _told(await served.replies(6))
            first = served.tallies()
            assert (first["session_decoded"], first["session_plain_decoded"]) == (7, 6)
            assert (first["shard_replies"], first["reply_plain_frames"]) == (6, 6)
            await served.read(b"".join(_old_frame(m) for m in _burst(1)))
            assert _told(await served.replies(6), 1) == new
            second = served.tallies()
            assert (second["session_decoded"], second["session_plain_decoded"]) == (13, 6)
            assert (second["shard_replies"], second["reply_plain_frames"]) == (12, 12)
            elsewhere = _submit(2, 9, "a", shard=1)  # not this server's shard: rejected
            await served.read(rw.frame(elsewhere) + rw.frame(_submit(1, 9, "a")))
            got = await served.replies(2)
            assert [(r.cmd_result.rifl, r.cmd_result.ready) for r in got] == [
                (Rifl(2, 9), True), (Rifl(1, 9), True)]
            assert got[0].cmd_result.results == {}
            third = served.tallies()
            assert (third["session_decoded"], third["session_plain_decoded"]) == (15, 8)
            assert (third["shard_replies"], third["reply_plain_frames"]) == (13, 13)
            # every reply on the connection, the rejection's too, is a frame of its kind
            assert third["reply_writes"] == 3 and third["replied"] == 13

    asyncio.run(go())


@pytest.mark.overload
def test_a_read_that_crosses_the_rings_bound_sheds_the_commands_past_it():
    """Seven commands in one read into a ring of four: the first four are
    admitted and executed, the last three shed with the ring at its
    bound in their reply, and none of those was tracked."""

    async def go():
        async with _Served(admission_limit=4, overload_retry_after_ms=5) as served:
            msgs = [_submit(1 + seq % 3, seq, f"k{seq}") for seq in range(1, 8)]
            await served.read(b"".join(rw.frame(m) for m in msgs))
            got = await served.replies(7)
            shed, answered = got[:3], got[3:]  # a shed is written inside the pass
            assert [(o.rifl, o.depth, o.limit, o.retry_after_ms) for o in shed] == [
                (m.cmd.rifl, 4, 4, 5) for m in msgs[4:]
            ]
            assert all(isinstance(o, Overloaded) for o in shed)
            assert [r.cmd_result.rifl for r in answered] == [m.cmd.rifl for m in msgs[:4]]
            ring = served.runtime._submit_queue
            assert (ring.sheds, ring.depth_hwm) == (3, 4)
            assert not served.session._owed and not served.runtime.rifl_sessions
            # the ring has drained: the retry of a shed command goes through
            await served.read(rw.frame(msgs[5]))
            assert [r.cmd_result.rifl for r in await served.replies(1)] == [msgs[5].cmd.rifl]
            tallies = served.tallies()
            assert (tallies["submitted"], tallies["shed_submissions"]) == (5, 3)
            assert served.runtime.driver.executed == 5

    asyncio.run(go())


def test_a_rejected_command_in_a_read_does_not_stop_the_ones_after_it():
    key_b = next(
        k for k in (f"b{i}" for i in range(1000))
        if _bucket(0, k, KEY_BUCKETS, 1) != _bucket(0, "a", KEY_BUCKETS, 1)
    )

    async def go():
        async with _Served() as served:
            wide = Submit(Command.from_keys(Rifl(2, 1), 0, {"a": (KVOp.put("x"),), key_b: (KVOp.put("y"),)}))
            elsewhere = _submit(2, 2, "a", shard=1)  # not this server's shard
            msgs = [_submit(1, 1, "a", "1"), wide, elsewhere, _submit(1, 2, "a", "2")]
            await served.read(b"".join(rw.frame(m) for m in msgs))
            got = await served.replies(4)
            # the two rejections answer inside the pass, with no key
            assert [(r.cmd_result.rifl, r.cmd_result.results) for r in got[:2]] == [
                (Rifl(2, 1), {}), (Rifl(2, 2), {}),
            ]
            assert _told(got[2:]) == {(1, 1): {"a": (None,)}, (1, 2): {"a": ("1",)}}
            assert served.tallies()["submitted"] == 2 == served.runtime.driver.executed
            assert not served.task.done()

    asyncio.run(go())


def test_a_reply_comes_only_after_execution_and_once_per_rifl():
    """The pass itself answers nothing it admits: the replies of a read
    come from the round that executed its commands, one per rifl."""

    async def go():
        async with _Served(key_width=2) as served:
            two_keys = Submit(Command(Rifl(3, 1), {0: {"x": (KVOp.put("1"),), "y": (KVOp.put("2"),)}}))
            msgs = [_submit(1, 1, "x", "0"), two_keys, _submit(2, 1, "y", "3")]
            served.reader.feed_data(b"".join(rw.frame(m) for m in msgs))
            while len(served.reader._buffer):
                await asyncio.sleep(0)
                # admitted or not yet: nothing is written before a round ran
                assert not served.writer.data or served.runtime.driver.executed
            got = await served.replies(3)
            assert sorted(r.cmd_result.rifl for r in got) == [Rifl(1, 1), Rifl(2, 1), Rifl(3, 1)]
            by_rifl = {r.cmd_result.rifl: r.cmd_result.results for r in got}
            assert by_rifl[Rifl(3, 1)] == {"x": ("0",), "y": (None,)}
            assert by_rifl[Rifl(2, 1)] == {"y": ("2",)}
            await asyncio.sleep(0.05)
            assert not served.writer.data  # and nothing twice
            assert served.runtime.replied == 3 and not served.runtime.rifl_sessions

    asyncio.run(go())


# --- a command in flight is what its frame carried (PR 50) ---


@pytest.mark.parametrize("shard_count", [1, 2])
def test_a_served_command_is_its_frames_tuple_from_the_read_to_the_reply(shard_count):
    """A read of one-key frames (and, on two shards, two-key ones) through
    ``recv_all`` and ``_admit``: what enters the ring is a ``Command`` that
    holds plain values alone, the tuple its frame unpickled to among them;
    a round later, answered, nothing has asked it for its dicts; and the
    tallies count every executed command as read off the wire."""
    import gc

    async def go():
        async with _Served(shard_count=shard_count, key_width=2, monitor=False) as served:
            runtime, ring = served.runtime, []
            submit_all = runtime.submit_all

            def entering(admitted, now_ms):
                for _dot, cmd in admitted:
                    assert type(cmd) is Command and not has_dicts(cmd)
                    assert not any(isinstance(x, (dict, KVOp, Submit)) for x in gc.get_referents(cmd))
                    ring.append(cmd)
                return submit_all(admitted, now_ms)

            runtime.submit_all = entering
            msgs = [_submit(1 + seq % 3, seq, f"k{seq % 4}", shard=seq % shard_count) for seq in range(1, 9)]
            if shard_count == 2:
                msgs.append(Submit(Command(Rifl(2, 20), {1: {"x": (KVOp.get(),)}, 0: {"y": (KVOp.get(),)}})))
                msgs.append(Submit(Command(Rifl(3, 21), {0: {"p": (KVOp.put("1"),), "q": (KVOp.put("2"),)}})))
            data = b"".join(rw.frame(m) for m in msgs)
            await served.read(data)
            got = await served.replies(len(msgs) + (1 if shard_count == 2 else 0))  # a reply a shard
            assert {r.cmd_result.rifl for r in got} == {m.cmd.rifl for m in msgs}
            assert [cmd.rifl for cmd in ring] == [m.cmd.rifl for m in msgs]
            wires = [pickle.loads(rw.serialize(m)[1:]) for m in msgs]
            assert [cmd._wire for cmd in ring] == wires
            # executed, delivered, answered: still the tuple alone
            assert not any(has_dicts(cmd) for cmd in ring)
            assert not any(isinstance(x, (dict, KVOp)) for cmd in ring for x in gc.get_referents(cmd))
            tallies = served.tallies()
            assert tallies["executed_off_wire"] == tallies["executed_in_pass"] == tallies["executed"] == len(msgs)
            assert not runtime.rifl_sessions and not served.session._owed
            return runtime.failure

    assert asyncio.run(go()) is None


def test_under_a_monitor_the_drain_asks_for_the_dicts_and_counts_nothing_off_the_wire():
    """The per-command loop (a store with a monitor) goes through
    ``Command.execute``: the dicts are built there, for the caller that asks."""

    async def go():
        async with _Served() as served:
            ring, submit_all = [], served.runtime.submit_all
            served.runtime.submit_all = lambda admitted, now_ms: (
                ring.extend(cmd for _dot, cmd in admitted), submit_all(admitted, now_ms))[1]
            await served.read(b"".join(rw.frame(m) for m in _burst(0)))
            await served.replies(6)
            assert len(ring) == 6 and all(has_dicts(cmd) for cmd in ring)
            tallies = served.tallies()
            return tallies["executed"], tallies["executed_in_pass"], tallies["executed_off_wire"]

    assert asyncio.run(go()) == (6, 0, 0)


def test_a_submit_object_is_not_what_admit_takes():
    """``_admit`` tells a command by its class: the sender's ``Submit``, which
    ``recv_all`` never hands it, is any other unexpected message."""

    async def go():
        async with _Served() as served:
            pushed = []
            served.runtime.submit_all = lambda admitted, now_ms: pushed.extend(admitted)
            with pytest.raises(ProtocolError, match="unexpected message Submit"):
                served.session._admit([_submit(1, 1, "a").cmd, _submit(1, 2, "b")])
            return [cmd.rifl for _dot, cmd in pushed]

    assert asyncio.run(go()) == [Rifl(1, 1)]  # what was admitted before it is pushed


# of tests/test_wire_codec.py's frames, the three kinds of breach the parent's restorer named
BROKEN_FRAMES = ("a_kind_code_out_of_range", "no_shard", "a_get_in_a_command_that_writes")


@pytest.mark.parametrize("name", BROKEN_FRAMES)
def test_a_frame_that_breaks_the_commands_contract_ends_the_session_as_on_the_parent(name):
    """As an unknown kind does: the walk raises what the parent's restorer
    raised (the same type, the same words), before the admit pass, so
    nothing of that read reaches ``submit_all``, the ring or the step's
    thread; the read before it was admitted and answered; the session's
    transport is closed and the runtime lives."""
    values, error, words = BROKEN[name]

    async def go():
        async with _Served(key_width=2) as served:
            pushed, submit_all = [], served.runtime.submit_all
            served.runtime.submit_all = lambda admitted, now_ms: (
                pushed.extend(cmd.rifl for _dot, cmd in admitted), submit_all(admitted, now_ms))[1]
            await served.read(rw.frame(_submit(1, 1, "a")))
            assert [r.cmd_result.rifl for r in await served.replies(1)] == [Rifl(1, 1)]
            bad = bytes((rw.KIND_SUBMIT,)) + pickle.dumps(values, protocol=pickle.HIGHEST_PROTOCOL)
            await served.read(rw.frame(_submit(1, 2, "b")) + rw._LEN.pack(len(bad)) + bad + rw.frame(_submit(1, 3, "c")))
            await asyncio.wait([served.task], timeout=5)
            exc = served.task.exception()
            assert type(exc) is error and words in str(exc)
            assert served.writer.closed and not served.runtime.rifl_sessions and not served.writer.data
            assert pushed == [Rifl(1, 1)] and len(served.runtime._submit_queue) == 0
            return served.tallies()["submitted"], served.runtime.driver.executed, served.runtime.failure

    assert asyncio.run(go()) == (1, 1, None)


def test_a_command_off_the_wire_is_refused_in_validates_words_as_one_the_constructor_made():
    """What fails ``_admit``'s quick test on the tuple goes to ``_validate``,
    which reads the command by its accessors: the same words for both births."""

    async def go():
        async with _Served(shard_count=2, key_width=2) as served:
            session = served.session
            shapes = [
                {5: {"a": (KVOp.put("1"),)}},  # a shard the server does not have
                {0: {"a": (KVOp.get(),)}, 3: {"b": (KVOp.get(),)}},  # one of two
                {0: {"a": (KVOp.put("1"),), "b": (KVOp.put("2"),)}, 1: {"c": (KVOp.put("3"),)}},  # three buckets
                {0: {}},  # no key
            ]
            said = []
            for number, shape in enumerate(shapes, start=1):
                built = Command(Rifl(4, number), shape)
                restored = rw.deserialize(rw.serialize(Submit(built))).cmd
                assert session._validate(restored) == session._validate(built) is not None
                said.append(session._validate(built))
            rejected = []
            session._reject = lambda cmd, why: rejected.append(why)
            data = b"".join(rw.frame(Submit(Command(Rifl(4, n), shape))) for n, shape in enumerate(shapes, start=1))
            await served.read(data + rw.frame(_submit(1, 1, "a")))
            assert rejected == said and len(set(said)) == 4
            assert [r.cmd_result.rifl for r in await served.replies(1)] == [Rifl(1, 1)]
            return served.tallies()["submitted"]

    assert asyncio.run(go()) == 1
