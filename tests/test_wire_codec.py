"""The client plane's wire codec (run/rw.py, PR 39): a ``Submit`` and a
``ToClient`` go as a kind byte and the pickle of their plain values, every
other message as its pickle, and a receiver reads either form by the first
byte: through ``serialize`` / ``deserialize`` and through ``Rw`` over a TCP
connection.  The session's side of it (the counters in the snapshot, a
session closed on a kind it does not know) is in tests/test_session_reads.py.
"""

import asyncio
import pickle

import pytest

from fantoch_tpu.core import Command, KVOp, Rifl
from fantoch_tpu.core.command import CommandResult
from fantoch_tpu.run import rw
from fantoch_tpu.run.prelude import (
    ClientHi,
    ClientHiAck,
    Overloaded,
    Register,
    Submit,
    ToClient,
    Unregister,
)

RIFL = Rifl(2**40 + 7, 2**33)
VALUE = "v" * 100  # the cells' payload

COMMANDS = {
    "one_key_put": {0: {"999999": (KVOp.put(VALUE),)}},
    "one_key_get": {0: {"k": (KVOp.get(),)}},
    "one_key_delete": {3: {"k": (KVOp.delete(),)}},
    "non_ascii_value": {0: {"ключ": (KVOp.put("é鍵🔑" * 9),)}},
    "a_1000_byte_value": {0: {"user4052": (KVOp.put("r" * 1000),)}},
    "two_keys_one_shard": {0: {"b": (KVOp.put("1"),), "a": (KVOp.put("2"),)}},
    "two_keys_two_shards": {2: {"905": (KVOp.put(VALUE),)}, 1: {"17": (KVOp.put(VALUE),)}},
}

# key count, then the partials: what a reply carries
RESULTS = {
    "a_put_with_a_previous_value": (1, {"999999": (VALUE,)}),
    "a_none_result": (1, {"k": (None,)}),
    "a_non_ascii_value": (1, {"ключ": ("é鍵🔑" * 9,)}),
    "a_1000_byte_record": (1, {"user4052": ("r" * 1000,)}),
    "two_keys_one_shard": (2, {"b": ("1",), "a": (None,)}),
    "one_shard_of_two": (1, {"905": (VALUE,)}),
    "a_zero_key_rejection": (0, {}),
}

# everything else on the client plane, and a plain tuple: pickled as before
OTHERS = {
    "a_plain_tuple": (1, 2, "three", (4, None)),
    "a_tuple_that_looks_like_a_reply": (RIFL[0], RIFL[1], 1, {"k": ("v",)}),
    "client_hi": ClientHi([1, 2, 3]),
    "client_hi_ack": ClientHiAck(),
    "overloaded": Overloaded(RIFL, 5, depth=4, limit=4),
    "register": Register(Command(RIFL, COMMANDS["two_keys_two_shards"])),
    "unregister": Unregister(RIFL),
    "none": None,
    "bytes": b"\x01\x02 not a frame of a kind",
}


def _submit(name):
    return Submit(Command(RIFL, COMMANDS[name]))


def _to_client(name):
    key_count, partials = RESULTS[name]
    result = CommandResult(RIFL, key_count)
    for key, values in partials.items():
        result.add_partial(key, values)
    return ToClient(result)


def _same_submit(got, sent):
    assert type(got) is Submit and got == sent
    _same_command(got.cmd, sent)


def _same_command(got, sent):
    """``got``: the command of the ``Submit`` ``sent``, as ``deserialize`` and
    ``recv`` give it inside a ``Submit`` and ``recv_all`` bare."""
    assert type(got) is Command and got == sent.cmd
    assert type(got.rifl) is Rifl
    assert list(got.all_keys()) == list(sent.cmd.all_keys())  # the order of execution
    assert (got.read_only, got.total_key_count, got.single_key()) == (
        sent.cmd.read_only, sent.cmd.total_key_count, sent.cmd.single_key())


def _as_recv_all_gives(msgs):
    """A ``Submit`` comes out of ``recv_all`` as its command, every other
    message as itself."""
    return [m.cmd if type(m) is Submit else m for m in msgs]


def _fields(to_client):
    result = to_client.cmd_result
    return result.rifl, result._key_count, result.results, list(result.results), result.ready


def _same_to_client(got, sent):
    assert type(got) is ToClient and type(got.cmd_result) is CommandResult
    assert type(got.cmd_result.rifl) is Rifl and _fields(got) == _fields(sent)


# --- serialize / deserialize ---


@pytest.mark.parametrize("name", sorted(COMMANDS))
def test_a_submit_goes_as_its_kind_and_its_commands_values(name):
    sent = _submit(name)
    payload = rw.serialize(sent)
    assert payload[0] == rw.KIND_SUBMIT < 0x80
    # the rest is the pickle of exactly what the command reduces to: every
    # shape takes the path, and the frame names no callable
    assert pickle.loads(payload[1:]) == sent.cmd.__reduce__()[1]
    for word in (b"fantoch_tpu", b"_submit", b"_restore", b"Command", b"KVOp"):
        assert word not in payload, word
    _same_submit(rw.deserialize(payload), sent)
    _same_submit(rw.deserialize(memoryview(payload)), sent)
    _same_submit(rw.deserialize(bytearray(payload)), sent)
    assert rw.frame(sent) == rw._LEN.pack(len(payload)) + payload


@pytest.mark.parametrize("name", sorted(RESULTS))
def test_a_to_client_goes_as_its_kind_and_its_results_four_values(name):
    sent = _to_client(name)
    payload = rw.serialize(sent)
    assert payload[0] == rw.KIND_TO_CLIENT < 0x80
    key_count, partials = RESULTS[name]
    assert pickle.loads(payload[1:]) == (RIFL[0], RIFL[1], key_count, partials)
    for word in (b"fantoch_tpu", b"_to_client", b"_restore", b"CommandResult"):
        assert word not in payload, word
    _same_to_client(rw.deserialize(payload), sent)
    # what the reply stage writes without building the ToClient
    assert rw.reply_frame(sent.cmd_result) == rw.frame(sent) == rw._LEN.pack(len(payload)) + payload


def test_the_cells_frames_on_the_sandbox():
    """A one-key ``Put`` of 100 bytes, length prefix included: 183 -> 144
    bytes on the way in and 189 -> 147 on the way out (ISSUE 39's table
    has 145 and 148, a byte of rifl more)."""
    put = Submit(Command.from_single(Rifl(8191, 123456), 0, "999999", KVOp.put(VALUE)))
    reply = CommandResult(Rifl(8191, 123456), 1)
    reply.add_partial("999999", (VALUE,))
    assert (len(rw.frame(put)), len(PARENTS_SUBMIT) + 4) == (144, 183)
    assert (len(rw.reply_frame(reply)), len(PARENTS_TO_CLIENT) + 4) == (147, 189)


@pytest.mark.parametrize("name", sorted(OTHERS))
def test_every_other_message_is_pickled_as_before_and_comes_back_unchanged(name):
    sent = OTHERS[name]
    payload = rw.serialize(sent)
    assert payload == pickle.dumps(sent, protocol=pickle.HIGHEST_PROTOCOL) and payload[0] == 0x80
    back = rw.deserialize(payload)
    assert type(back) is type(sent) and back == sent


@pytest.mark.parametrize("kind", [0x00, 0x03, 0x28, 0x7F, 0x81, 0xFF])
def test_an_unknown_kind_byte_is_a_protocol_error(kind):
    payload = bytes((kind,)) + rw.serialize(_submit("one_key_put"))[1:]
    with pytest.raises(rw.ProtocolError, match="unknown frame kind"):
        rw.deserialize(payload)


def test_an_empty_payload_is_a_protocol_error():
    with pytest.raises(rw.ProtocolError, match="empty frame"):
        rw.deserialize(b"")


# --- a frame of the kind whose values break the command's contract ---

# what the parent's restorer raised by building (KINDS[code] of a KVOp, the
# constructor's two asserts), raised now by the check of the tuple in the walk
BROKEN = {
    "a_kind_code_out_of_range": (
        (7, 9, 0, "k", 3, None), IndexError, "tuple index out of range"),
    "a_kind_code_out_of_range_among_several_keys": (
        (7, 9, ((0, (("a", ((1, "x"),)), ("b", ((7, None),)))),)), IndexError, "tuple index out of range"),
    "a_kind_code_that_is_no_number": (
        (7, 9, 0, "k", "Put", "x"), TypeError, "tuple indices must be integers or slices, not str"),
    "no_shard": (
        (7, 9, ()), AssertionError, "commands must have at least one shard"),
    "a_get_in_a_command_that_writes": (
        (7, 9, ((0, (("a", ((0, None),)),)), (1, (("b", ((1, "x"),)),)))), AssertionError,
        "non-read-only commands cannot contain Get operations"),
    "a_get_and_a_put_on_one_key": (
        (7, 9, ((0, (("a", ((1, "x"), (0, None))),)),)), AssertionError,
        "non-read-only commands cannot contain Get operations"),
    "two_values": ((7, 9), TypeError, "a command's values are"),
    "seven_values": ((7, 9, 0, "k", 1, "x", None), TypeError, "a command's values are"),
    "a_flat_form_without_its_key": ((7, 9, 0, None, 1, "x"), TypeError, "a command's values are"),
}


def _broken_payload(name):
    return bytes((rw.KIND_SUBMIT,)) + pickle.dumps(BROKEN[name][0], protocol=pickle.HIGHEST_PROTOCOL)


@pytest.mark.parametrize("by", ["deserialize", "recv", "recv_all", "pickle"])
@pytest.mark.parametrize("name", sorted(BROKEN))
def test_a_frame_that_breaks_the_commands_contract_raises_in_the_walk_as_on_the_parent(name, by):
    """The exception's type and words are the parent's (read off a copy of
    its tree) for a code outside ``KINDS``, no shard and a ``Get`` among
    writes; a tuple of no command's length is a ``TypeError`` as there."""
    _values, error, words = BROKEN[name]
    payload = _broken_payload(name)
    good = rw.frame(_submit("one_key_put"))

    async def send(client):
        client.write_frames(good + rw._LEN.pack(len(payload)) + payload + good)
        await client.flush()

    async def receive(served):
        if by == "recv":
            _same_submit(await served.recv(), _submit("one_key_put"))
            await served.recv()
        else:
            await _recv_n(served, 3)

    with pytest.raises(error, match=words):
        if by == "deserialize":
            rw.deserialize(payload)
        elif by == "pickle":  # the command alone, under its restorer's name
            from fantoch_tpu.core.command import _restore_command

            _restore_command(*_values)
        else:
            _over_tcp(send, receive)


# --- the form before PR 39: a pickle that names one callable ---

# what PR 38's ``serialize`` gave (run from a copy of that tree): a one-key
# Put of 100 bytes, a command over two shards, a reply with a previous value
PARENTS_SUBMIT = bytes.fromhex(
    "800595a8000000000000008c1766616e746f63685f7470752e72756e2e7072656c756465948c075f7375626d6974"
    "949394284dff1f4a40e201004b008c06393939393939944b018c64" + "76" * 100 + "94749452942e")
PARENTS_TWO_SHARDS = bytes.fromhex(
    "8005955e000000000000008c1766616e746f63685f7470752e72756e2e7072656c756465948c075f7375626d6974"
    "9493944b074b094b028c03393035944b018c017894869485948694859486944b018c023137944b01680486948594"
    "8694859486948694879452942e")
PARENTS_TO_CLIENT = bytes.fromhex(
    "800595ae000000000000008c1766616e746f63685f7470752e72756e2e7072656c756465948c0a5f746f5f636c69"
    "656e74949394284dff1f4a40e201004b017d948c06393939393939948c64" + "70" * 100 + "94859473749452942e")


def _parents():
    put = Submit(Command.from_single(Rifl(8191, 123456), 0, "999999", KVOp.put(VALUE)))
    two = Submit(Command(Rifl(7, 9), {2: {"905": (KVOp.put("x"),)}, 1: {"17": (KVOp.put("x"),)}}))
    result = CommandResult(Rifl(8191, 123456), 1)
    result.add_partial("999999", ("p" * 100,))
    return [(PARENTS_SUBMIT, put), (PARENTS_TWO_SHARDS, two), (PARENTS_TO_CLIENT, ToClient(result))]


@pytest.mark.parametrize("at", range(3), ids=["submit", "two_shard_submit", "to_client"])
def test_a_frame_the_parents_serialize_made_still_decodes(at):
    payload, sent = _parents()[at]
    assert payload[0] == 0x80 and (b"_submit" in payload or b"_to_client" in payload)
    same = _same_submit if isinstance(sent, Submit) else _same_to_client
    same(rw.deserialize(payload), sent)
    # the generic pickle of the two messages is that form still (their
    # ``__reduce__`` stays: a peer message that carries one, a deepcopy)
    assert pickle.dumps(sent, protocol=pickle.HIGHEST_PROTOCOL) == payload
    same(pickle.loads(payload), sent)


@pytest.mark.parametrize("name", sorted(COMMANDS))
def test_a_submit_pickled_with_its_callable_decodes_to_an_equal_object(name):
    sent = _submit(name)
    old = pickle.dumps(sent, protocol=pickle.HIGHEST_PROTOCOL)
    assert old[0] == 0x80 and b"_submit" in old
    _same_submit(rw.deserialize(old), sent)
    assert rw.deserialize(old) == rw.deserialize(rw.serialize(sent))


@pytest.mark.parametrize("name", sorted(RESULTS))
def test_a_to_client_pickled_with_its_callable_decodes_to_an_equal_object(name):
    sent = _to_client(name)
    old = pickle.dumps(sent, protocol=pickle.HIGHEST_PROTOCOL)
    assert old[0] == 0x80 and b"_to_client" in old
    _same_to_client(rw.deserialize(old), sent)


# --- through Rw, over a TCP connection ---


def _over_tcp(send, receive):
    """``receive(server side's Rw)`` while ``send(client side's Rw)`` writes
    to it, over a connection on localhost; returns what ``receive`` does
    and the server side's tally."""

    async def go():
        tally = [0, 0, 0, 0, 0, 0, 0]
        accepted = asyncio.get_running_loop().create_future()

        def on_connect(reader, writer):
            accepted.set_result(rw.Rw(reader, writer, decode_tally=tally))

        server = await asyncio.start_server(on_connect, "127.0.0.1", 0)
        client = await rw.connect_with_retry(server.sockets[0].getsockname()[:2])
        served = await asyncio.wait_for(accepted, 5)
        try:
            await send(client)
            return await asyncio.wait_for(receive(served), 5), tally
        finally:
            client.close()
            served.close()
            server.close()
            await server.wait_closed()

    return asyncio.run(go())


async def _recv_n(conn, n):
    got = []
    while len(got) < n:
        msgs = await conn.recv_all()
        assert msgs is not None
        got.extend(msgs)
    return got


@pytest.mark.parametrize("name", sorted(COMMANDS))
def test_a_submit_round_trips_through_rw_by_recv_and_by_recv_all(name):
    sent = _submit(name)

    async def send(client):
        await client.send(sent)

    one, tally = _over_tcp(send, lambda served: served.recv())
    _same_submit(one, sent)
    assert tally[1] == 1 and tally[6] == 1  # decoded through its kind byte
    # the server's way in: the command, with no Submit around it
    (got,), tally = _over_tcp(send, lambda served: _recv_n(served, 1))
    _same_command(got, sent)
    assert tally[1:3] == [1, 1] and tally[6] == 1


@pytest.mark.parametrize("name", sorted(RESULTS))
def test_a_to_client_round_trips_through_rw_as_written_and_as_a_reply_frame(name):
    sent = _to_client(name)

    async def send(client):
        client.write(sent)
        client.write_frames(rw.reply_frame(sent.cmd_result))
        await client.flush()

    (written, framed), tally = _over_tcp(send, lambda served: _recv_n(served, 2))
    _same_to_client(written, sent)
    _same_to_client(framed, sent)
    assert tally[1] == 2 and tally[6] == 2

    async def recv_twice(served):
        return await served.recv(), await served.recv()

    (written, framed), tally = _over_tcp(send, recv_twice)
    _same_to_client(written, sent)
    _same_to_client(framed, sent)
    assert tally[1] == 2 and tally[6] == 2


def test_a_connection_carries_both_forms_and_counts_the_ones_of_a_kind():
    """An old sender's frames, a new sender's and the handshake's on one
    connection: all decode, in order, and only the new form counts as
    plain."""
    olds = [payload for payload, _ in _parents()]
    news = [_submit("one_key_put"), _to_client("a_none_result"), _submit("two_keys_two_shards")]
    others = [OTHERS["client_hi"], OTHERS["a_plain_tuple"], OTHERS["overloaded"]]

    async def send(client):
        client.write(others[0])
        for payload in olds:
            client.write_frames(rw._LEN.pack(len(payload)) + payload)
        for msg in news + others[1:]:
            client.write(msg)
        await client.flush()

    got, tally = _over_tcp(send, lambda served: _recv_n(served, 9))
    sent = _as_recv_all_gives(others[:1] + [msg for _, msg in _parents()] + news + others[1:])
    assert len(got) == len(sent) == 9 and sum(type(msg) is Command for msg in sent) == 4
    for back, msg in zip(got, sent):
        if isinstance(msg, ToClient):  # a result compares by identity
            _same_to_client(back, msg)
        else:
            assert type(back) is type(msg) and back == msg
    assert tally[1] == 9 and tally[6] == 3


@pytest.mark.parametrize("by", ["recv", "recv_all"])
def test_an_unknown_kind_on_a_connection_raises_where_the_frame_stands(by):
    good = rw.frame(_submit("one_key_put"))
    bad = rw._LEN.pack(3) + b"\x07ab"

    async def send(client):
        client.write_frames(good + bad + good)
        await client.flush()

    async def receive(served):
        if by == "recv":
            _same_submit(await served.recv(), _submit("one_key_put"))
            await served.recv()
        else:
            await _recv_n(served, 3)

    with pytest.raises(rw.ProtocolError, match="unknown frame kind 0x07"):
        _over_tcp(send, receive)


def test_an_empty_frame_on_a_connection_is_a_protocol_error():
    async def send(client):
        client.write_frames(rw._LEN.pack(0) + rw.frame(_submit("one_key_put")))
        await client.flush()

    with pytest.raises(rw.ProtocolError, match="empty frame"):
        _over_tcp(send, lambda served: _recv_n(served, 1))
    with pytest.raises(rw.ProtocolError, match="empty frame"):
        _over_tcp(send, lambda served: served.recv())


# --- recv_all: the stream cut at every byte ---


class _NoWriter:
    transport = None

    def get_extra_info(self, name):
        return None


def _fed(reads):
    """What ``recv_all`` returns call by call when the stream is fed
    ``reads`` one at a time, the tail it keeps after each, and the tally."""

    async def go():
        reader = asyncio.StreamReader()
        tally = [0, 0, 0, 0, 0, 0, 0]
        conn = rw.Rw(reader, _NoWriter(), decode_tally=tally)
        out, tails = [], []
        pending = asyncio.ensure_future(conn.recv_all())
        for data in reads:
            reader.feed_data(data)
            await asyncio.sleep(0)
            if pending.done():
                out.append(pending.result())
                pending = asyncio.ensure_future(conn.recv_all())
                await asyncio.sleep(0)
            tails.append(bytes(conn._tail))
        pending.cancel()
        return out, tails, tally

    return asyncio.run(go())


THREE = [_submit("one_key_put"), OTHERS["client_hi"], _submit("two_keys_two_shards")]


@pytest.mark.parametrize("form", ["new", "old"])
def test_a_three_frame_read_cut_at_every_byte_gives_the_three_messages_and_the_right_tail(form):
    if form == "new":
        frames = [rw.frame(m) for m in THREE]
    else:
        frames = [rw._LEN.pack(len(p)) + p
                  for p in (pickle.dumps(m, protocol=pickle.HIGHEST_PROTOCOL) for m in THREE)]
    whole = b"".join(frames)
    ends = [len(frames[0]), len(frames[0]) + len(frames[1]), len(whole)]
    three = _as_recv_all_gives(THREE)
    assert _fed([whole])[0] == [three] and [type(m) for m in three] == [Command, ClientHi, Command]
    for cut in range(1, len(whole)):
        out, tails, tally = _fed([whole[:cut], whole[cut:]])
        done = sum(end <= cut for end in ends)  # frames whole in the first read
        assert [m for msgs in out for m in msgs] == three, cut
        assert [len(msgs) for msgs in out] == [n for n in (done, 3 - done) if n], cut
        # the first read leaves what follows its last whole frame, the second nothing
        assert tails == [whole[([0] + ends)[done]:cut], b""], cut
        assert tally[1] == 3 and tally[2] == len(out)
        assert tally[6] == (2 if form == "new" else 0)
