"""Multi-shard, multi-key commands and their (partially aggregated) results.

Reference: fantoch/src/command.rs:12-262.  A command is a map
``shard -> key -> op`` identified by a Rifl; conflict = key intersection;
results aggregate per-key op results until all keys have reported.

A command has two forms of its ops, and is born with one: the dicts
``shard -> key -> (KVOp, ...)`` its constructor is given, or the tuple of
plain values its frame carried (``Command.__reduce__``).  Each is built from
the other at its first use and kept.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Tuple, TYPE_CHECKING

from fantoch_tpu.core.ids import Rifl, ShardId
from fantoch_tpu.core.kvs import KINDS, KVOp, KVOpResult, Key, KVStore

if TYPE_CHECKING:
    from fantoch_tpu.executor.base import ExecutorResult


# how many values the flat form of a command's ``_wire`` has (one shard, one
# key, one op); the nested form has three
FLAT = 6


class Command:
    """A client command spanning one or more shards (fantoch/src/command.rs:12-170).

    ``_shard_to_ops`` is the dict form of its ops, ``shard -> key -> (KVOp,
    ...)``; ``_wire`` the plain values it goes on the wire as: ``(source,
    sequence, shard, key, kind code, value)`` where it has one shard, one key
    and one op (``FLAT`` values), ``(source, sequence, ((shard, ((key,
    ((kind code, value), ...)), ...)), ...))`` otherwise, shards and keys in
    the dicts' order, a kind's code its index in ``KINDS``.  A command made
    by the constructor, ``from_single`` or ``from_keys`` is born with the
    dicts, one restored from a frame with the frame's own tuple
    (``_off_wire``), and the slot of the other form stays unset until
    something reads it (``__getattr__``: the dicts of a command off the wire
    are built for the caller that asks, which on the served path nobody
    does).  Every answer is the same whichever form a command was born
    with."""

    __slots__ = (
        "_rifl", "_wire", "_shard_to_ops", "_read_only", "_total_key_count", "_off_wire",
    )

    def __init__(self, rifl: Rifl, shard_to_ops: Dict[ShardId, Dict[Key, Tuple[KVOp, ...]]]):
        assert shard_to_ops, "commands must have at least one shard"
        self._rifl = rifl
        self._shard_to_ops = shard_to_ops
        self._off_wire = False
        # read_only inference (fantoch/src/command.rs:28-36): a command is
        # read-only iff every op on every key is a read.  One pass over the
        # ops — this constructor sits on the client submit path, so no
        # intermediate list / multiple scans.
        reads = 0
        writes = 0
        total = 0
        for ops in shard_to_ops.values():
            total += len(ops)
            for key_ops in ops.values():
                for op in key_ops:
                    if op.is_read:
                        reads += 1
                    else:
                        writes += 1
        self._read_only = writes == 0
        # reference invariant (fantoch/src/command.rs:32-41): either all ops
        # are reads or none are — mixed commands break read-only fast paths
        assert reads == 0 or writes == 0, (
            "non-read-only commands cannot contain Get operations"
        )
        self._total_key_count = total

    @staticmethod
    def from_single(rifl: Rifl, shard_id: ShardId, key: Key, op: KVOp) -> "Command":
        # the dominant wire shape (one shard, one key, one op): the general
        # scan above degenerates to constants, so skip it — single-op
        # commands cannot violate the mixed-ops invariant
        cmd = Command.__new__(Command)
        cmd._rifl = rifl
        cmd._shard_to_ops = {shard_id: {key: (op,)}}
        cmd._read_only = op.is_read
        cmd._total_key_count = 1
        cmd._off_wire = False
        return cmd

    def __getattr__(self, name: str):
        # only an unset slot comes here: the form the command was not born
        # with, built from the other (read through its slot: both unset is
        # an AttributeError, not a loop) and kept
        if name == "_shard_to_ops":
            built = self._shard_to_ops = _dicts_of(_WIRE_SLOT.__get__(self))
        elif name == "_wire":
            built = self._wire = _wire_of(self._rifl, _DICTS_SLOT.__get__(self))
        else:
            raise AttributeError(name)
        return built

    @staticmethod
    def from_keys(rifl: Rifl, shard_id: ShardId, key_ops: Dict[Key, Tuple[KVOp, ...]]) -> "Command":
        return Command(rifl, {shard_id: dict(key_ops)})

    @property
    def rifl(self) -> Rifl:
        return self._rifl

    @property
    def read_only(self) -> bool:
        return self._read_only

    @property
    def shard_count(self) -> int:
        return len(self._shard_to_ops)

    def shards(self) -> Iterator[ShardId]:
        return iter(self._shard_to_ops.keys())

    def replicated_by(self, shard_id: ShardId) -> bool:
        return shard_id in self._shard_to_ops

    def multi_shard(self) -> bool:
        return len(self._shard_to_ops) > 1

    def key_count(self, shard_id: ShardId) -> int:
        """Number of keys accessed on `shard_id` (fantoch/src/command.rs:88)."""
        return len(self._shard_to_ops.get(shard_id, {}))

    @property
    def total_key_count(self) -> int:
        return self._total_key_count

    def keys(self, shard_id: ShardId) -> Iterator[Key]:
        """Keys accessed on a given shard (fantoch/src/command.rs:97-103)."""
        return iter(self._shard_to_ops.get(shard_id, {}).keys())

    def iter_ops(self, shard_id: ShardId) -> Iterator[Tuple[Key, Tuple[KVOp, ...]]]:
        """(key, ops) pairs for one shard (fantoch/src/command.rs into_iter)."""
        return iter(self._shard_to_ops.get(shard_id, {}).items())

    def all_keys(self) -> Iterator[Tuple[ShardId, Key]]:
        for shard_id, ops in self._shard_to_ops.items():
            for key in ops:
                yield shard_id, key

    def single_key(self) -> Optional[Tuple[ShardId, Key]]:
        """``(shard, key)`` of a command of one key on one shard (the
        dominant shape: its callers skip their general scans), else
        None."""
        if self._total_key_count == 1 and len(self._shard_to_ops) == 1:
            for shard_id, ops in self._shard_to_ops.items():
                for key in ops:
                    return shard_id, key
        return None

    def conflicts(self, other: "Command") -> bool:
        """Key-intersection conflict check (fantoch/src/command.rs:141-147)."""
        for shard_id, ops in self._shard_to_ops.items():
            other_ops = other._shard_to_ops.get(shard_id)
            if other_ops and not ops.keys().isdisjoint(other_ops.keys()):
                return True
        return False

    def execute(self, shard_id: ShardId, store: KVStore) -> List["ExecutorResult"]:
        """Execute this command's ops for `shard_id`, returning per-key results.

        Reference: fantoch/src/command.rs:114-127.  Returns a list (not a
        generator): this is the serving hot path — one call per executed
        command — and the dominant shape is a single key with a single op,
        which skips the genexpr entirely.
        """
        ExecutorResult = _ExecutorResult or _bind_executor_result()
        rifl = self._rifl
        out = []
        for key, key_ops in self._shard_to_ops.get(shard_id, {}).items():
            if len(key_ops) == 1:
                results = (store.execute(key, key_ops[0], rifl),)
            else:
                results = tuple(store.execute(key, op, rifl) for op in key_ops)
            out.append(ExecutorResult(rifl, key, results))
        return out

    def __eq__(self, other: object) -> bool:
        return (
            isinstance(other, Command)
            and self._rifl == other._rifl
            and self._shard_to_ops == other._shard_to_ops
        )

    def __hash__(self) -> int:
        return hash(self._rifl)

    def __reduce__(self):
        # plain values on the wire (the class's docstring): a command off
        # the wire gives its frame's own tuple back
        return _restore_command, self._wire

    def __repr__(self) -> str:
        keys = {s: sorted(ops) for s, ops in self._shard_to_ops.items()}
        return f"Command({self._rifl}, {keys})"


# ``Command.execute``'s result class: executor/base.py is imported by a
# package that imports this module, so it is bound at the first execute and
# not by an ``import`` statement every call
_ExecutorResult = None


def _bind_executor_result():
    global _ExecutorResult
    from fantoch_tpu.executor.base import ExecutorResult

    _ExecutorResult = ExecutorResult
    return ExecutorResult


_KIND_CODE = {kind: code for code, kind in enumerate(KINDS)}
_tuple_new = tuple.__new__
_new_command = Command.__new__
_WIRE_SLOT = Command.__dict__["_wire"]
_DICTS_SLOT = Command.__dict__["_shard_to_ops"]


def _wire_of(rifl: Rifl, shard_to_ops: Dict[ShardId, Dict[Key, Tuple[KVOp, ...]]]) -> tuple:
    """The wire form of a command of ``shard_to_ops`` (``Command``'s
    docstring)."""
    if len(shard_to_ops) == 1:
        for shard_id, ops in shard_to_ops.items():
            if len(ops) == 1:
                for key, key_ops in ops.items():
                    if len(key_ops) == 1:
                        op = key_ops[0]
                        return (rifl[0], rifl[1], shard_id, key, _kind_code(op), op.value)
    return (
        rifl[0],
        rifl[1],
        tuple(
            (
                shard_id,
                tuple(
                    (key, tuple((_kind_code(op), op.value) for op in key_ops))
                    for key, key_ops in ops.items()
                ),
            )
            for shard_id, ops in shard_to_ops.items()
        ),
    )


def _kind_code(op: KVOp) -> int:
    code = _KIND_CODE.get(op.kind)
    if code is None:
        # in ``KVStore._do_execute``'s words: the store's one pass reads
        # the code where the plain route reads the kind
        raise AssertionError(f"unknown op kind {op.kind}")
    return code


def _dicts_of(wire: tuple) -> Dict[ShardId, Dict[Key, Tuple[KVOp, ...]]]:
    """The dict form of a command of the wire form ``wire``.  A shard or a
    key named twice keeps its last, as a dict does."""
    if len(wire) == FLAT:
        return {wire[2]: {wire[3]: (KVOp(KINDS[wire[4]], wire[5]),)}}
    return {
        shard_id: {
            key: tuple([KVOp(KINDS[code], value) for code, value in key_ops])
            for key, key_ops in keys
        }
        for shard_id, keys in wire[2]
    }


def _restore_command(*wire) -> Command:
    """Unpickle a :class:`Command` from the values its ``__reduce__``
    carries."""
    return _off_wire(wire)


def _off_wire(wire: tuple) -> Command:
    """The :class:`Command` of the tuple a frame unpickled to (``Command``'s
    docstring has the two forms), which it keeps as it is: no ``KVOp``, no
    dict.  What the constructor checks is checked here, on the tuple, with
    a kind's code: a code outside ``KINDS`` (an ``IndexError``), no shard,
    a ``Get`` in a command that writes."""
    cmd = _new_command(Command)
    # tuple.__new__: Rifl's own __new__ is a Python-level call, and this
    # runs once a frame on the server's loop
    cmd._rifl = _tuple_new(Rifl, wire[:2])
    if len(wire) == FLAT and wire[3] is not None:
        kind = wire[4]
        KINDS[kind]
        # Command.from_single (kind code 0 is the one read)
        cmd._read_only = not kind
        cmd._total_key_count = 1
    elif len(wire) == 3:
        # the constructor's scan
        nested = wire[2]
        if not nested:
            raise AssertionError("commands must have at least one shard")
        ops = writes = total = 0
        # a name twice: two shards (the shape of most commands of several
        # keys) by one comparison, more by their dict
        if len(nested) == 2:
            twice = nested[0][0] == nested[1][0]
        else:
            twice = len(nested) > 2 and len(dict(nested)) != len(nested)
        for _shard_id, keys in nested:
            for _key, key_ops in keys:
                for code, _ in key_ops:
                    KINDS[code]
                    if code:
                        writes += 1
                ops += len(key_ops)
            if len(keys) > 1 and len(dict(keys)) != len(keys):
                twice = True
            total += len(keys)
        if writes and writes != ops:
            raise AssertionError("non-read-only commands cannot contain Get operations")
        if twice:
            # a shard or a key named twice, which no ``__reduce__`` gives:
            # the command is what the dicts make of it
            shard_to_ops = _dicts_of(wire)
            wire = _wire_of(cmd._rifl, shard_to_ops)
            total = sum(map(len, shard_to_ops.values()))
        cmd._read_only = not writes
        cmd._total_key_count = total
    else:
        raise TypeError(
            "a command's values are (source, sequence, shard, key, kind, value) "
            f"or (source, sequence, shards), not {wire!r}"
        )
    cmd._wire = wire
    cmd._off_wire = True
    return cmd


class CommandResult:
    """Partial aggregation of per-key results for one shard's portion.

    Reference: fantoch/src/command.rs:173-216.  Ready when `key_count` keys
    have reported.
    """

    __slots__ = ("_rifl", "_key_count", "_results")

    def __init__(self, rifl: Rifl, key_count: int):
        self._rifl = rifl
        self._key_count = key_count
        self._results: Dict[Key, Tuple[KVOpResult, ...]] = {}

    @property
    def rifl(self) -> Rifl:
        return self._rifl

    def add_partial(self, key: Key, result: Tuple[KVOpResult, ...]) -> bool:
        """Add one key's results; returns True once the result is ready."""
        assert key not in self._results, f"duplicate partial result for {key}"
        self._results[key] = result
        return self.ready

    def increment_key_count(self, by: int = 1) -> None:
        """Raise the number of expected partials (fantoch/src/command.rs:203)."""
        self._key_count += by

    @property
    def ready(self) -> bool:
        return len(self._results) == self._key_count

    @property
    def results(self) -> Dict[Key, Tuple[KVOpResult, ...]]:
        return self._results

    def merge(self, other: "CommandResult") -> None:
        """Merge results from another shard (used by ShardsPending aggregation)."""
        assert self._rifl == other._rifl
        self._key_count += other._key_count
        for key, res in other._results.items():
            assert key not in self._results
            self._results[key] = res

    def __reduce__(self):
        # plain values on the wire: no class path per field, no BUILD
        rifl = self._rifl
        return _restore_result, (rifl[0], rifl[1], self._key_count, self._results)

    def __repr__(self) -> str:
        return f"CommandResult({self._rifl}, {len(self._results)}/{self._key_count})"


def _restore_result(
    source: int, sequence: int, key_count: int, results: Dict[Key, Tuple[KVOpResult, ...]]
) -> CommandResult:
    """Unpickle a :class:`CommandResult` from the four values its
    ``__reduce__`` carries."""
    result = CommandResult(Rifl(source, sequence), key_count)
    result._results = results
    return result
