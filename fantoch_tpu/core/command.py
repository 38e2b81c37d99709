"""Multi-shard, multi-key commands and their (partially aggregated) results.

Reference: fantoch/src/command.rs:12-262.  A command is a map
``shard -> key -> op`` identified by a Rifl; conflict = key intersection;
results aggregate per-key op results until all keys have reported.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Tuple, TYPE_CHECKING

from fantoch_tpu.core.ids import Rifl, ShardId
from fantoch_tpu.core.kvs import KINDS, KVOp, KVOpResult, Key, KVStore

if TYPE_CHECKING:
    from fantoch_tpu.executor.base import ExecutorResult


class Command:
    """A client command spanning one or more shards (fantoch/src/command.rs:12-170)."""

    __slots__ = ("_rifl", "_shard_to_ops", "_read_only", "_total_key_count")

    def __init__(self, rifl: Rifl, shard_to_ops: Dict[ShardId, Dict[Key, Tuple[KVOp, ...]]]):
        assert shard_to_ops, "commands must have at least one shard"
        self._rifl = rifl
        self._shard_to_ops = shard_to_ops
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
        return cmd

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
        # plain values on the wire: the rifl's two numbers, then the
        # command's shape, kinds as their index in ``KINDS``.  One
        # shard, one key, one op (what the command itself shows) goes
        # flat; every other shape as nested tuples, shard -> key -> ops
        # in the dicts' order.
        rifl = self._rifl
        shard_to_ops = self._shard_to_ops
        single = self.single_key()
        if single is not None:
            shard_id, key = single
            key_ops = shard_to_ops[shard_id][key]
            if len(key_ops) == 1:
                op = key_ops[0]
                return _restore_command, (
                    rifl[0], rifl[1], shard_id, key, _KIND_CODE[op.kind], op.value,
                )
        return _restore_command, (
            rifl[0],
            rifl[1],
            tuple(
                (
                    shard_id,
                    tuple(
                        (key, tuple((_KIND_CODE[op.kind], op.value) for op in key_ops))
                        for key, key_ops in ops.items()
                    ),
                )
                for shard_id, ops in shard_to_ops.items()
            ),
        )

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


def _restore_command(source: int, sequence: int, shard, key=None, kind=0, value=None) -> Command:
    """Unpickle a :class:`Command` from the values its ``__reduce__``
    carries: ``shard, key, kind, value`` of the flat form, or in
    ``shard``'s place the general form's ``((shard, ((key, ((kind,
    value), ...)), ...)), ...)``."""
    # tuple.__new__: Rifl's own __new__ is a Python-level call, and this
    # runs once a frame on the server's loop
    rifl = _tuple_new(Rifl, (source, sequence))
    if key is not None:
        # Command.from_single, spelled out (kind code 0 is the one read)
        cmd = Command.__new__(Command)
        cmd._rifl = rifl
        cmd._shard_to_ops = {shard: {key: (KVOp(KINDS[kind], value),)}}
        cmd._read_only = not kind
        cmd._total_key_count = 1
        return cmd
    # the constructor's scan, folded into the pass that builds the ops
    shard_to_ops: Dict[ShardId, Dict[Key, Tuple[KVOp, ...]]] = {}
    reads = writes = total = 0
    for shard_id, keys in shard:
        ops = shard_to_ops[shard_id] = {}
        for k, key_ops in keys:
            ops[k] = tuple([KVOp(KINDS[code], v) for code, v in key_ops])
            for code, _ in key_ops:
                if code:
                    writes += 1
                else:
                    reads += 1
        total += len(ops)
    assert shard_to_ops, "commands must have at least one shard"
    assert reads == 0 or writes == 0, (
        "non-read-only commands cannot contain Get operations"
    )
    cmd = Command.__new__(Command)
    cmd._rifl = rifl
    cmd._shard_to_ops = shard_to_ops
    cmd._read_only = writes == 0
    cmd._total_key_count = total
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
