"""Key-value store: the replicated state machine being ordered.

Reference: fantoch/src/kvs.rs:6-138.  ``Key``/``Value`` are strings; ops are
Get/Put/Delete with ``Optional[str]`` results.  The KVStore itself stays on
the host (it is control-plane: string keys, tiny values); the accelerator
works on *pre-hashed* int keys (see fantoch_tpu/ops) so the store never has
to cross the device boundary.
"""

from __future__ import annotations

from dataclasses import dataclass
from enum import Enum
from operator import attrgetter
from typing import Dict, List, Optional, TYPE_CHECKING

if TYPE_CHECKING:
    from fantoch_tpu.core.audit import ExecutionDigest
    from fantoch_tpu.core.command import Command
    from fantoch_tpu.core.ids import Rifl, ShardId
    from fantoch_tpu.executor.base import ExecutorResult
    from fantoch_tpu.executor.monitor import ExecutionOrderMonitor

Key = str
Value = str
KVOpResult = Optional[Value]


class KVOpKind(Enum):
    GET = "Get"
    PUT = "Put"
    DELETE = "Delete"


# a kind's code on the wire (core/command.py, Command.__reduce__): its
# index here, so that no frame carries an Enum by class path
KINDS = (KVOpKind.GET, KVOpKind.PUT, KVOpKind.DELETE)


@dataclass(frozen=True)
class KVOp:
    """A single-key operation (fantoch/src/kvs.rs:12-16)."""

    kind: KVOpKind
    value: Optional[Value] = None  # only for PUT

    @staticmethod
    def get() -> "KVOp":
        return KVOp(KVOpKind.GET)

    @staticmethod
    def put(value: Value) -> "KVOp":
        return KVOp(KVOpKind.PUT, value)

    @staticmethod
    def delete() -> "KVOp":
        return KVOp(KVOpKind.DELETE)

    @property
    def is_read(self) -> bool:
        return self.kind is KVOpKind.GET


class KVStore:
    """In-memory string KV store (fantoch/src/kvs.rs:21-69)."""

    def __init__(
        self,
        monitor_execution_order: bool = False,
        execution_digests: bool = False,
    ):
        self._store: Dict[Key, Value] = {}
        self._monitor: Optional["ExecutionOrderMonitor"] = None
        if monitor_execution_order:
            from fantoch_tpu.executor.monitor import ExecutionOrderMonitor

            self._monitor = ExecutionOrderMonitor()
        # consistency-audit plane (core/audit.py): per-key hash chain
        # over executed writes, exchanged by the run layer for online
        # divergence detection (Config.execution_digests)
        self._digest: Optional["ExecutionDigest"] = None
        if execution_digests:
            from fantoch_tpu.core.audit import ExecutionDigest

            self._digest = ExecutionDigest()
        # commands ``execute_commands`` applied by its one-op spelling, and
        # those of them that came off the wire (``Command._off_wire``: read
        # off their frame's own tuple, no dict form ever asked of them here)
        self.applied_in_pass = 0
        self.applied_off_wire = 0

    @property
    def monitor(self) -> Optional["ExecutionOrderMonitor"]:
        return self._monitor

    @property
    def digest(self) -> Optional["ExecutionDigest"]:
        return self._digest

    def __len__(self) -> int:
        """Records held: keys with a value (the snapshot's ``store_records``)."""
        return len(self._store)

    def execute(self, key: Key, op: KVOp, rifl: "Rifl") -> KVOpResult:
        """Execute op on key, recording it in the monitor if enabled.

        Reference: fantoch/src/kvs.rs:37-56 (monitored execute).
        """
        if self._monitor is not None:
            self._monitor.add(key, rifl, read=op.is_read)
        if self._digest is not None and not op.is_read:
            # writes only: reads commute, so their relative order is
            # legitimately unordered across replicas (the monitor's
            # write-order rule)
            self._digest.record(key, rifl, op.kind.value, op.value)
        return self._do_execute(key, op)

    def _do_execute(self, key: Key, op: KVOp) -> KVOpResult:
        if op.kind is KVOpKind.GET:
            return self._store.get(key)
        if op.kind is KVOpKind.PUT:
            # Returns the previous value, like the reference's HashMap::insert.
            assert op.value is not None
            return self._put(key, op.value)
        if op.kind is KVOpKind.DELETE:
            return self._store.pop(key, None)
        raise AssertionError(f"unknown op kind {op.kind}")

    def _put(self, key: Key, value: Value) -> KVOpResult:
        prev = self._store.get(key)
        self._store[key] = value
        return prev

    @property
    def plain(self) -> bool:
        """No monitor, no digest, and ``execute``, ``_do_execute`` and
        ``_put`` are still the three written above (replaced on the class,
        overridden by a subclass or set on the store, they are not):
        what ``execute_commands`` spells out is then what they do."""
        return (
            self._monitor is None
            and self._digest is None
            and getattr(self.execute, "__func__", None) is _EXECUTE
            and getattr(self._do_execute, "__func__", None) is _DO_EXECUTE
            and getattr(self._put, "__func__", None) is _PUT
        )

    def execute_commands(
        self, cmds: List["Command"], shard_id: Optional["ShardId"] = None
    ) -> List["ExecutorResult"]:
        """Apply a round's commands in order, in one pass: what
        ``Command.execute`` a shard a command gives, the same results in
        the same order and the same store after (every shard of a command
        where ``shard_id`` is None, the one shard otherwise).  A command's
        ops are read off its wire form (``Command._wire``: for a command
        off a frame, the frame's own tuple), and on a ``plain`` store a
        key's one op is spelled out on the dict by its kind's code, with
        no call a command; several ops a key, a code the pass does not
        spell, and every op of a store that is not plain, go through
        ``execute`` in the command's dict form."""
        from fantoch_tpu.core.command import FLAT
        from fantoch_tpu.executor.base import ExecutorResult

        spelled = self.plain
        store = self._store
        get = store.get
        pop = store.pop
        execute = self.execute
        results: List["ExecutorResult"] = []
        append = results.append
        every_shard = shard_id is None
        # the commands with a key that went through ``execute`` (by id),
        # and whether each came off the wire
        routed: Dict[int, bool] = {}

        def through_execute(cmd: "Command", shard: "ShardId", key: Key) -> None:
            routed[id(cmd)] = cmd._off_wire
            rifl = cmd._rifl
            key_ops = cmd._shard_to_ops[shard][key]
            append(ExecutorResult(rifl, key, tuple([execute(key, op, rifl) for op in key_ops])))

        for cmd in cmds:
            wire = cmd._wire
            if len(wire) == FLAT:
                # one shard, one key, one op: (source, sequence, shard, key, code, value)
                if every_shard or wire[2] == shard_id:
                    key = wire[3]
                    code = wire[4] if spelled else None
                    if code == 0:
                        value = get(key)
                    elif code == 1:
                        # the previous value, as ``_put`` returns it
                        value = get(key)
                        store[key] = wire[5]
                    elif code == 2:
                        value = pop(key, None)
                    else:
                        through_execute(cmd, wire[2], key)
                        continue
                    # tuple.__new__: a NamedTuple's own __new__ is a
                    # Python-level call (core/command.py, _off_wire)
                    append(_tuple_new(ExecutorResult, (cmd._rifl, key, (value,))))
                continue
            for shard, keys in wire[2]:
                if every_shard or shard == shard_id:
                    for key, key_ops in keys:
                        code = key_ops[0][0] if spelled and len(key_ops) == 1 else None
                        if code == 0:
                            value = get(key)
                        elif code == 1:
                            value = get(key)
                            store[key] = key_ops[0][1]
                        elif code == 2:
                            value = pop(key, None)
                        else:
                            through_execute(cmd, shard, key)
                            continue
                        append(_tuple_new(ExecutorResult, (cmd._rifl, key, (value,))))
        if spelled:
            self.applied_in_pass += len(cmds) - len(routed)
            self.applied_off_wire += sum(map(_OFF_WIRE, cmds)) - sum(routed.values())
        return results


# the three methods ``execute_commands`` was written from, by name
_EXECUTE = KVStore.execute
_DO_EXECUTE = KVStore._do_execute
_PUT = KVStore._put
_tuple_new = tuple.__new__
_OFF_WIRE = attrgetter("_off_wire")
