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
        # commands ``execute_commands`` applied by its one-op spelling
        self.applied_in_pass = 0

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
        where ``shard_id`` is None, the one shard otherwise).  On a
        ``plain`` store a key's one op is spelled out on the dict, with no
        call a command; several ops a key, and every op of a store that is
        not plain, go through ``execute``."""
        from fantoch_tpu.executor.base import ExecutorResult

        spelled = self.plain
        store = self._store
        get = store.get
        pop = store.pop
        execute = self.execute
        results: List["ExecutorResult"] = []
        append = results.append
        every_shard = shard_id is None
        routed = set()  # commands with a key that went through ``execute``
        for cmd in cmds:
            rifl = cmd._rifl
            if every_shard:
                portions = cmd._shard_to_ops.values()
            else:
                ops = cmd._shard_to_ops.get(shard_id)
                if ops is None:
                    continue
                portions = (ops,)
            for ops in portions:
                for key, key_ops in ops.items():
                    if spelled and len(key_ops) == 1:
                        op = key_ops[0]
                        kind = op.kind
                        if kind is _GET:
                            value = get(key)
                        elif kind is _PUT_KIND:
                            # the previous value, as ``_put`` returns it
                            value = get(key)
                            store[key] = op.value
                        elif kind is _DELETE:
                            value = pop(key, None)
                        else:
                            value = execute(key, op, rifl)  # raises
                        # tuple.__new__: a NamedTuple's own __new__ is a
                        # Python-level call (core/command.py, _restore_command)
                        append(_tuple_new(ExecutorResult, (rifl, key, (value,))))
                    else:
                        routed.add(id(cmd))
                        append(
                            ExecutorResult(
                                rifl, key, tuple([execute(key, op, rifl) for op in key_ops])
                            )
                        )
        if spelled:
            self.applied_in_pass += len(cmds) - len(routed)
        return results


# the three methods ``execute_commands`` was written from, by name
_EXECUTE = KVStore.execute
_DO_EXECUTE = KVStore._do_execute
_PUT = KVStore._put
_GET, _PUT_KIND, _DELETE = KINDS
_tuple_new = tuple.__new__
