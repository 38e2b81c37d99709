"""The program's server with reads broken underneath: every 40th ``Get``
is answered from before its key's last write (the value that write
overwrote, nothing where it was the first), as a replica that serves reads
from a lagging copy would.  Every write is sound, so the chains of writes
hold; only a read comes back older than a write acknowledged before it was
sent.  Started in the server's place by ``test_benchmark_e2e_ycsb.py``; the
check has to see it (``stale_read``)."""

import sys

from fantoch_tpu.core.kvs import KVOpKind, KVStore

_gets = 0
_before = {}  # key -> what its last write overwrote
_sound_execute = KVStore._do_execute


def _stale_execute(self, key, op):
    global _gets
    if op.kind is KVOpKind.GET:
        _gets += 1
        if _gets % 40 == 0 and key in _before:
            return _before[key]
    elif op.kind is KVOpKind.PUT:
        _before[key] = self._store.get(key)
    return _sound_execute(self, key, op)


if __name__ == "__main__":
    KVStore._do_execute = _stale_execute
    from benchmark.server_entry import main

    main(sys.argv[1:])
