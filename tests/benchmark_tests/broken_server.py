"""The program's server with the timed path broken underneath: every 40th
``Put`` answers as usual and is then dropped, so the next write on that key
returns the value the dropped one had already returned (a fork) and the
dropped write is acknowledged but lost.  Started in the server's place by
``test_benchmark_e2e.py``; the check has to see it."""

import sys

from fantoch_tpu.core.kvs import KVStore

_puts = 0
_sound_put = KVStore._put


def _dropping_put(self, key, value):
    global _puts
    _puts += 1
    if _puts % 40 == 0:
        return self._store.get(key)
    return _sound_put(self, key, value)


if __name__ == "__main__":
    KVStore._put = _dropping_put
    from benchmark.server_entry import main

    main(sys.argv[1:])
