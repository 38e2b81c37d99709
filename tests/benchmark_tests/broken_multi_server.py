"""The program's server with the one order of commands broken underneath:
of every 40th command over several shards only the first shard's part is
applied where the command stands in the order; the rest is applied, and
answered, after the next command that writes one of its keys (or after
eight further commands).  Every key's own chain of writes stays a sound
chain, every command is answered once on every key, and nothing is lost: only
the order across keys is no longer one.  Started in the server's place by
``test_benchmark_e2e.py``; the check has to see it."""

import sys

from fantoch_tpu.run import device_runner

_sound_entry = device_runner._DriverCore._execute_entry
_state = {"torn": 0, "held": None}  # held: [command, shards still to apply, their keys, patience]


def _release(driver, results):
    cmd, shards, _, _ = _state["held"]
    _state["held"] = None
    for shard in shards:
        results.extend(cmd.execute(shard, driver.store))


def _tearing_entry(self, cmd):
    held = _state["held"]
    if held is None and cmd.shard_count > 1 and not cmd.read_only:
        _state["torn"] += 1
        if _state["torn"] % 40 == 0:
            first, *rest = cmd.shards()
            _state["held"] = [cmd, rest, {key for shard in rest for key in cmd.keys(shard)}, 8]
            return cmd.execute(first, self.store)
    results = _sound_entry(self, cmd)
    if held is not None:
        held[3] -= 1
        if held[3] == 0 or any(key in held[2] for _, key in cmd.all_keys()):
            _release(self, results)
    return results


if __name__ == "__main__":
    device_runner._DriverCore._execute_entry = _tearing_entry
    from benchmark.server_entry import main

    main(sys.argv[1:])
