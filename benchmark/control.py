"""The control: histories that the check must call incorrect.

This system states no precision, so the control breaks, one at a time, a
guarantee its configurations state, by the benchmark's own means on the
answers a real run recorded (``history.npz`` in the run's output directory);
the program gets no switch for it.

    python3 benchmark/control.py benchmark_out/<cell>/trace0 [--seeds 1,2,3]

exits 0 iff the sound history is accepted and every mutation of it, for
every seed, is rejected.  The mutations:

* ``fork``: a write returns the value another write already returned
  (per-key linearizable writes: one order per key).
* ``duplicate_ack``: one request is acknowledged a second time (exactly
  once per rifl).
* ``inversion``: a write late in a key's chain is stamped as acknowledged
  before an earlier one was sent (real time).
* ``lost_write``: the read-back of a key returns the value before the last
  acknowledged write (a reply only after execution; nothing acknowledged is
  lost).
* ``foreign_value``: a write returns a value no client of the run wrote.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark.check import check_history  # noqa: E402
from benchmark.generators.kv_loop import BAD_VALUE, GET, OK, PUT, READBACK  # noqa: E402


def _chains(rec: dict) -> dict[int, list[int]]:
    """key -> rows of its acknowledged writes, for keys with three or more."""
    rows = np.flatnonzero((rec["op"] == PUT) & (rec["status"] == OK))
    by_key: dict[int, list[int]] = {}
    for row in rows[np.argsort(rec["key"][rows], kind="stable")]:
        by_key.setdefault(int(rec["key"][row]), []).append(int(row))
    return {key: rs for key, rs in by_key.items() if len(rs) >= 3}


def mutations(records: dict, strays, seed: int):
    """Yield ``(name, records, strays)``: each breaks one guarantee."""
    rng = np.random.default_rng([int(seed), 29])
    chains = _chains(records)
    if not chains:
        raise ValueError("no key with three acknowledged writes to mutate")
    keys = sorted(chains)
    strays = np.asarray(strays, dtype=np.float64).reshape(-1, 3)

    def fresh() -> dict:
        return {name: col.copy() for name, col in records.items()}

    rows = chains[keys[int(rng.integers(len(keys)))]]
    a, b = (int(r) for r in rng.choice(rows, size=2, replace=False))
    rec = fresh()
    rec["ret_client"][b], rec["ret_seq"][b] = rec["ret_client"][a], rec["ret_seq"][a]
    yield "fork", rec, strays

    row = rows[int(rng.integers(len(rows)))]
    again = [[records["client"][row], records["seq"][row], records["acked"][row] + 0.001]]
    yield "duplicate_ack", fresh(), np.concatenate([strays, np.array(again, dtype=np.float64)])

    # in chain order: the row whose value another returned comes first
    position = {(int(records["client"][r]), int(records["seq"][r])): r for r in rows}
    later = next(r for r in rows
                 if (int(records["ret_client"][r]), int(records["ret_seq"][r])) in position)
    earlier = position[(int(records["ret_client"][later]), int(records["ret_seq"][later]))]
    rec = fresh()
    rec["acked"][later] = rec["sent"][earlier] - 0.002
    rec["sent"][later] = rec["due"][later] = rec["acked"][later] - 0.001
    yield "inversion", rec, strays

    reads = np.flatnonzero((records["phase"] == READBACK) & (records["op"] == GET)
                           & (records["status"] == OK) & (records["ret_client"] > 0))
    for read in (int(r) for r in rng.permutation(reads)):
        writers = {(int(records["client"][r]), int(records["seq"][r])): r
                   for r in chains.get(int(records["key"][read]), [])}
        tail = writers.get((int(records["ret_client"][read]), int(records["ret_seq"][read])))
        if tail is not None and records["ret_client"][tail] > 0:
            rec = fresh()  # the read now returns what the last write overwrote
            rec["ret_client"][read], rec["ret_seq"][read] = rec["ret_client"][tail], rec["ret_seq"][tail]
            yield "lost_write", rec, strays
            break

    rec = fresh()
    rec["ret_client"][rows[0]] = rec["ret_seq"][rows[0]] = BAD_VALUE
    yield "foreign_value", rec, strays


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("run_dir")
    parser.add_argument("--seeds", default="1,2,3")
    args = parser.parse_args(argv)
    history = dict(np.load(os.path.join(args.run_dir, "history.npz")))
    strays = history.pop("strays")
    sound = check_history(history, strays)
    print("CONTROL sound history:", "accepted" if sound["correct"] else "REJECTED",
          json.dumps(sound["stats"]))
    ok = sound["correct"]
    for seed in (int(s) for s in args.seeds.split(",")):
        for name, rec, extra in mutations(history, strays, seed):
            verdict = check_history(rec, extra)
            caught = not verdict["correct"]
            ok &= caught
            first = verdict["witnesses"][0]["check"] if verdict["witnesses"] else None
            print(f"CONTROL seed {seed} {name}: violations {verdict['stats']['violations']}"
                  f" (the check passes only at 0) -> {'rejected' if caught else 'ACCEPTED'} [{first}]")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
