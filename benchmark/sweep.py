"""The one-time sweep for a configuration's knee (run on the chip by hand).

    python3 benchmark/sweep.py --workload <open-loop cell> --rates 4000,8000,... --seconds 10

Each rung is one whole run of the cell (``benchmark.run.run_cell``) with its
offered rate replaced.  A rung *holds* when goodput keeps up with the
offered rate, nothing fails and the backlog does not grow through the rung
(the median latency of the window's last third stays within 1.25x + 5 ms of
its first third).  The knee is the highest rate that holds below the first
rate that does not: one rule, and it decides.  (The program's
``exp/scenarios.detect_knee`` names the first *saturated* rung from goodput
alone; it cannot see a backlog that grows while goodput still keeps up, so
it is not used here.)  The cell then fixes 0.8 of the knee in
``benchmark/cells/<cell>.json``.  The sweep stops after two rungs in a row
that do not hold.
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

from benchmark import run  # noqa: E402


def rung(workload: str, rate: float, seconds: float, seed: int) -> dict:
    result = run.run_cell(workload, seed, seconds, False, overrides={"rate_per_s": rate})
    cols = np.load(os.path.join(ROOT, "benchmark_out", workload, "trace0", "history.npz"))
    window = cols["phase"] == 1
    due, acked, ok = cols["due"][window], cols["acked"][window], cols["status"][window] == 0
    t0 = due.min()
    third = seconds / 3.0
    latency = (acked - due) * 1000.0

    def median(lo, hi):
        pick = ok & (due >= t0 + lo) & (due < t0 + hi)
        return float(np.median(latency[pick])) if pick.any() else float("inf")

    first, last = median(0.0, third), median(2 * third, seconds)
    offered = result["attempted"] / seconds
    goodput = float(np.count_nonzero(ok & (acked <= t0 + seconds)) / seconds)
    point = {
        "offered_cmds_per_s": offered, "goodput_cmds_per_s": goodput,
        "failed": result["failed"], "correct": result["correct"],
        "p50_first_third_ms": first, "p50_last_third_ms": last,
        "p50_ms": float(np.median(latency[ok])) if ok.any() else None,
        "p95_ms": float(np.percentile(latency[ok], 95)) if ok.any() else None,
    }
    point["holds"] = bool(
        goodput >= 0.97 * offered and result["failed"] == 0 and last <= 1.25 * first + 5.0
    )
    return point


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True, help="comma list, ascending")
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--out", default=None, help="also write the table here, as JSON")
    args = parser.parse_args(argv)
    points, misses = [], 0
    for step, rate in enumerate(float(r) for r in args.rates.split(",")):
        point = {"rate": rate, **rung(args.workload, rate, args.seconds, args.seed + step)}
        points.append(point)
        print("RUNG", json.dumps(point), flush=True)
        misses = 0 if point["holds"] else misses + 1
        if misses == 2:
            break
    held = [p["rate"] for index, p in enumerate(points)
            if p["holds"] and all(q["holds"] for q in points[:index])]
    table = {"workload": args.workload, "seconds": args.seconds, "points": points,
             "knee_per_s": max(held) if held else None}
    print("SWEEP", json.dumps(table), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(table, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
