"""CLI front for the multi-process ordering pool: measure aggregate
ordering throughput across N key-sharded worker processes.

The process-granularity twin of the reference's 16-worker production
defaults (fantoch/src/run/pool.rs:115-124 +
fantoch_exp/src/config.rs:21-29): one front shards a workload by key
bucket, N OS processes each order their shard through their own
BatchedDependencyGraph, and the front reports the aggregate.

    python -m fantoch_tpu.bin.ordering_pool --workers 4 --commands 1000000
"""

from __future__ import annotations

import argparse
import json
import time


def main() -> None:
    parser = argparse.ArgumentParser("fantoch_tpu.bin.ordering_pool")
    parser.add_argument("--workers", type=int, default=4)
    parser.add_argument("--commands", type=int, default=1 << 20)
    parser.add_argument("--conflict", type=float, default=0.5)
    args = parser.parse_args()

    import multiprocessing as mp

    import numpy as np

    import bench  # repo-root module: shared workload builder
    from fantoch_tpu.run.local_pool import OrderingPool

    key, dep, src, seq = bench.build_workload(args.commands, args.conflict)
    warm_key, warm_dep, warm_src, warm_seq = bench.build_workload(
        args.commands, args.conflict, seed=7
    )
    shards = OrderingPool.shard_columns(
        key, src.astype(np.int64), seq.astype(np.int64) + 1,
        dep.astype(np.int64), args.workers,
    )
    warm = OrderingPool.shard_columns(
        warm_key, warm_src.astype(np.int64),
        warm_seq.astype(np.int64) + 1 + args.commands,
        warm_dep.astype(np.int64), args.workers,
    )
    with OrderingPool(args.workers) as pool:
        pool.prepare(max(len(s[0]) for s in shards + warm))
        pool.run_shards(warm)
        t0 = time.perf_counter()
        orders = pool.run_shards(shards)
        dt = time.perf_counter() - t0
    executed = sum(len(s) for s, _ in orders)
    assert executed == args.commands
    print(
        json.dumps(
            {
                "workers": args.workers,
                "cpus": mp.cpu_count(),
                "commands": args.commands,
                "wall_ms": round(dt * 1000.0, 1),
                "cmds_per_s": int(args.commands / dt),
            }
        )
    )


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    main()
