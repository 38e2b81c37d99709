"""Sequencer microbenchmark: key-clock proposal throughput.

Reference: fantoch_ps/src/bin/sequencer_bench.rs — measures the key-clock
sequencer (the Newt proposal hot loop) under configurable keys / clients.
Here both implementations are measured: the host ``SequentialKeyClocks``
(per-command Python bumps) and the batched device kernel
``batched_clock_proposal`` (one launch per batch), reporting commands/s
for each.

    python -m fantoch_tpu.bin.sequencer_bench --keys 64 --batch 100000
"""

from __future__ import annotations

import argparse
import json
import time


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(
        prog="fantoch_tpu.bin.sequencer_bench", description=__doc__
    )
    parser.add_argument("--keys", type=int, default=64)
    parser.add_argument("--batch", type=int, default=100_000)
    parser.add_argument("--iters", type=int, default=5)
    parser.add_argument("--host-batch", type=int, default=None,
                        help="commands for the host measurement "
                        "(default: min(batch, 50000))")
    args = parser.parse_args(argv)

    from fantoch_tpu.bin.common import start_device_entry

    device = start_device_entry("bin/sequencer_bench")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from fantoch_tpu.core.command import Command
    from fantoch_tpu.core.ids import Rifl
    from fantoch_tpu.core.kvs import KVOp
    from fantoch_tpu.ops.table_ops import batched_clock_proposal
    from fantoch_tpu.protocol.common.table_clocks import SequentialKeyClocks

    rng = np.random.default_rng(3)
    key = jnp.asarray(rng.integers(0, args.keys, size=args.batch), jnp.int32)
    mins = jnp.zeros((args.batch,), jnp.int32)
    prior = jnp.zeros((args.keys,), jnp.int32)

    # device: one kernel launch per batch
    out = batched_clock_proposal(prior, key, mins)
    jax.block_until_ready(out[0])
    times = []
    for _ in range(args.iters):
        t0 = time.perf_counter()
        out = batched_clock_proposal(out[2], key, mins)
        jax.block_until_ready(out[0])
        times.append(time.perf_counter() - t0)
    device_s = float(np.median(times))

    # host: per-command proposal (the reference's sequencer shape)
    host_batch = args.host_batch or min(args.batch, 50_000)
    clocks = SequentialKeyClocks(1, 0)
    cmds = [
        Command.from_single(
            Rifl(1, i + 1), 0, str(int(k)), KVOp.put("x")
        )
        for i, k in enumerate(np.asarray(key[:host_batch]))
    ]
    t0 = time.perf_counter()
    for cmd in cmds:
        clocks.proposal(cmd, 0)
    host_s = time.perf_counter() - t0

    print(
        json.dumps(
            {
                **device,
                "keys": args.keys,
                "batch": args.batch,
                "device_cmds_per_s": int(args.batch / device_s),
                "host_batch": host_batch,
                "host_cmds_per_s": int(host_batch / host_s),
                "speedup": round((args.batch / device_s) / (host_batch / host_s), 1),
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
