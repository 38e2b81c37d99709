"""Replay an execution log through a fresh executor.

Reference: fantoch_ps/src/bin/graph_executor_replay.rs:14-38 — offline
debugging of executor ordering from a log written with --execution-log.

    python -m fantoch_tpu.bin.replay --log execution_p1.log \\
        --protocol epaxos --id 1 -n 3 -f 1
"""

from __future__ import annotations

import argparse
import json

from fantoch_tpu.bin.common import (
    add_config_flags,
    config_from_args,
    start_device_entry,
    protocol_by_name,
)


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(prog="fantoch_tpu.bin.replay", description=__doc__)
    parser.add_argument("--log", required=True)
    parser.add_argument("--protocol", required=True)
    parser.add_argument("--id", type=int, required=True)
    parser.add_argument("--shard-id", type=int, default=0)
    add_config_flags(parser)
    args = parser.parse_args(argv)
    config = config_from_args(args)
    if config.dispatches_to_device():
        start_device_entry("bin/replay")

    from fantoch_tpu.run.observe import replay_execution_log

    summary = replay_execution_log(
        args.log,
        protocol_by_name(args.protocol),
        args.id,
        args.shard_id,
        config,
    )
    print(
        json.dumps(
            {
                "batches_handled": summary["batches_handled"],
                "results": summary["results"],
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
