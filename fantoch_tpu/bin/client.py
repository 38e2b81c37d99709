"""Client binary: drive a workload against a cluster.

Reference: fantoch_ps/src/bin/client.rs:65-172 (clap flag set: id ranges,
per-shard addresses, open-loop interval, workload knobs, metrics file).

Example:
    python -m fantoch_tpu.bin.client --ids 1-4 \\
        --addresses 0=127.0.0.1:8001 \\
        --commands-per-client 100 --conflict-rate 50 --payload-size 16
"""

from __future__ import annotations

import argparse
import asyncio
import json
import pickle

from fantoch_tpu.bin.common import (
    maybe_log_file,
    parse_id_range,
    parse_shard_addr,
)


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="fantoch_tpu.bin.client", description=__doc__
    )
    parser.add_argument("--ids", required=True, help="client id range, e.g. 1-8")
    parser.add_argument(
        "--addresses",
        required=True,
        help="comma list of shard=host:client_port (one per shard)",
    )
    parser.add_argument("--interval", type=int, default=None, metavar="MS",
                        help="open-loop submit interval; omit for closed loop")
    parser.add_argument("--arrival-rate", type=float, default=None,
                        metavar="PER_S",
                        help="open-loop Poisson arrival rate per client "
                        "(run/backpressure.OpenLoopPacer); mutually "
                        "exclusive with --interval")
    parser.add_argument("--arrival-seed", type=int, default=None,
                        help="seed for the Poisson arrival gaps and the "
                        "overload-retry jitter (reproducible schedules)")
    parser.add_argument("--deadline", type=int, default=None, metavar="MS",
                        help="per-command deadline budget across overload "
                        "retries: once it expires the command is shed, "
                        "not executed late")
    # workload flags (client.rs:100-151)
    parser.add_argument("--key-gen", choices=["conflict_rate", "zipf"],
                        default="conflict_rate")
    parser.add_argument("--conflict-rate", type=int, default=50)
    parser.add_argument("--zipf-coefficient", type=float, default=1.0)
    parser.add_argument("--keys-per-shard", type=int, default=1_000_000)
    parser.add_argument("--keys-per-command", type=int, default=1)
    parser.add_argument("--commands-per-client", type=int, required=True)
    parser.add_argument("--read-only-percentage", type=int, default=0)
    parser.add_argument("--payload-size", type=int, default=0)
    parser.add_argument("--shard-count", type=int, default=None,
                        help="defaults to the number of --addresses entries")
    parser.add_argument("--metrics-file", default=None,
                        help="pickle the per-client latency data here")
    parser.add_argument("--telemetry-file", default=None,
                        help="client-plane windowed telemetry series "
                        "(observability/timeseries.py): submit/reply "
                        "rates, retry/shed tallies, latency windows")
    parser.add_argument("--telemetry-interval", type=int, default=None,
                        metavar="MS", help="telemetry window cadence "
                        "(default 1000)")
    parser.add_argument("--status-frequency", type=int, default=None)
    parser.add_argument("--trace", type=float, default=0.0, metavar="RATE",
                        help="client-plane lifecycle tracing sample rate "
                        "(needs --trace-file): submit/reply span events "
                        "that `bin/obs.py critpath` stitches against the "
                        "servers' logs")
    parser.add_argument("--trace-file", default=None, metavar="PATH",
                        help="client-plane span log (JSONL)")
    parser.add_argument("--log-file", default=None)
    return parser


def workload_from_args(args: argparse.Namespace, shard_count: int):
    from fantoch_tpu.client import ConflictRateKeyGen, Workload
    from fantoch_tpu.client.key_gen import ZipfKeyGen

    if args.key_gen == "conflict_rate":
        key_gen = ConflictRateKeyGen(args.conflict_rate)
    else:
        key_gen = ZipfKeyGen(args.zipf_coefficient, args.keys_per_shard)
    return Workload(
        shard_count=shard_count,
        key_gen=key_gen,
        keys_per_command=args.keys_per_command,
        commands_per_client=args.commands_per_client,
        read_only_percentage=args.read_only_percentage,
        payload_size=args.payload_size,
    )


async def drive(args: argparse.Namespace) -> None:
    from fantoch_tpu.run.client_runner import run_clients

    shard_addresses = {}
    for entry in args.addresses.split(","):
        shard, host, port = parse_shard_addr(entry)
        shard_addresses[shard] = (host, port)
    shard_count = args.shard_count or len(shard_addresses)
    client_ids = parse_id_range(args.ids)
    workload = workload_from_args(args, shard_count)

    import time

    # client-plane lifecycle tracing: the submit/reply span events the
    # critical-path correlator stitches against the servers' logs
    tracer = None
    if args.trace_file is not None and args.trace > 0:
        from fantoch_tpu.core.timing import RunTime
        from fantoch_tpu.observability.tracer import Tracer

        tracer = Tracer(RunTime(), args.trace_file, args.trace, clock="wall")

    t0 = time.perf_counter()
    try:
        clients = await run_clients(
            client_ids,
            shard_addresses,
            workload,
            open_loop_interval_ms=args.interval,
            arrival_rate_per_s=args.arrival_rate,
            arrival_seed=args.arrival_seed,
            deadline_ms=args.deadline,
            status_frequency=args.status_frequency,
            telemetry_file=args.telemetry_file,
            telemetry_interval_ms=args.telemetry_interval,
            **({"tracer": tracer} if tracer is not None else {}),
        )
    finally:
        if tracer is not None:
            tracer.close()
    elapsed_s = time.perf_counter() - t0

    latencies = []  # ClientData latencies are microseconds (data.py)
    sheds = retries = 0
    for client in clients.values():
        latencies.extend(client.data().latency_data())
        sheds += client.shed_commands
        retries += client.overload_retries
    latencies.sort()
    total = len(latencies)

    def ms(micros):
        return round(micros / 1000.0, 3)

    summary = {
        "clients": len(clients),
        "commands": total,
        # workload wall time measured inside the client (excludes the
        # subprocess's interpreter/JAX startup — the honest throughput base)
        "elapsed_s": round(elapsed_s, 3),
        "throughput_cmds_per_s": round(total / elapsed_s, 1) if elapsed_s else None,
        # overload plane: completed/total is the goodput; sheds are
        # deadline-expired commands the plane refused to execute late
        "shed_commands": sheds,
        "overload_retries": retries,
        "latency_ms": {
            "min": ms(latencies[0]) if total else None,
            "p50": ms(latencies[total // 2]) if total else None,
            "p99": ms(latencies[int(total * 0.99)]) if total else None,
            "max": ms(latencies[-1]) if total else None,
        },
    }
    print(json.dumps(summary), flush=True)

    if args.metrics_file:
        with open(args.metrics_file, "wb") as fh:
            pickle.dump({cid: c.data() for cid, c in clients.items()}, fh)


def main(argv=None) -> None:
    # the client plane is asyncio + pickle: it never imports jax, so it
    # can share a host with the process that owns the chip
    args = build_parser().parse_args(argv)
    maybe_log_file(args.log_file)
    asyncio.run(drive(args))


if __name__ == "__main__":
    main()
