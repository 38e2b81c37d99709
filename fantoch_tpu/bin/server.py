"""Server binary: boot one protocol process of a cluster.

Reference: fantoch_ps/src/bin/common/protocol.rs:64-368 (`run::<P>()` and
the clap flag set) — protocol selection is a flag here instead of one
binary per protocol.

Example (3-process localhost EPaxos, process 1):
    python -m fantoch_tpu.bin.server --protocol epaxos --id 1 --shard-id 0 \\
        --port 7001 --client-port 8001 \\
        --addresses 2=127.0.0.1:7002,3=127.0.0.1:7003 \\
        --sorted 1:0,2:0,3:0 -n 3 -f 1
"""

from __future__ import annotations

import argparse
import asyncio
import signal

from fantoch_tpu.bin.common import (
    add_config_flags,
    config_from_args,
    maybe_log_file,
    parse_peer,
    parse_sorted,
    protocol_by_name,
    start_device_entry,
)


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="fantoch_tpu.bin.server", description=__doc__
    )
    parser.add_argument("--protocol", required=True,
                        help="basic|epaxos|atlas|newt|caesar|fpaxos; with "
                        "--device-step the protocol round runs as one device "
                        "program: 'newt' the timestamp-consensus round, "
                        "'caesar' the timestamp+predecessors round, 'fpaxos' "
                        "the leader-based slot round, anything else the "
                        "dep-commit round (dependencies with the read/write "
                        "split): 'atlas' under Atlas's quorums (n/2 + f, "
                        "f + 1) and its fast path (every dependency reported "
                        "by f of the fast quorum), 'epaxos' and 'basic' "
                        "under EPaxos's (f = n/2, identical reports)")
    parser.add_argument("--id", type=int, default=None,
                        help="process id (required without --device-step)")
    parser.add_argument("--shard-id", type=int, default=0)
    parser.add_argument("--ip", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=None, help="peer port")
    parser.add_argument("--client-port", type=int, required=True)
    parser.add_argument(
        "--device-step",
        action="store_true",
        help="serve through the device-resident protocol step "
        "(run/device_runner.py): the whole commit+execute round is one "
        "jit program over a (replica x batch) mesh; no TCP peer mesh",
    )
    parser.add_argument("--device-batch", type=int, default=256,
                        help="compiled device batch size (--device-step)")
    parser.add_argument("--device-key-buckets", type=int, default=4096)
    parser.add_argument("--device-key-width", type=int, default=1,
                        help="max conflict-key buckets per command")
    parser.add_argument("--device-pending", type=int, default=256,
                        help="device pending-buffer capacity")
    parser.add_argument(
        "--multihost", action="store_true",
        help="build the device mesh topology-aware for multi-host slices "
        "(parallel/multihost.py): hosts on the replica axis (quorum "
        "fan-ins ride DCN), each host's chips on the batch axis (sorts "
        "ride ICI); bootstraps jax.distributed when a coordinator is "
        "configured, degrades to the single-host mesh otherwise")
    parser.add_argument(
        "--addresses",
        default=None,
        help="comma list of pid=host:port[:delay_ms] for every peer this "
        "process connects to (own-shard peers + closest process of each "
        "other shard); delay_ms adds an artificial FIFO delay line "
        "(delay.rs:6-39)",
    )
    parser.add_argument(
        "--sorted",
        default=None,
        help="distance-sorted 'pid:shard,...' process list (self first); "
        "omit with --ping-sort to measure instead (ping.rs:13-78)",
    )
    parser.add_argument("--ping-sort", action="store_true")
    add_config_flags(parser)
    parser.add_argument("--workers", type=int, default=1)
    parser.add_argument("--executors", type=int, default=1)
    parser.add_argument("--multiplexing", type=int, default=1,
                        help="TCP connections per peer (random writer pick, "
                        "process.rs:71-97)")
    parser.add_argument("--metrics-file", default=None,
                        help="periodic crash-consistent snapshots; gzip+pickle "
                        "ProcessMetrics normally, JSON round/path tallies "
                        "under --device-step")
    parser.add_argument("--metrics-interval", type=int, default=5000, metavar="MS")
    parser.add_argument("--telemetry-file", default=None,
                        help="live windowed telemetry series "
                        "(observability/timeseries.py): one JSONL ring of "
                        "per-window rates + histogram snapshots; `obs "
                        "watch` renders it live")
    parser.add_argument("--metrics-port", type=int, default=None,
                        help="Prometheus-text exposition endpoint "
                        "(observability/exposition.py): GET /metrics "
                        "scrapes the live sample, GET /profile?ms=N "
                        "captures an on-demand jax.profiler device trace "
                        "next to the telemetry file (SIGUSR2 triggers the "
                        "same capture); 0 = OS-assigned")
    parser.add_argument(
        "--heartbeat-interval", type=float, default=1.0, metavar="S",
        help="peer failure-detector probe interval (seconds)")
    parser.add_argument(
        "--heartbeat-misses", type=int, default=8,
        help="silent intervals before a peer is declared lost; raise on "
             "contended machines (testbeds sharing one core) so CPU "
             "starvation does not read as peer death")
    parser.add_argument("--trace-file", default=None, metavar="PATH",
                        help="per-dot lifecycle span log (JSONL; needs "
                        "--trace RATE > 0): message edges + spans that "
                        "`bin/obs.py critpath` stitches across processes")
    parser.add_argument("--flight-dir", default=None, metavar="DIR",
                        help="where --flight-recorder dumps "
                        "flight_p<pid>.json black boxes (default: next "
                        "to the trace/telemetry/metrics file)")
    parser.add_argument("--execution-log", default=None)
    parser.add_argument("--wal-dir", default=None, metavar="DIR",
                        help="durable command log + snapshots (run/wal.py): "
                        "on a restart with the same dir the server recovers "
                        "(snapshot + tail replay) and rejoins via MSync "
                        "instead of starting empty")
    parser.add_argument("--wal-snapshot-interval", type=int, default=2000,
                        metavar="MS", help="WAL snapshot cadence")
    parser.add_argument("--tracer-show-interval", type=int, default=None, metavar="MS")
    parser.add_argument("--log-file", default=None)
    return parser


def _backend_banner(backend: dict) -> str:
    """The banner clause that names what serves (a parent script reads
    it to tell a chip run from a CPU run)."""
    mesh = backend.get("mesh_shape")
    shards = backend.get("shards_on_device")
    return (
        f" [platform={backend['platform']} "
        f"device_kind={backend['device_kind']!r} "
        f"devices={backend['device_count']}"
        + (
            " mesh=" + "x".join(f"{a}:{n}" for a, n in mesh.items())
            if mesh
            else ""
        )
        # the shards on each device, devices apart by "|": 0|1|2|3 is
        # one shard a device
        + (
            " shards_on_device="
            + "|".join(",".join(map(str, held)) for held in shards)
            if shards
            else ""
        )
        + (
            f" rule={backend['rule']} quorums="
            + "/".join(map(str, backend["quorums"]))
            if "rule" in backend
            else ""
        )
        + (f" resolver={backend['resolver']}" if "resolver" in backend else "")
        + (
            f" round={backend['round']} accept_quorum={backend['accept_quorum']}"
            if "round" in backend
            else ""
        )
        + f" compile_cache={backend['compile_cache_dir']}]"
    )


async def serve_device_step(args: argparse.Namespace, loop_selector=None) -> None:
    """The TPU serving path: one server, the protocol round on-device.
    ``loop_selector``: the selector the running loop was made over, where
    it is one that reads the clock (``main``): the runtime's recorder then
    keeps the loop's thread's account of its own time."""
    protocol_by_name(args.protocol)  # validate the label even when unused
    config = config_from_args(args)
    # the platform rule, before a mesh is built or a port is bound
    backend = start_device_entry("bin/server --device-step")

    from fantoch_tpu.run.device_runner import DeviceRuntime

    process_id = args.id if args.id is not None else 1
    mesh = None
    if args.multihost:
        from fantoch_tpu.parallel.multihost import (
            distributed_init,
            make_multihost_mesh,
        )

        distributed_init()
        # the mesh is sized by TOTAL replica rows: the sharded device state
        # holds n rows per shard in shard-major order (_init_sharded_mesh),
        # so validating against config.n alone would under-count the mesh
        mesh = make_multihost_mesh(
            num_replicas=config.n * config.shard_count,
            shard_count=config.shard_count,
        )
    runtime = DeviceRuntime(
        config,
        (args.ip, args.client_port),
        protocol=args.protocol,
        process_id=process_id,
        batch_size=args.device_batch,
        key_buckets=args.device_key_buckets,
        key_width=args.device_key_width,
        pending_capacity=args.device_pending,
        monitor_execution_order=config.executor_monitor_execution_order,
        metrics_file=args.metrics_file,
        metrics_interval_ms=args.metrics_interval,
        mesh=mesh,
        telemetry_file=args.telemetry_file,
        metrics_port=args.metrics_port,
        trace_file=args.trace_file,
        flight_dir=args.flight_dir,
        loop_selector=loop_selector,
    )
    await runtime.start()
    _arm_profile_signal(args)
    _arm_flight_signal(runtime)
    # SIGTERM stops the server the way Ctrl-C does: the serve task is
    # cancelled and the finally below leaves the final snapshot
    asyncio.get_running_loop().add_signal_handler(
        signal.SIGTERM, asyncio.current_task().cancel
    )
    print(
        f"p{process_id} (device-step, n={config.n}) serving clients on "
        f"{args.ip}:{args.client_port}"
        + (
            f"; /metrics on :{runtime.metrics_port}"
            if runtime.metrics_port is not None
            else ""
        )
        + _backend_banner({**backend, **runtime.backend_report()}),
        flush=True,
    )
    try:
        await runtime.failed.wait()
        raise SystemExit(f"p{process_id} failed: {runtime.failure!r}")
    finally:
        # runs under task cancellation too (Ctrl-C or SIGTERM through
        # asyncio.run): short serves must still leave a final metrics
        # snapshot (and the round-stage spans beside it)
        runtime.emit_final()


def _arm_flight_signal(runtime) -> None:
    """SIGUSR1 = dump the flight-recorder ring on demand (a black box
    without killing the run); no-op when the recorder is off."""
    if getattr(runtime, "flight", None) is None:
        return
    from fantoch_tpu.observability.recorder import install_flight_signal

    install_flight_signal(runtime.flight, runtime.flight_dir)


def _arm_profile_signal(args: argparse.Namespace) -> None:
    """SIGUSR2 = capture a 1s jax.profiler device trace next to the
    telemetry/metrics file (the no-port spelling of ``/profile?ms=N``)."""
    from fantoch_tpu.observability.exposition import (
        install_profile_signal,
        profile_output_dir,
    )

    install_profile_signal(
        profile_output_dir(args.telemetry_file, args.metrics_file)
    )


async def serve(args: argparse.Namespace, loop_selector=None) -> None:
    from fantoch_tpu.run.process_runner import ProcessRuntime

    if args.device_step:
        await serve_device_step(args, loop_selector)
        return
    if args.id is None or args.port is None or args.addresses is None:
        raise SystemExit(
            "--id, --port and --addresses are required without --device-step"
        )
    protocol_cls = protocol_by_name(args.protocol)
    config = config_from_args(args)
    # a batched executor or a device plane dispatches to the device: the
    # platform rule applies before a port is bound (on a one-chip host
    # only ONE such server can own the chip; its peers run under
    # JAX_PLATFORMS=cpu)
    backend = (
        start_device_entry(f"bin/server --protocol {args.protocol}")
        if config.dispatches_to_device()
        else None
    )

    peers = {}
    delays = {}
    for entry in args.addresses.split(","):
        pid, host, port, delay = parse_peer(entry)
        peers[pid] = (host, port)
        if delay is not None:
            delays[pid] = delay

    if args.sorted:
        sorted_processes = parse_sorted(args.sorted)
    else:
        if not args.ping_sort:
            raise SystemExit("--sorted or --ping-sort is required")
        # the address list carries no shard labels, so the provisional
        # all-own-shard list is only correct single-shard; multi-shard
        # topologies must say which peer serves which shard via --sorted
        if args.shard_count != 1:
            raise SystemExit(
                "--ping-sort without --sorted requires --shard-count 1; "
                "pass --sorted for multi-shard topologies"
            )
        # provisional order (self first); ping_sort re-sorts at startup
        sorted_processes = [(args.id, args.shard_id)] + [
            (pid, args.shard_id) for pid in sorted(peers)
        ]

    runtime = ProcessRuntime(
        protocol_cls,
        args.id,
        args.shard_id,
        config,
        listen_addr=(args.ip, args.port),
        client_addr=(args.ip, args.client_port),
        peers=peers,
        sorted_processes=sorted_processes,
        workers=args.workers,
        executors=args.executors,
        multiplexing=args.multiplexing,
        peer_delays=delays or None,
        ping_sort=args.ping_sort,
        metrics_file=args.metrics_file,
        metrics_interval_ms=args.metrics_interval,
        execution_log=args.execution_log,
        tracer_show_interval_ms=args.tracer_show_interval,
        heartbeat_interval_s=args.heartbeat_interval,
        heartbeat_misses=args.heartbeat_misses,
        wal_dir=args.wal_dir,
        wal_snapshot_interval_ms=args.wal_snapshot_interval,
        telemetry_file=args.telemetry_file,
        metrics_port=args.metrics_port,
        trace_file=args.trace_file,
        flight_dir=args.flight_dir,
    )
    await runtime.start()
    _arm_profile_signal(args)
    _arm_flight_signal(runtime)
    print(
        f"p{args.id} ({args.protocol}) up on {args.ip}:{args.port}"
        + (
            f"; /metrics on :{runtime.metrics_port}"
            if runtime.metrics_port is not None
            else ""
        )
        + (_backend_banner(backend) if backend is not None else ""),
        flush=True,
    )
    await runtime.failed.wait()
    raise SystemExit(f"p{args.id} failed: {runtime.failure!r}")


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    maybe_log_file(args.log_file)
    selector = loop_factory = None
    if args.device_step:
        # the served path's loop runs over a selector that reads the clock
        # around each of its calls: what the loop's thread does with its
        # time is the runtime's recorder's to say (observability/device.py)
        from fantoch_tpu.observability.device import TimedSelector

        selector = TimedSelector()

        def loop_factory():
            return asyncio.SelectorEventLoop(selector)

    try:
        asyncio.run(serve(args, selector), loop_factory=loop_factory)
    except (KeyboardInterrupt, asyncio.CancelledError):
        pass


if __name__ == "__main__":
    main()
