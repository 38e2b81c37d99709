"""Localhost whole-system harness: boots a full n-process TCP cluster plus
clients inside one asyncio loop.

Reference: fantoch/src/run/mod.rs:1030-1346 (`run_test_with_inspect_fun`) —
the reference boots every server and client as tokio tasks in one runtime
on random localhost ports; here they are asyncio tasks in one loop, and
instead of shipping Inspect closures through the periodic task we keep
direct references to the runtimes for post-run assertions.
"""

from __future__ import annotations

import asyncio
import socket
from typing import Dict, List, Optional, Tuple

from fantoch_tpu.client.client import Client
from fantoch_tpu.client.workload import Workload
from fantoch_tpu.core.config import Config
from fantoch_tpu.core.ids import ClientId, ProcessId, process_ids
from fantoch_tpu.run.client_runner import run_clients
from fantoch_tpu.run.process_runner import ProcessRuntime


_claimed_ports: set = set()


def free_port() -> int:
    """An OS-assigned free port, never handed out twice by this process.

    The probe socket is closed before the caller binds, so the kernel may
    recycle the port for a concurrent probe — within one process (the
    common harness pattern: allocate 2 ports x n processes up front) the
    claimed-set closes that race; across processes the startup retry in
    the runners covers the rest."""
    for _ in range(64):
        with socket.socket() as sock:
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            sock.bind(("127.0.0.1", 0))
            port = sock.getsockname()[1]
        if port not in _claimed_ports:
            _claimed_ports.add(port)
            return port
    raise RuntimeError("could not allocate a fresh localhost port")


async def run_localhost_cluster(
    protocol_cls: type,
    config: Config,
    workload: Workload,
    clients_per_process: int,
    open_loop_interval_ms: Optional[int] = None,
    arrival_rate_per_s: Optional[float] = None,
    arrival_seed: Optional[int] = None,
    deadline_ms: Optional[int] = None,
    extra_run_time_ms: int = 500,
    workers: int = 1,
    executors: int = 1,
    multiplexing: int = 1,
    peer_delays: Optional[Dict[ProcessId, Dict[ProcessId, int]]] = None,
    ping_sort: bool = False,
    observe_dir: Optional[str] = None,
    metrics_ports: Optional[Dict[ProcessId, int]] = None,
    runtime_kwargs: Optional[dict] = None,
    chaos=None,
) -> Tuple[Dict[ProcessId, ProcessRuntime], Dict[ClientId, Client]]:
    """Boot n*shard_count processes + clients, run the workload to
    completion, keep the cluster alive `extra_run_time_ms` (for GC rounds),
    then tear down.

    Multi-shard topology (mod.rs:786-838 region-index pattern): shard s
    owns ids s*n+1..=(s+1)*n; the process at offset o of shard s peers with
    its own shard plus the offset-o process of every other shard (its
    "closest" of that shard), mirroring the reference's
    connect-to-closest-per-shard rule (run/task/process.rs:21)."""
    if observe_dir is not None:
        import os

        os.makedirs(observe_dir, exist_ok=True)
    # lifecycle tracing: with a sample rate and an observe dir, every
    # runtime writes trace_p<pid>.jsonl and the client plane
    # trace_clients.jsonl — bin/obs.py consumes all of them together
    tracing = observe_dir is not None and config.trace_sample_rate > 0
    client_tracer = None
    if tracing:
        from fantoch_tpu.core.timing import RunTime
        from fantoch_tpu.observability.tracer import Tracer

        client_tracer = Tracer(
            RunTime(), f"{observe_dir}/trace_clients.jsonl",
            config.trace_sample_rate, clock="wall",
        )
    shard_count = config.shard_count
    shard_ids = {s: list(process_ids(s, config.n)) for s in range(shard_count)}
    all_pids = [pid for ids in shard_ids.values() for pid in ids]
    shard_of = {pid: s for s, ids in shard_ids.items() for pid in ids}
    offset_of = {pid: pid - shard_ids[shard_of[pid]][0] for pid in all_pids}
    peer_ports = {pid: free_port() for pid in all_pids}
    client_ports = {pid: free_port() for pid in all_pids}
    runtimes: Dict[ProcessId, ProcessRuntime] = {}
    for pid in all_pids:
        shard_id = shard_of[pid]
        ids = shard_ids[shard_id]
        offset = offset_of[pid]
        # localhost processes are equidistant except to themselves: the
        # distance-sorted list must lead with self (ping 0), like the
        # reference's ping sort (run/task/ping.rs:144), or a process's fast
        # quorum may exclude itself and its submits would rely on acks for
        # payloads it never stored
        sorted_processes = [(pid, shard_id)] + [
            (peer, shard_id) for peer in ids if peer != pid
        ]
        peers = {peer: ("127.0.0.1", peer_ports[peer]) for peer in ids if peer != pid}
        for other_shard, other_ids in shard_ids.items():
            if other_shard != shard_id:
                closest = other_ids[offset]
                sorted_processes.append((closest, other_shard))
                peers[closest] = ("127.0.0.1", peer_ports[closest])
        runtimes[pid] = ProcessRuntime(
            protocol_cls,
            pid,
            shard_id,
            config,
            listen_addr=("127.0.0.1", peer_ports[pid]),
            client_addr=("127.0.0.1", client_ports[pid]),
            peers=peers,
            sorted_processes=sorted_processes,
            workers=workers,
            executors=executors,
            multiplexing=multiplexing,
            peer_delays=(peer_delays or {}).get(pid),
            ping_sort=ping_sort,
            metrics_file=(
                f"{observe_dir}/metrics_p{pid}.gz" if observe_dir else None
            ),
            metrics_interval_ms=200,
            execution_log=(
                f"{observe_dir}/execution_p{pid}.log" if observe_dir else None
            ),
            trace_file=(
                f"{observe_dir}/trace_p{pid}.jsonl" if tracing else None
            ),
            # live telemetry: windowed series per process (plus the
            # client plane's below), and an optional exposition endpoint
            # per pid (metrics_ports={pid: port}; 0 = OS-assigned, read
            # the real one back from runtime.metrics_port)
            telemetry_file=(
                f"{observe_dir}/telemetry_p{pid}.jsonl" if observe_dir else None
            ),
            metrics_port=(metrics_ports or {}).get(pid),
            # flight recorder dumps land next to the traces they stitch
            # against (Config.flight_recorder resolves its own default
            # when no observe dir exists)
            flight_dir=(observe_dir if config.flight_recorder else None),
            **(runtime_kwargs or {}),
        )

    await asyncio.gather(*(runtime.start() for runtime in runtimes.values()))

    # one client pool per shard-0 process; each pool talks to the offset-o
    # process of every shard (mod.rs:1240-1290)
    client_groups: List[Tuple[List[ClientId], ProcessId]] = []
    next_client = 1
    for pid in shard_ids[0]:
        group = list(range(next_client, next_client + clients_per_process))
        next_client += clients_per_process
        client_groups.append((group, pid))

    # optional chaos driver runs alongside the clients (e.g. severing peer
    # links mid-run to exercise the reconnect path)
    chaos_task = (
        asyncio.ensure_future(chaos(runtimes)) if chaos is not None else None
    )
    client_task = asyncio.gather(
        *(
            run_clients(
                group,
                {
                    s: ("127.0.0.1", client_ports[shard_ids[s][offset_of[pid]]])
                    for s in range(shard_count)
                },
                workload,
                open_loop_interval_ms=open_loop_interval_ms,
                arrival_rate_per_s=arrival_rate_per_s,
                arrival_seed=arrival_seed,
                deadline_ms=deadline_ms,
                **({"tracer": client_tracer} if client_tracer is not None else {}),
                **(
                    {
                        "telemetry_file": (
                            f"{observe_dir}/telemetry_clients_p{pid}.jsonl"
                        ),
                        "telemetry_interval_ms": config.telemetry_interval_ms,
                    }
                    if observe_dir is not None
                    else {}
                ),
            )
            for group, pid in client_groups
        )
    )
    # a runtime failure (e.g. a typed QuorumLostError) must surface loudly
    # instead of hanging the clients forever
    failure_tasks = {
        asyncio.ensure_future(runtime.failed.wait()): pid
        for pid, runtime in runtimes.items()
    }
    try:
        done, _pending = await asyncio.wait(
            {client_task, *failure_tasks}, return_when=asyncio.FIRST_COMPLETED
        )
        if client_task not in done:
            failed = next(t for t in done if t in failure_tasks)
            pid = failure_tasks[failed]
            client_task.cancel()
            # reap the cancelled gather BEFORE raising: an un-awaited
            # cancellation can resurface as CancelledError during the
            # AssertionError's unwind and replace it out of asyncio.run
            try:
                await client_task
            except (asyncio.CancelledError, Exception):
                pass
            # a typed failure must also stop the survivors: their tasks
            # would otherwise outlive this coroutine and be cancelled by
            # the loop teardown mid-write
            await asyncio.gather(
                *(runtime.stop() for runtime in runtimes.values()),
                return_exceptions=True,
            )
            raise AssertionError(
                f"runtime p{pid} failed mid-run: {runtimes[pid].failure!r}"
            )
        results = client_task.result()
        if chaos_task is not None:
            await chaos_task
    finally:
        for task in failure_tasks:
            task.cancel()
        # on any failure path the chaos driver must not outlive the run
        # (it would keep poking runtimes that are being stopped)
        if chaos_task is not None and not chaos_task.done():
            chaos_task.cancel()
        # failure paths skip the clean close below: flush so the span
        # log's crash-consistent prefix covers everything emitted
        if client_tracer is not None:
            client_tracer.flush()

    await asyncio.sleep(extra_run_time_ms / 1000)
    # stop concurrently: a sequential shutdown leaves the last runtimes
    # watching already-stopped peers, and their failure detectors would
    # (correctly, but uselessly) report the shutdown as peer loss
    await asyncio.gather(*(runtime.stop() for runtime in runtimes.values()))
    if client_tracer is not None:
        client_tracer.close()

    clients: Dict[ClientId, Client] = {}
    for group in results:
        clients.update(group)
    return runtimes, clients


def run_overload_phase(
    protocol_cls,
    config: Config,
    workload: Workload,
    clients_per_process: int,
    arrival_rate_per_s: Optional[float] = None,
    arrival_seed: Optional[int] = None,
    deadline_ms: Optional[int] = None,
    extra_run_time_ms: int = 100,
) -> dict:
    """One measured load phase against a fresh localhost cluster — the
    shared instrument of ``bench.py bench_overload`` and
    ``scripts/overload_smoke.py`` (one implementation, so the CI gate and
    the bench row cannot drift on accounting semantics).

    Boots, drives the client pool (closed loop, or open-loop Poisson at
    ``arrival_rate_per_s`` per client), tears down; returns goodput,
    latency percentiles, the overload-plane tallies, and the depth
    high-watermarks split by queue family.  ``bound_violations`` lists
    queues whose depth high-watermark passed 2x their configured
    capacity: the capacity is a *pause watermark*, not a hard cap
    (``put_nowait`` never blocks — synchronous producers may overshoot
    while a gate drains, tallied as overflows), so bounded-ness is
    pinned as "never past 2x the watermark", while the truly hard bounds
    (the device submit ring, the admission limit) assert exactly.
    """
    runtimes, clients = asyncio.run(
        run_localhost_cluster(
            protocol_cls, config, workload, clients_per_process,
            arrival_rate_per_s=arrival_rate_per_s,
            arrival_seed=arrival_seed,
            deadline_ms=deadline_ms,
            extra_run_time_ms=extra_run_time_ms,
        )
    )
    latencies = sorted(
        value
        for client in clients.values()
        for value in client.data().latency_data()
    )
    # goodput over the SERVING span (first submit to last completion,
    # reconstructed from the client records) — not the harness wall,
    # which includes cluster boot/connect and would deflate the
    # saturation estimate the burst rates are calibrated against
    spans = [
        client.data().span_millis()
        for client in clients.values()
        if list(client.data().latency_data())
    ]
    wall_s = (
        (max(end for _s, end in spans) - min(start for start, _e in spans))
        / 1000.0
        if spans
        else 0.0
    )
    queue_hwm = unacked_hwm = 0
    violations = []
    for runtime in runtimes.values():
        for name, row in runtime.queue_stats().items():
            if name.startswith("unacked->"):
                unacked_hwm = max(unacked_hwm, row["depth_hwm"])
            else:
                queue_hwm = max(queue_hwm, row["depth_hwm"])
            if row["capacity"] and row["depth_hwm"] > 2 * row["capacity"]:
                violations.append((name, row["depth_hwm"], row["capacity"]))
    total = len(latencies)
    # device-plane counters folded across the cluster (None entries are
    # plane-off runtimes): the serving rows assert the plane actually
    # carried the run (dispatches > 0) instead of silently measuring the
    # host path
    from fantoch_tpu.observability.device import merge_counters

    device_counters: dict = {}
    for runtime in runtimes.values():
        per_runtime = runtime._device_counters()
        if per_runtime:
            # host-process-global: summing across co-hosted runtimes
            # would n-fold them (observability/device.py)
            per_runtime = dict(per_runtime)
            per_runtime.pop("jax_recompiles", None)
            per_runtime.pop("jax_compile_ms", None)
        merge_counters(device_counters, per_runtime)
    return {
        "completed": total,
        "device": device_counters,
        "goodput_cmds_per_s": int(total / wall_s) if wall_s > 0 else 0,
        "p50_ms": round(latencies[total // 2] / 1000.0, 2) if total else None,
        "p95_ms": (
            round(latencies[int(total * 0.95)] / 1000.0, 2) if total else None
        ),
        "p99_ms": (
            round(latencies[int(total * 0.99)] / 1000.0, 2) if total else None
        ),
        "sheds": sum(r.shed_submissions for r in runtimes.values()),
        "backpressure_pauses": sum(
            r.backpressure_pauses for r in runtimes.values()
        ),
        "client_retries": sum(c.overload_retries for c in clients.values()),
        "shed_commands": sum(c.shed_commands for c in clients.values()),
        "queue_depth_hwm": int(queue_hwm),
        "unacked_depth_hwm": int(unacked_hwm),
        "bound_violations": violations,
    }


async def run_device_server(
    config: Config,
    workload: Workload,
    client_count: int,
    *,
    protocol: str = "epaxos",
    batch_size: int = 64,
    key_buckets: int = 1024,
    key_width: int = 1,
    pending_capacity: int = 64,
    open_loop_interval_ms: Optional[int] = None,
    arrival_rate_per_s: Optional[float] = None,
    arrival_seed: Optional[int] = None,
    deadline_ms: Optional[int] = None,
    monitor_execution_order: bool = True,
    telemetry_file: Optional[str] = None,
    metrics_port: Optional[int] = None,
    trace_file: Optional[str] = None,
    flight_dir: Optional[str] = None,
):
    """Boot the TPU serving path (run/device_runner.py) on a localhost
    port and drive real TCP clients against it; returns
    ``(DeviceRuntime, clients)``.  A runtime failure tears the run down
    loudly instead of stalling the clients."""
    from fantoch_tpu.run.device_runner import DeviceRuntime

    port = free_port()
    runtime = DeviceRuntime(
        config,
        ("127.0.0.1", port),
        protocol=protocol,
        batch_size=batch_size,
        key_buckets=key_buckets,
        key_width=key_width,
        pending_capacity=pending_capacity,
        monitor_execution_order=monitor_execution_order,
        telemetry_file=telemetry_file,
        metrics_port=metrics_port,
        trace_file=trace_file,
        flight_dir=flight_dir,
    )
    await runtime.start()
    client_task = asyncio.ensure_future(
        run_clients(
            list(range(1, client_count + 1)),
            # the unified mesh server owns every shard: all shard ids map
            # to its one address (clients open one connection per shard)
            {s: ("127.0.0.1", port) for s in range(config.shard_count)},
            workload,
            open_loop_interval_ms=open_loop_interval_ms,
            arrival_rate_per_s=arrival_rate_per_s,
            arrival_seed=arrival_seed,
            deadline_ms=deadline_ms,
        )
    )
    failure_task = asyncio.ensure_future(runtime.failed.wait())
    try:
        done, _pending = await asyncio.wait(
            {client_task, failure_task}, return_when=asyncio.FIRST_COMPLETED
        )
        if failure_task in done:
            client_task.cancel()
            raise AssertionError(f"device runtime failed: {runtime.failure!r}")
        clients = client_task.result()
    finally:
        failure_task.cancel()
        await runtime.stop()
    return runtime, clients
