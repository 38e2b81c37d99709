"""Shared CLI plumbing: protocol registry, config flags, device start-up.

Reference: fantoch_ps/src/bin/common/protocol.rs:126-368 (the full server
flag set) and common/mod.rs.  Which device a binary runs on is decided
by ``JAX_PLATFORMS`` alone (fantoch_tpu/hostenv.py).
"""

from __future__ import annotations

import argparse
from typing import Dict, Optional, Tuple


def start_device_entry(entry: str) -> Dict[str, object]:
    """Start-up of a binary that dispatches to a device: the platform
    rule (TPU unless the caller set ``JAX_PLATFORMS=cpu``; exits
    non-zero otherwise) and then the persistent compile cache — a
    server's first dispatch otherwise pays a full cold compile INSIDE
    the serving loop, starving the heartbeat task until peers declare
    the process dead.  Returns the device report plus the cache
    directory in effect, for the binary's banner."""
    from fantoch_tpu.core.compile_cache import ensure_compile_cache
    from fantoch_tpu.hostenv import require_device_platform

    report = require_device_platform(entry)
    report["compile_cache_dir"] = ensure_compile_cache()
    return report


def protocol_by_name(name: str):
    from fantoch_tpu.protocol import Atlas, Basic, Caesar, EPaxos, FPaxos, Newt

    registry = {
        "basic": Basic,
        "epaxos": EPaxos,
        "atlas": Atlas,
        "newt": Newt,
        "caesar": Caesar,
        "fpaxos": FPaxos,
    }
    if name not in registry:
        raise SystemExit(f"unknown protocol {name!r}; one of {sorted(registry)}")
    return registry[name]


def add_config_flags(parser: argparse.ArgumentParser) -> None:
    """The Config-backed flags (common/protocol.rs:126-368)."""
    parser.add_argument("--processes", "-n", type=int, required=True, help="replicas per shard")
    parser.add_argument("--faults", "-f", type=int, required=True)
    parser.add_argument("--shard-count", type=int, default=1)
    parser.add_argument("--execute-at-commit", action="store_true")
    parser.add_argument("--executor-executed-notification-interval", type=int, default=50, metavar="MS")
    parser.add_argument("--executor-cleanup-interval", type=int, default=5, metavar="MS")
    parser.add_argument("--executor-monitor-execution-order", action="store_true")
    parser.add_argument("--gc-interval", type=int, default=50, metavar="MS")
    parser.add_argument("--leader", type=int, default=None, help="leader process (FPaxos)")
    parser.add_argument(
        "--fpaxos-leader-timeout", type=int, default=None, metavar="MS",
        help="FPaxos leader failover: heartbeat at a quarter of this, "
        "followers elect after ring-staggered silence (also unlocks the "
        "crash-restart rejoin via MSlotSync; requires --gc-interval)",
    )
    parser.add_argument("--newt-tiny-quorums", action="store_true")
    parser.add_argument("--newt-clock-bump-interval", type=int, default=None, metavar="MS")
    parser.add_argument("--newt-detached-send-interval", type=int, default=None, metavar="MS")
    parser.add_argument("--caesar-wait-condition", action="store_true", default=True)
    parser.add_argument("--no-caesar-wait-condition", dest="caesar_wait_condition", action="store_false")
    parser.add_argument("--skip-fast-ack", action="store_true")
    parser.add_argument("--batched-graph-executor", action="store_true",
                        help="order committed commands with the batched device resolver")
    parser.add_argument("--device-pred-plane", action="store_true",
                        help="Caesar resident predecessors plane "
                        "(executor/pred_plane.py): the pending window "
                        "stays on device across batches; commits drain "
                        "as column batches")
    parser.add_argument("--device-graph-plane", action="store_true",
                        default=None,
                        help="EPaxos/Atlas resident graph plane "
                        "(executor/graph/graph_plane.py): the dependency "
                        "backlog stays on device across feeds; requires "
                        "--batched-graph-executor and shard-count 1; "
                        "default off")
    parser.add_argument("--graph-kernel-threshold", type=int, default=None,
                        metavar="N",
                        help="backlog size gating exact structure metrics "
                        "and the resident general path in the batched "
                        "graph executor; default 4096")
    parser.add_argument("--serving-pipeline-depth", type=int, default=None,
                        metavar="K",
                        help="device serving pipeline depth (run/pipeline.py): "
                        "dispatched-but-undrained rounds kept in flight; "
                        "default 1.  Setting it also turns the overlap "
                        "on where the backend is the CPU (off the CPU it "
                        "is on already)")
    parser.add_argument("--ingest-deadline", type=float, default=None,
                        metavar="MS", dest="ingest_deadline_ms",
                        help="adaptive ingest batching deadline budget "
                        "(run/ingest.py): a queued submission waits at most "
                        "this long for its round to fill; default 2.0; "
                        "0 disables batching")
    parser.add_argument("--ingest-target", type=int, default=None,
                        metavar="N", dest="ingest_target",
                        help="fixed ingest size target (rows that release "
                        "a round), overriding the EWMA-adaptive target; "
                        "default adaptive")
    parser.add_argument("--serving-chain-max", type=int, default=None,
                        metavar="S", dest="serving_chain_max",
                        help="ceiling on the auto-tuned serving chain "
                        "length (rounds fused per device dispatch); "
                        "default 8; 1 disables chaining")
    parser.add_argument("--wal-sync", default=None,
                        choices=("always", "interval", "never"),
                        help="durable command-log fsync policy (run/wal.py); "
                        "default FANTOCH_WAL_SYNC env, else 'interval'; only "
                        "consulted when the server runs with --wal-dir")
    parser.add_argument("--queue-capacity", type=int, default=None,
                        metavar="N",
                        help="high watermark of the run-layer bounded queues "
                        "(run/backpressure.py): readers pause past it; "
                        "default 8192, 0 = unbounded legacy")
    parser.add_argument("--admission-limit", type=int, default=None,
                        metavar="N",
                        help="client-edge admission depth: past it new "
                        "submissions are shed with a typed Overloaded "
                        "reply + retry-after hint; omit to disable shedding")
    parser.add_argument("--overload-retry-after", type=int, default=100,
                        metavar="MS",
                        help="base retry-after hint on Overloaded replies")
    parser.add_argument("--link-unacked-cap", type=int, default=None,
                        metavar="N",
                        help="cap on a peer link's unacked resend window "
                        "(run/links.py): past it the link is declared lost "
                        "via the typed path; default 32768, 0 = uncapped")
    parser.add_argument("--telemetry-interval", type=int, default=None,
                        metavar="MS",
                        help="live-telemetry window cadence "
                        "(observability/timeseries.py): one knob for the "
                        "windowed series emit AND the legacy metrics "
                        "snapshot; default = the runtime's "
                        "--metrics-interval (run) or 1000ms (sim)")
    parser.add_argument("--execution-digests", action="store_true",
                        help="consistency-audit plane (core/audit.py): "
                        "per-key hash chains over executed writes, "
                        "exchanged on the heartbeat path — a forked "
                        "replica surfaces a typed DivergenceError naming "
                        "the first diverging key+command")
    parser.add_argument("--audit-commits", action="store_true",
                        help="record every commit decision (dot/slot -> "
                        "(rifl, value), surviving GC) so divergence "
                        "errors resolve dots and the auditor can check "
                        "commit-value agreement (audit/test only: the "
                        "log grows with the run)")
    parser.add_argument("--trace", type=float, default=0.0, metavar="RATE",
                        dest="trace_sample_rate",
                        help="per-dot lifecycle tracing sample rate "
                        "(0.0-1.0; Config.trace_sample_rate).  Servers "
                        "also need --trace-file; 1.0 stitches every span "
                        "for `bin/obs.py critpath`")
    parser.add_argument("--flight-recorder", action="store_true",
                        help="failure flight recorder "
                        "(observability/recorder.py): bounded in-memory "
                        "ring of recent UNSAMPLED trace events, dumped as "
                        "flight_p<pid>.json on typed failures, WAL-restart "
                        "boots, and SIGUSR1 (capacity: "
                        "FANTOCH_FLIGHT_EVENTS)")


def config_from_args(args: argparse.Namespace):
    from fantoch_tpu.core import Config

    return Config(
        n=args.processes,
        f=args.faults,
        shard_count=args.shard_count,
        execute_at_commit=args.execute_at_commit,
        executor_executed_notification_interval_ms=args.executor_executed_notification_interval,
        executor_cleanup_interval_ms=args.executor_cleanup_interval,
        executor_monitor_execution_order=args.executor_monitor_execution_order,
        gc_interval_ms=args.gc_interval,
        leader=args.leader,
        fpaxos_leader_timeout_ms=args.fpaxos_leader_timeout,
        newt_tiny_quorums=args.newt_tiny_quorums,
        newt_clock_bump_interval_ms=args.newt_clock_bump_interval,
        newt_detached_send_interval_ms=args.newt_detached_send_interval,
        caesar_wait_condition=args.caesar_wait_condition,
        skip_fast_ack=args.skip_fast_ack,
        batched_graph_executor=args.batched_graph_executor,
        device_graph_plane=args.device_graph_plane,
        graph_kernel_threshold=args.graph_kernel_threshold,
        device_pred_plane=args.device_pred_plane,
        serving_pipeline_depth=args.serving_pipeline_depth,
        ingest_deadline_ms=args.ingest_deadline_ms,
        ingest_target=args.ingest_target,
        serving_chain_max=args.serving_chain_max,
        wal_sync=args.wal_sync,
        queue_capacity=args.queue_capacity,
        admission_limit=args.admission_limit,
        overload_retry_after_ms=args.overload_retry_after,
        link_unacked_cap=args.link_unacked_cap,
        execution_digests=args.execution_digests,
        audit_log_commits=args.audit_commits,
        telemetry_interval_ms=args.telemetry_interval,
        trace_sample_rate=args.trace_sample_rate,
        flight_recorder=args.flight_recorder,
    )


def parse_peer(entry: str) -> Tuple[int, str, int, Optional[int]]:
    """'pid=host:port' or 'pid=host:port:delay_ms' -> (pid, host, port, delay)."""
    pid_s, addr = entry.split("=", 1)
    parts = addr.split(":")
    if len(parts) == 2:
        host, port = parts
        delay = None
    elif len(parts) == 3:
        host, port, delay_s = parts
        delay = int(delay_s)
    else:
        raise SystemExit(f"bad peer address {entry!r} (pid=host:port[:delay_ms])")
    return int(pid_s), host, int(port), delay


def parse_shard_addr(entry: str) -> Tuple[int, str, int]:
    """'shard=host:port' -> (shard, host, port)."""
    shard_s, addr = entry.split("=", 1)
    host, port_s = addr.rsplit(":", 1)
    return int(shard_s), host, int(port_s)


def parse_sorted(entry: str) -> list:
    """'1:0,2:0,3:0' -> [(pid, shard), ...]."""
    out = []
    for item in entry.split(","):
        pid_s, shard_s = item.split(":")
        out.append((int(pid_s), int(shard_s)))
    return out


def parse_id_range(entry: str) -> list:
    """'1-3' or '7' -> [ids]."""
    if "-" in entry:
        lo, hi = entry.split("-")
        return list(range(int(lo), int(hi) + 1))
    return [int(entry)]


def maybe_log_file(path: Optional[str]) -> None:
    if path:
        import logging

        handler = logging.FileHandler(path)
        handler.setFormatter(logging.Formatter("%(asctime)s %(levelname)s %(message)s"))
        logging.getLogger("fantoch_tpu").addHandler(handler)
        logging.getLogger("fantoch_tpu").setLevel(logging.INFO)
