"""Run-layer observability: metrics snapshots and execution logging/replay.

Reference:
- fantoch/src/run/task/metrics_logger.rs:75-87 — every interval, serialize
  the process's worker + executor metrics to a tmp file and atomically
  rename over the target (crash-consistent snapshots);
- fantoch/src/run/task/execution_logger.rs:8-29 — append every
  ExecutionInfo to a log file for offline debugging;
- fantoch_ps/src/bin/graph_executor_replay.rs:14-38 — replay such a log
  through a fresh executor.

Serialization is pickle (the runner's wire codec); metrics snapshots are
gzip'd like the reference's gzip+bincode.
"""

from __future__ import annotations

import gzip
import os
import pickle
from dataclasses import dataclass
from typing import Any, BinaryIO, Dict, Iterator, List, Optional

from fantoch_tpu.core.config import Config
from fantoch_tpu.core.ids import ProcessId, ShardId
from fantoch_tpu.core.metrics import Metrics
from fantoch_tpu.core.timing import RunTime


@dataclass
class ProcessMetrics:
    """One metrics snapshot: protocol ("workers") + executor metrics
    (metrics_logger.rs:12-30), plus the device-plane counters
    (fantoch_tpu/observability/device.py: dispatch counts, batch
    occupancy, recompiles, kernel wall-ms — no reference counterpart;
    the reference has no device planes).  ``device`` is None on
    planes-off runs and on snapshots written before the field existed
    (``read_metrics_snapshot`` backfills it on read)."""

    workers: List[Metrics]
    executors: List[Metrics]
    device: Optional[Dict[str, float]] = None
    # overload-control plane (run/backpressure.py): per-queue depth /
    # depth-high-watermark / pause / overflow gauges (``queues``) plus
    # the process-level shed/backpressure running totals (``overload``).
    # WarnQueue used to only *log* a falling-behind consumer; these make
    # it a gauge that survives the run.  None on snapshots written
    # before the fields existed (dataclass defaults cover old pickles)
    queues: Optional[Dict[str, Dict[str, float]]] = None
    overload: Optional[Dict[str, float]] = None
    # what produced the ``device`` counters: platform, device_kind and
    # device_count as jax reports them, and the mesh shape (None here —
    # the executor planes run on the default device; the device-step
    # runtime's JSON snapshot carries its (replica x batch) mesh).  None
    # whenever ``device`` is None
    backend: Optional[Dict[str, Any]] = None


def write_metrics_snapshot(path: str, metrics: ProcessMetrics) -> None:
    """Write-tmp-then-rename for crash consistency
    (metrics_logger.rs:75-87)."""
    tmp = path + ".tmp"
    with gzip.open(tmp, "wb") as fh:
        pickle.dump(metrics, fh)
    os.replace(tmp, path)


def write_json_snapshot(path: str, obj) -> None:
    """Crash-consistent JSON snapshot (same tmp+rename discipline as the
    pickle variant); used by the device-serving runtime, whose metrics are
    round/path tallies rather than per-message histograms."""
    import json

    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(obj, fh)
    os.replace(tmp, path)


def read_metrics_snapshot(path: str) -> ProcessMetrics:
    with gzip.open(path, "rb") as fh:
        out = pickle.load(fh)
    assert isinstance(out, ProcessMetrics)
    # snapshots written before the device-counter field existed unpickle
    # without it in __dict__; reads still see None via the dataclass
    # class-attribute default, so no backfill is needed
    return out


class ExecutionLogger:
    """Appends execution infos to a log file (execution_logger.rs:8-29:
    8KB buffering, flush on close; one pickle frame per batch)."""

    def __init__(self, path: str):
        self._fh: BinaryIO = open(path, "wb", buffering=8192)

    def log(self, infos: List[Any]) -> None:
        pickle.dump(infos, self._fh)

    def flush(self) -> None:
        self._fh.flush()

    def close(self) -> None:
        self._fh.close()


def read_execution_log(path: str) -> Iterator[List[Any]]:
    with open(path, "rb") as fh:
        while True:
            try:
                yield pickle.load(fh)
            except EOFError:
                return


def replay_execution_log(
    path: str,
    protocol_cls: type,
    process_id: ProcessId,
    shard_id: ShardId,
    config: Config,
) -> Dict[str, Any]:
    """Replay a log through one fresh executor
    (graph_executor_replay.rs:14-38); returns summary stats.  Replay is
    inherently single-executor: the log already merges every executor
    task's batches in arrival order."""
    executor = protocol_cls.Executor(process_id, shard_id, config)
    executor.set_executor_index(0)
    time = RunTime()
    handled = 0
    results = 0
    for infos in read_execution_log(path):
        handled += len(infos)
        executor.handle_batch(infos, time)
        results += sum(1 for _ in executor.to_clients_iter())
    return {
        "batches_handled": handled,
        "results": results,
        "metrics": executor.metrics(),
    }
