"""Multi-process host scaling for the ordering path: the pool.rs analog
at OS-process granularity.

Reference: fantoch/src/run/pool.rs:115-124 scales one process across 16
worker/16 executor THREADS with Atomic/Locked shared-state variants; this
framework's intra-process parallelism axis is the batch (one core moves
~13-18M cmds/s through the array ordering path, README design notes), so
the multicore unit here is the PROCESS: ``OrderingPool`` spawns N worker
processes, each owning the key buckets ``hash % N == i`` (the same
key-partitioned executor routing as run/routing.py, at process
granularity), and drives each worker's own ``BatchedDependencyGraph``
over array chunks shipped through pipes.  Keys never span workers, so
per-key execution order is exact by construction — the same argument as
the reference's key-partitioned executors (fantoch/src/executor/
mod.rs:161-166) — and aggregate ordering throughput scales with cores.

The pool is deliberately transport-simple (pickled numpy columns over
``multiprocessing`` pipes): the ordering work per chunk is O(batch) with
large constants, so IPC is a few percent at 256k-row chunks.  Workers
run on the CPU: N processes cannot share one chip (fantoch_tpu/hostenv.py),
and host cores are the resource this pool scales over.
"""

from __future__ import annotations

import multiprocessing as mp
from typing import List, Optional, Sequence, Tuple

import numpy as np

from fantoch_tpu.run.ingest import AdaptiveIngestBatcher, plan_ingest_releases


def _worker_main(conn, worker_index: int) -> None:
    """Worker process: owns one key shard's ordering graph."""
    from fantoch_tpu.hostenv import force_cpu_platform

    force_cpu_platform()
    from fantoch_tpu.core import Command, Config, KVOp, Rifl, RunTime
    from fantoch_tpu.executor.graph.batched import BatchedDependencyGraph
    from fantoch_tpu.ops.frontier import pack_dots

    shard = 0
    config = Config(5, 2, batched_graph_executor=True)
    graph = BatchedDependencyGraph(1, shard, config)
    graph.record_order_arrays = True
    clock = RunTime()
    arena: List[Command] = []
    try:
        while True:
            msg = conn.recv()
            kind = msg[0]
            if kind == "stop":
                break
            try:
                if kind == "arena":
                    # the command arena exists at submit time in any
                    # design (bench_integrated_executor's accounting);
                    # build it outside the timed region
                    (_, n) = msg
                    arena = [
                        Command.from_keys(
                            Rifl(1, i + 1), shard, {f"k{i}": (KVOp.put(""),)}
                        )
                        for i in range(n)
                    ]
                    conn.send(("ready", worker_index))
                elif kind == "add":
                    (_, src, seq, key, dep_rows) = msg
                    b = len(src)
                    assert b <= len(arena), (
                        f"arena {len(arena)} < chunk {b}: call prepare() "
                        "with the largest shard size first"
                    )
                    has_dep = dep_rows >= 0
                    dep_idx = np.where(has_dep, dep_rows, 0)
                    dep_dots = np.where(
                        has_dep, pack_dots(src[dep_idx], seq[dep_idx]), -1
                    ).reshape(-1, 1)
                    graph.handle_add_arrays(
                        src, seq, key, dep_dots, arena[:b], clock
                    )
                    graph.resolve_now(clock)
                    order_src, order_seq = graph.take_order_arrays()
                    conn.send(("done", order_src, order_seq))
                else:
                    raise AssertionError(f"unknown pool message {kind!r}")
            except Exception:  # noqa: BLE001 — ship the traceback home
                import traceback

                conn.send(("error", traceback.format_exc(), None))
                raise
    finally:
        conn.close()


class OrderingPool:
    """N key-sharded ordering worker processes behind one front."""

    def __init__(self, workers: int):
        assert workers >= 1
        self.workers = workers
        ctx = mp.get_context("spawn")
        self._conns = []
        self._procs = []
        for i in range(workers):
            parent, child = ctx.Pipe()
            proc = ctx.Process(
                target=_worker_main, args=(child, i), daemon=True
            )
            proc.start()
            child.close()
            self._conns.append(parent)
            self._procs.append(proc)

    def prepare(self, rows_per_worker: int) -> None:
        """Build each worker's command arena (untimed); must cover the
        largest shard any later run will ship."""
        for conn in self._conns:
            conn.send(("arena", rows_per_worker))
        for conn in self._conns:
            msg = conn.recv()
            if msg[0] == "error":
                raise RuntimeError(f"pool worker failed:\n{msg[1]}")
            assert msg[0] == "ready"

    @staticmethod
    def shard_columns(
        key: np.ndarray,
        src: np.ndarray,
        seq: np.ndarray,
        dep_rows: np.ndarray,
        workers: int,
    ) -> List[Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]]:
        """Partition a workload by key bucket and remap the dependency
        row indices into each shard's local numbering (a key's whole
        conflict chain lands in exactly one shard, so every dependency
        stays local)."""
        shard_of = key % workers
        # the sharding is only sound for latest-per-SAME-key dep chains
        # (a key's whole chain lands in one shard); anything else would
        # remap into the wrong shard's numbering — fail loudly instead
        has_any = dep_rows >= 0
        assert (
            key[dep_rows[has_any]] == key[has_any]
        ).all(), "dependency crosses keys: not shardable by key bucket"
        out = []
        # global row -> local row within its shard
        local = np.empty(len(key), dtype=np.int64)
        for w in range(workers):
            rows = np.flatnonzero(shard_of == w)
            local[rows] = np.arange(len(rows))
            dep = dep_rows[rows]
            has = dep >= 0
            remapped = np.where(has, local[np.where(has, dep, 0)], -1)
            out.append(
                (key[rows], src[rows], seq[rows], remapped)
            )
        return out

    def run_shards(self, shards) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Dispatch one pre-sharded workload and wait for every worker's
        (order_src, order_seq); wall time across the call is the
        aggregate ordering latency."""
        self.submit_shards(shards)
        return self.drain_shards()

    def submit_shards(self, shards) -> None:
        """Ship one pre-sharded workload to the workers WITHOUT waiting —
        the dispatch half of the run/pipeline.py dispatch/drain split at
        process granularity.  Each pipe is FIFO, so workloads drain in
        submission order; ``drain_shards`` retires the oldest."""
        assert len(shards) == self.workers
        for conn, (key, src, seq, dep) in zip(self._conns, shards):
            conn.send(("add", src, seq, key, dep))

    def drain_shards(self) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Wait for every worker's (order_src, order_seq) of the oldest
        submitted workload."""
        orders = []
        for conn in self._conns:
            kind, order_src, order_seq = conn.recv()
            if kind == "error":
                raise RuntimeError(f"pool worker failed:\n{order_src}")
            assert kind == "done"
            orders.append((order_src, order_seq))
        return orders

    def run_shards_pipelined(
        self, workloads, depth: int = 1
    ) -> List[List[Tuple[np.ndarray, np.ndarray]]]:
        """Run a sequence of pre-sharded workloads keeping up to
        ``depth`` of them in flight across the worker processes: IPC
        serialization of workload k+1 overlaps the workers' ordering of
        workload k (the serving loop's depth-K overlap applied to the
        host pool).  Results come back in submission order.

        Sends run on a feeder thread: the worker loop is strict
        recv->process->send, so a single-threaded submit-then-drain
        deadlocks as soon as a pickled workload and a pending result
        together exceed the pipe's socket buffer (each side blocked in
        send, neither reading).  With the feeder owning the send
        direction and this thread the recv direction, the main thread is
        always free to drain — each duplex Connection is used by exactly
        one thread per direction, never the same operation concurrently.
        A semaphore caps submitted-but-undrained workloads at
        ``depth + 1`` (depth remain in flight while one drains — the
        PipelineCore convention, so depth=1 really does overlap the IPC
        of workload k+1 with the workers' ordering of workload k); the
        drain loop never blocks on a workload the feeder has not
        confirmed submitting, so a feeder failure raises instead of
        hanging the caller."""
        assert depth >= 1
        import threading

        workloads = list(workloads)
        sem = threading.Semaphore(depth + 1)
        cond = threading.Condition()
        submitted = [0]
        feeder_error: List[BaseException] = []

        def feeder() -> None:
            try:
                for workload in workloads:
                    sem.acquire()
                    self.submit_shards(workload)
                    with cond:
                        submitted[0] += 1
                        cond.notify()
            except BaseException as exc:  # noqa: BLE001 — rethrown below
                with cond:
                    feeder_error.append(exc)
                    cond.notify()

        thread = threading.Thread(target=feeder, daemon=True)
        thread.start()
        results: List[List[Tuple[np.ndarray, np.ndarray]]] = []
        try:
            for i in range(len(workloads)):
                with cond:
                    while submitted[0] <= i and not feeder_error:
                        cond.wait()
                    if submitted[0] <= i:
                        # feeder died before this workload went out: the
                        # workers will never answer it — raise, don't hang
                        raise RuntimeError(
                            "pool feeder failed"
                        ) from feeder_error[0]
                results.append(self.drain_shards())
                sem.release()
        finally:
            thread.join(timeout=60)
        if feeder_error:
            raise RuntimeError("pool feeder failed") from feeder_error[0]
        return results

    def run_shards_adaptive(
        self,
        key: np.ndarray,
        src: np.ndarray,
        seq: np.ndarray,
        dep_rows: np.ndarray,
        arrival_ms: Sequence[float],
        batcher: AdaptiveIngestBatcher,
        depth: int = 1,
    ) -> Tuple[
        List[Tuple[float, int, int]],
        List[List[Tuple[np.ndarray, np.ndarray]]],
    ]:
        """Coalesce an arrival-stamped workload into ingest rounds and
        run them through the pipelined pool: the adaptive batcher's
        size-or-deadline policy (run/ingest.py) replayed offline over the
        sorted ``arrival_ms`` column decides the round boundaries, each
        round is key-sharded and shipped, and up to ``depth`` rounds stay
        in flight.  Returns ``(release plan, per-round orders)`` with the
        plan's half-open ``(release_ms, start, end)`` groups indexing the
        input rows.

        A dependency row that falls in an *earlier* round is dropped
        (-1): each pipe is FIFO, so by the time a round reaches its
        worker every earlier round's rows are already ordered there —
        submission order satisfies the cross-round edge by construction,
        exactly as an earlier dispatch satisfies a dependency in the
        device serving loop."""
        plan = plan_ingest_releases(arrival_ms, batcher)
        workloads = []
        for _release_ms, start, end in plan:
            dep = dep_rows[start:end]
            in_round = dep >= start
            dep = np.where(in_round, dep - start, -1)
            workloads.append(
                self.shard_columns(
                    key[start:end], src[start:end], seq[start:end],
                    dep, self.workers,
                )
            )
        return plan, self.run_shards_pipelined(workloads, depth=depth)

    def close(self) -> None:
        for conn in self._conns:
            try:
                conn.send(("stop",))
            except (BrokenPipeError, OSError):
                pass
        for proc in self._procs:
            proc.join(timeout=30)
            if proc.is_alive():
                proc.terminate()
        for conn in self._conns:
            conn.close()

    def __enter__(self) -> "OrderingPool":
        return self

    def __exit__(self, *exc) -> Optional[bool]:
        self.close()
        return None
