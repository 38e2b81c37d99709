"""Multi-host (replica, batch) meshes: DCN for quorums, ICI for batches.

The reference scales across machines with one NCCL/MPI-style TCP link per
replica pair (fantoch/src/run/mod.rs:105-445 — every process connects to
every peer); collectives do not exist, so topology never matters. Here the
device plane IS collective (parallel/mesh_step.py), so on a multi-host
TPU deployment the mesh layout decides which interconnect each collective
rides:

* the **replica axis carries the quorum fan-ins** — masked ``pmax/pmin``
  agreement, ``psum`` accept counts, GC stability ``pmin`` — all small
  frontier-shaped reductions that model WAN consensus rounds in the first
  place.  They are latency-bound and tiny, exactly what DCN (between
  hosts) is acceptable for; replicas are also distinct failure domains,
  which only makes sense across hosts.
* the **batch axis carries the bandwidth** — the per-shard sorts, gathers
  and scatters over the command batch.  Those want ICI, i.e. must stay
  within one host's chips.

``make_multihost_mesh`` therefore maps processes (hosts) to the replica
axis and each host's local chips to the batch axis.  ``make_mesh``
(mesh_step.py) keeps its single-host behavior; this module is additive
and degrades to it when only one process is present, so everything
dryrun/CI runs today is unchanged.

Bootstrap: on real multi-host slices call :func:`distributed_init` (a
thin, idempotent gate around ``jax.distributed.initialize``) on every
host before building the mesh — the standard jax multi-controller
recipe.  Every driver in run/device_runner.py accepts ``mesh=`` and every
``init_*_state``/``jit_*_step`` in mesh_step.py takes the mesh it is
given, so a multi-host mesh drops into the existing serving stack
unchanged.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax

from fantoch_tpu.parallel.mesh_step import (
    BATCH_AXIS,
    REPLICA_AXIS,
    Mesh,
    make_mesh,
)
from fantoch_tpu.utils import logger

_DISTRIBUTED_INITIALIZED = False


# auto-detected clusters get a short barrier timeout: a CI runner that
# merely *carries* SLURM env vars (no actual peers) must fail fast and
# fall back to single-host instead of blocking on jax's ~300 s default
AUTO_DETECT_INIT_TIMEOUT_S = 30


def distributed_init(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    initialization_timeout_s: Optional[int] = None,
) -> bool:
    """Idempotently initialize jax's multi-controller runtime.

    Returns True when ``jax.distributed.initialize`` ran (or had already
    run via this gate), False when single-process operation was detected
    (no coordinator and no cluster env) and nothing was done — callers can
    use the same code path on laptops, CI and pods.

    Timeouts: with an explicit ``coordinator_address`` the operator named
    a real cluster, so jax's long default barrier (~300 s, slow pod
    boots) stands unless ``initialization_timeout_s`` overrides it.  On
    the auto-detect path (cluster env vars only) the barrier is capped at
    ``AUTO_DETECT_INIT_TIMEOUT_S`` so a stray SLURM_JOB_ID on a
    peer-less runner degrades to single-host in seconds, not minutes.
    """
    global _DISTRIBUTED_INITIALIZED
    if _DISTRIBUTED_INITIALIZED:
        return True
    import os

    # cluster hints jax.distributed.initialize can auto-detect from
    # (explicit coordinator > jax's own env > SLURM > TPU pod metadata)
    cluster_env = ("JAX_COORDINATOR_ADDRESS", "SLURM_JOB_ID", "TPU_WORKER_HOSTNAMES")
    if coordinator_address is None and not any(
        v in os.environ for v in cluster_env
    ):
        # no explicit coordinator and no cluster environment: single host
        return False
    kwargs = {}
    if initialization_timeout_s is not None:
        kwargs["initialization_timeout"] = initialization_timeout_s
    elif coordinator_address is None:
        kwargs["initialization_timeout"] = AUTO_DETECT_INIT_TIMEOUT_S
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            **kwargs,
        )
    except (ValueError, RuntimeError) as exc:
        if coordinator_address is not None:
            raise  # the operator asked for a specific cluster: fail loudly
        # a half-present cluster env (e.g. a single-chip rig that sets
        # TPU_WORKER_HOSTNAMES) from which jax cannot derive a
        # coordinator: fall back to single-host rather than killing the
        # server over a hint
        logger.warning(
            "cluster env detected but jax.distributed could not "
            "initialize (%r); continuing single-host", exc,
        )
        return False
    _DISTRIBUTED_INITIALIZED = True
    return True


def group_by_process(devices: Sequence) -> list:
    """Group a device list by ``process_index``, each group sorted by
    device id, groups ordered by process index.  Raises on ragged
    topologies (hosts with different chip counts) — a mesh needs a
    rectangle, and a ragged slice means the deployment is broken."""
    by_proc: dict = {}
    for d in devices:
        by_proc.setdefault(d.process_index, []).append(d)
    groups = [
        sorted(by_proc[p], key=lambda d: d.id) for p in sorted(by_proc)
    ]
    sizes = {len(g) for g in groups}
    if len(sizes) > 1:
        raise ValueError(
            f"ragged multi-host topology: per-host chip counts {sorted(sizes)}"
        )
    return groups


def make_multihost_mesh(
    num_replicas: Optional[int] = None, shard_count: int = 1
) -> Mesh:
    """(replica, batch) mesh with hosts on the replica axis.

    Single-process: defers to ``make_mesh`` (identical behavior, so CI /
    dryrun / the virtual-device suite are unaffected).  Multi-process:
    process p's chips form row p — the replica axis crosses hosts (DCN,
    quorum fan-ins), the batch axis stays on-host (ICI, batch sorts).

    ``num_replicas`` is the mesh's **total replica-axis row count**.  In
    sharded mode the device state holds ``n * shard_count`` rows in
    shard-major order (mesh_step.shard_of_row: shard s owns rows
    ``[s*n, (s+1)*n)``) — callers must size the mesh against that total,
    NOT the per-shard ``n`` (run/device_runner.py ``_init_sharded_mesh``
    builds ``shard_count * num_replicas`` rows).  When given it must be a
    multiple of the host count, mirroring ``make_mesh``'s divisibility
    contract (init_state shards whole replica blocks per row), and with
    ``shard_count > 1`` each host row should additionally hold whole
    shard blocks, or a shard's quorum fan-in straddles hosts and rides
    DCN instead of ICI (warned, not fatal: it is a performance contract,
    not a correctness one).
    """
    import numpy as np

    devices = jax.devices()
    groups = group_by_process(devices)
    if len(groups) == 1:
        return make_mesh(num_replicas=num_replicas, shard_count=shard_count)
    hosts = len(groups)
    if num_replicas is not None:
        if num_replicas % hosts != 0:
            raise ValueError(
                f"num_replicas={num_replicas} (total replica rows, i.e. "
                f"n * shard_count) must be a multiple of the host count "
                f"{hosts} (whole replica blocks per mesh row)"
            )
        if shard_count > 1:
            rows_per_host = num_replicas // hosts
            per_shard = num_replicas // shard_count
            if rows_per_host % per_shard != 0:
                logger.warning(
                    "multihost mesh: %d rows/host does not hold whole "
                    "shard blocks of %d rows (shard-major order, "
                    "mesh_step.shard_of_row) — sharded quorum fan-ins "
                    "will cross hosts on DCN instead of staying on ICI",
                    rows_per_host,
                    per_shard,
                )
    dev_array = np.array(groups)  # (hosts, chips_per_host)
    return Mesh(dev_array, (REPLICA_AXIS, BATCH_AXIS))
