"""Multi-host mesh layout (parallel/multihost.py).

The virtual 8-device CPU backend is one process, so the true multi-host
branch is exercised through fake device records; the single-process path
runs against the real backend and must match make_mesh exactly — the
module's degrade-to-single-host contract."""

from dataclasses import dataclass

import numpy as np
import pytest

from fantoch_tpu.parallel.mesh_step import BATCH_AXIS, REPLICA_AXIS, make_mesh
from fantoch_tpu.parallel.multihost import (
    group_by_process,
    make_multihost_mesh,
)


@dataclass(frozen=True)
class FakeDev:
    id: int
    process_index: int


def test_single_process_defers_to_make_mesh():
    mesh = make_multihost_mesh(num_replicas=4)
    ref = make_mesh(num_replicas=4)
    assert mesh.axis_names == ref.axis_names == (REPLICA_AXIS, BATCH_AXIS)
    assert mesh.devices.shape == ref.devices.shape
    assert (mesh.devices == ref.devices).all()


def test_group_by_process_orders_hosts_and_chips():
    # interleaved arrival order, 2 hosts x 3 chips
    devs = [
        FakeDev(5, 1), FakeDev(0, 0), FakeDev(4, 1),
        FakeDev(2, 0), FakeDev(3, 1), FakeDev(1, 0),
    ]
    groups = group_by_process(devs)
    assert [[d.id for d in g] for g in groups] == [[0, 1, 2], [3, 4, 5]]
    assert [g[0].process_index for g in groups] == [0, 1]


def test_group_by_process_rejects_ragged_topology():
    devs = [FakeDev(0, 0), FakeDev(1, 0), FakeDev(2, 1)]
    with pytest.raises(ValueError, match="ragged"):
        group_by_process(devs)


def test_multihost_rows_are_hosts(monkeypatch):
    """4 hosts x 2 chips: replica axis must cross hosts (row p = host p),
    batch axis must stay on-host — the DCN/ICI layout contract."""
    import fantoch_tpu.parallel.multihost as mh

    devs = [FakeDev(h * 2 + c, h) for h in range(4) for c in range(2)]
    monkeypatch.setattr(mh.jax, "devices", lambda: devs)
    # Mesh would reject fake devices; capture the array it is built from
    captured = {}

    def fake_mesh(dev_array, axes):
        captured["array"] = np.array(dev_array)
        captured["axes"] = axes
        return "mesh-sentinel"

    monkeypatch.setattr(mh, "Mesh", fake_mesh)
    out = mh.make_multihost_mesh(num_replicas=4)
    assert out == "mesh-sentinel"
    assert captured["axes"] == (REPLICA_AXIS, BATCH_AXIS)
    arr = captured["array"]
    assert arr.shape == (4, 2)
    for host in range(4):
        assert {d.process_index for d in arr[host]} == {host}


def test_multihost_divisibility_contract(monkeypatch):
    import fantoch_tpu.parallel.multihost as mh

    devs = [FakeDev(h * 2 + c, h) for h in range(3) for c in range(2)]
    monkeypatch.setattr(mh.jax, "devices", lambda: devs)
    with pytest.raises(ValueError, match="multiple of the host count"):
        mh.make_multihost_mesh(num_replicas=4)  # 3 hosts


def test_multihost_mesh_counts_total_rows_not_per_shard(monkeypatch):
    """Sharded deployments size the mesh by n * shard_count rows
    (shard-major, mesh_step.shard_of_row); validating against per-shard n
    would accept meshes the device state cannot shard."""
    import fantoch_tpu.parallel.multihost as mh

    devs = [FakeDev(h * 2 + c, h) for h in range(3) for c in range(2)]
    monkeypatch.setattr(mh.jax, "devices", lambda: devs)
    monkeypatch.setattr(mh, "Mesh", lambda arr, axes: "mesh-sentinel")
    # n=2 x 3 shards = 6 total rows over 3 hosts: whole shard blocks per
    # host, accepted; per-shard n=2 alone would NOT divide by 3 hosts
    assert mh.make_multihost_mesh(num_replicas=6, shard_count=3) == "mesh-sentinel"
    with pytest.raises(ValueError, match="total replica rows"):
        mh.make_multihost_mesh(num_replicas=2, shard_count=1)


def test_multihost_mesh_warns_when_shard_blocks_straddle_hosts(monkeypatch, caplog):
    """Shard-major blocks that don't align with host rows demote the
    quorum fan-in to DCN — surfaced as a warning."""
    import logging

    import fantoch_tpu.parallel.multihost as mh

    devs = [FakeDev(h * 2 + c, h) for h in range(4) for c in range(2)]
    monkeypatch.setattr(mh.jax, "devices", lambda: devs)
    monkeypatch.setattr(mh, "Mesh", lambda arr, axes: "mesh-sentinel")
    with caplog.at_level(logging.WARNING, logger="fantoch_tpu"):
        # 8 rows = 2 shards x n=4 over 4 hosts: 2 rows/host < 4-row blocks
        mh.make_multihost_mesh(num_replicas=8, shard_count=2)
    assert any("shard blocks" in r.message for r in caplog.records)


def test_shard_of_row_is_shard_major():
    """Pin the replica-row order the sharded device state uses: shard s
    owns the contiguous block [s*n, (s+1)*n) (protocol_step's on-device
    row // per_shard), NOT a replica-major interleave."""
    from fantoch_tpu.parallel.mesh_step import shard_of_row

    n, shards = 3, 2
    total = n * shards
    assert [shard_of_row(r, total, shards) for r in range(total)] == [0, 0, 0, 1, 1, 1]
    # replica-major interleave would read [0, 1, 0, 1, 0, 1] — reject it
    assert [shard_of_row(r, total, shards) for r in range(total)] != [0, 1, 0, 1, 0, 1]


def test_distributed_init_auto_detect_times_out_fast(monkeypatch):
    """A runner with SLURM env vars but no peers must hit the short
    auto-detect barrier timeout, not jax's ~300 s default; an explicit
    coordinator keeps the long default."""
    import fantoch_tpu.parallel.multihost as mh

    captured = {}

    def fake_initialize(**kwargs):
        captured.update(kwargs)

    monkeypatch.setenv("SLURM_JOB_ID", "12345")
    monkeypatch.setattr(mh, "_DISTRIBUTED_INITIALIZED", False)
    monkeypatch.setattr(mh.jax.distributed, "initialize", fake_initialize)
    assert mh.distributed_init() is True
    assert captured["initialization_timeout"] == mh.AUTO_DETECT_INIT_TIMEOUT_S

    captured.clear()
    monkeypatch.setattr(mh, "_DISTRIBUTED_INITIALIZED", False)
    assert mh.distributed_init(coordinator_address="10.0.0.1:1234") is True
    assert "initialization_timeout" not in captured

    captured.clear()
    monkeypatch.setattr(mh, "_DISTRIBUTED_INITIALIZED", False)
    assert mh.distributed_init(initialization_timeout_s=7) is True
    assert captured["initialization_timeout"] == 7


def test_distributed_init_noop_without_cluster(monkeypatch):
    import fantoch_tpu.parallel.multihost as mh

    for var in ("JAX_COORDINATOR_ADDRESS", "SLURM_JOB_ID", "TPU_WORKER_HOSTNAMES"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(mh, "_DISTRIBUTED_INITIALIZED", False)
    assert mh.distributed_init() is False


def test_distributed_init_survives_half_present_cluster_env(monkeypatch):
    """A rig that sets TPU_WORKER_HOSTNAMES without a derivable
    coordinator (a one-chip TPU host does exactly this:
    ``TPU_WORKER_HOSTNAMES=localhost``) must fall back to single-host,
    not kill the server over a hint."""
    import fantoch_tpu.parallel.multihost as mh

    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    monkeypatch.setattr(mh, "_DISTRIBUTED_INITIALIZED", False)

    def boom(**_kw):
        raise ValueError("coordinator_address should be defined.")

    monkeypatch.setattr(mh.jax.distributed, "initialize", boom)
    assert mh.distributed_init() is False
    # an EXPLICIT coordinator still fails loudly
    with pytest.raises(ValueError):
        mh.distributed_init(coordinator_address="10.0.0.1:1234")
