"""chip_smoke.py on the CPU: the three legs' functions at tiny sizes, and
the script as the driver runs it refusing a machine with no chip.

What only the chip can show (the full-size run on ``platform == "tpu"``)
is proven through the chip tool and recorded in CHANGES.md; this suite
keeps the legs' control flow and pass conditions honest in tier-1.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import chip_smoke
from tests.test_cli import HAS_TPU_NODE, REPO


def test_leg_served_tiny(tmp_path):
    """Leg 1 through the real binaries (JAX_PLATFORMS=cpu => it serves):
    both bursts acknowledged, the snapshot accounts for every command
    and names its backend, no compile in the warm burst, SIGTERM leaves
    a final snapshot."""
    leg = chip_smoke.leg_served(
        n=3, key_buckets=256, batch=16, pending=16, clients=4, commands=10,
        keys_per_shard=1000, workdir=str(tmp_path), timeout_s=300,
    )
    assert leg["ok"] and leg["platform"] == "cpu"
    assert leg["acknowledged"] == [40, 40]
    assert leg["executed"] == leg["replied"] == 80
    assert leg["device_dispatches"] > 0
    assert leg["recompiles_warm_burst"] == 0
    assert set(leg["backend"]) == {
        "platform", "device_kind", "device_count", "mesh_shape", "shards_on_device",
        "resolver", "rule", "quorums",
    }
    assert leg["backend"]["rule"] == "epaxos" and leg["backend"]["quorums"] == [2, 2]
    assert leg["backend"]["resolver"] == "run_position"  # one key a command
    assert "platform=cpu" in leg["banner"] and "mesh=" in leg["banner"]
    assert " shards_on_device=0" in leg["banner"]
    assert " rule=epaxos quorums=2/2 resolver=run_position compile_cache=" in leg["banner"]


def test_leg_served_reports_a_dead_server(tmp_path):
    """A server that cannot start fails the leg at once, with its
    stderr in the message."""
    with pytest.raises(chip_smoke.LegFailed, match="before its banner"):
        chip_smoke.leg_served(
            protocol="epaxos", n=3, f=9, key_buckets=256, batch=16,
            pending=16, clients=1, commands=1, workdir=str(tmp_path),
            timeout_s=120,
        )


def test_leg_kernel_tiny():
    leg = chip_smoke.leg_kernel(batch=20_000)
    assert leg["ok"] and leg["n_resolved"] == 20_000 and not leg["overflow"]
    assert leg["order_checked_against_graph"]
    assert leg["compile_cache_dir"]


def test_leg_planes_tiny():
    leg = chip_smoke.leg_planes(
        table=dict(batch=2000, keys=256, n=3, rounds=2),
        pred=dict(batch=1024, keys=128, rounds=2),
        graph=dict(batch=256, keys=64, rounds=2),
    )
    assert leg["ok"] and leg["host_twin_parity"]
    for plane in ("table", "pred", "graph"):
        assert leg["planes"][plane]["dispatches"] > 0
        assert leg["planes"][plane]["failovers"] == 0
        assert leg["planes"][plane]["resident_uploads"] >= 1
    assert leg["sizes"] == {
        "table_batch": 2000, "pred_batch": 1024, "graph_batch": 256,
    }
    # beside what it checked, the leg reports the compile tally
    assert {"compile_s", "recompiles", "cache_hits", "cache_misses"} <= set(leg)


def test_last_line_is_the_verdict_and_nothing_else():
    """The driver reads the last stdout line: exactly ``ok`` and
    ``device`` {platform, kind, count}; everything else is the line
    before it."""
    backend = {"platform": "tpu", "device_kind": "TPU v5 lite",
               "device_count": 1, "mesh_shape": {"replica": 1, "batch": 1}}
    legs = {"served": {"ok": True, "backend": backend},
            "kernel": {"ok": True, "compile_cache_dir": "/x/.jax_cache"},
            "planes": {"ok": True}}
    report, verdict = map(json.loads, chip_smoke.result_lines(legs, 12.34))
    assert verdict == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    assert report["legs"] == legs and report["backend"] == backend
    assert report["compile_cache_dir"] == "/x/.jax_cache"
    assert report["reduced"] == [] and report["wall_s"] == 12.3


def _run_script(cwd, env):
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )
    return out, time.monotonic() - t0


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "PYTHONPATH")}
    env.update(extra)
    return env


def test_script_refuses_the_cpu():
    out, secs = _run_script(REPO, _env(JAX_PLATFORMS="cpu"))
    assert out.returncode != 0 and out.stdout == ""
    assert "JAX_PLATFORMS=cpu" in out.stderr and secs < 60


@pytest.mark.skipif(HAS_TPU_NODE, reason="this machine has a TPU")
def test_script_refuses_a_machine_with_no_chip():
    """JAX_PLATFORMS unset, no chip: jax would fall back to the CPU
    quietly; the server's platform rule refuses, the script exits
    non-zero within a minute, says why, and prints no result."""
    out, secs = _run_script(REPO, _env())
    assert out.returncode != 0 and out.stdout == ""
    assert "needs the TPU" in out.stderr and secs < 60


def test_script_alone_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out, _secs = _run_script(str(tmp_path), _env())
    assert out.returncode != 0 and out.stdout == ""
