"""CLI binaries: a 3-process localhost cluster driven purely from the
shell completes a workload (VERDICT r2 item 6 done-criterion), plus the
aux tools (simulation sweep, shard distribution, replay).

Reference: fantoch_ps/src/bin/{common/protocol.rs,client.rs,simulation.rs,
shard_distribution.rs,graph_executor_replay.rs} and the reference's own
3-process localhost smoke scripts (bin/{proc,client,bench})."""

import glob
import json
import os
import signal
import subprocess
import sys
import time

import pytest

from fantoch_tpu.run.harness import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cli_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    return env


HAS_TPU_NODE = bool(
    glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*")
)


@pytest.mark.skipif(HAS_TPU_NODE, reason="this machine has a TPU")
@pytest.mark.parametrize(
    "argv",
    [
        ["-m", "fantoch_tpu.bin.server", "--device-step", "--protocol",
         "epaxos", "-n", "3", "-f", "1", "--client-port", "1"],
        ["-m", "fantoch_tpu.bin.server", "--protocol", "epaxos", "--id", "1",
         "--port", "1", "--client-port", "2", "--addresses", "2=127.0.0.1:3",
         "--sorted", "1:0,2:0", "-n", "3", "-f", "1",
         "--batched-graph-executor"],
        ["bench.py"],
    ],
    ids=["device-step", "batched-executor", "bench-full-mode"],
)
def test_device_entry_points_refuse_a_silent_cpu(argv):
    """The platform rule: with JAX_PLATFORMS unset and no chip, jax
    falls back to the CPU without a word — every entry point that
    dispatches to a device must exit non-zero within seconds, before it
    binds a port, naming what it found.  (With JAX_PLATFORMS=cpu the
    same binaries serve: every other row of this file, and
    tests/test_chip_smoke.py's served leg.)"""
    env = cli_env()
    del env["JAX_PLATFORMS"]
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, *argv], capture_output=True, text=True, timeout=60,
        env=env, cwd=REPO,
    )
    assert out.returncode != 0, out.stdout
    assert "needs the TPU but jax fell back to 'cpu'" in out.stderr
    assert out.stdout == "" and time.monotonic() - t0 < 60


def test_ci_pins_the_supported_jax():
    from fantoch_tpu.hostenv import SUPPORTED_JAX

    with open(os.path.join(REPO, ".github", "workflows", "ci.yml")) as fh:
        ci = fh.read()
    assert ci.count(f'"jax[cpu]=={SUPPORTED_JAX}"') == 2  # both jobs


def test_client_never_imports_jax():
    """bin/client shares the host with the process that owns the chip:
    it must not initialise (or even import) a backend."""
    code = (
        "import sys; import fantoch_tpu.bin.client, "
        "fantoch_tpu.run.client_runner; "
        "assert 'jax' not in sys.modules and 'jaxlib' not in sys.modules"
    )
    subprocess.run([sys.executable, "-c", code], check=True, env=cli_env(),
                   cwd=REPO, timeout=60)


def run_tool(module, args, timeout=120):
    out = subprocess.run(
        [sys.executable, "-m", module, *args],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=cli_env(),
        cwd=REPO,
    )
    assert out.returncode == 0, f"{module} failed:\n{out.stdout}\n{out.stderr}"
    return out.stdout


@pytest.mark.slow
def test_cli_cluster_end_to_end(tmp_path):
    n = 3
    peer_ports = {pid: free_port() for pid in (1, 2, 3)}
    client_ports = {pid: free_port() for pid in (1, 2, 3)}
    sorted_flag = "1:0,2:0,3:0"
    servers = []
    try:
        for pid in (1, 2, 3):
            addresses = ",".join(
                f"{peer}=127.0.0.1:{peer_ports[peer]}" for peer in (1, 2, 3) if peer != pid
            )
            own_sorted = ",".join(
                [f"{pid}:0"] + [f"{p}:0" for p in (1, 2, 3) if p != pid]
            )
            servers.append(
                subprocess.Popen(
                    [
                        sys.executable, "-m", "fantoch_tpu.bin.server",
                        "--protocol", "epaxos",
                        "--id", str(pid),
                        "--port", str(peer_ports[pid]),
                        "--client-port", str(client_ports[pid]),
                        "--addresses", addresses,
                        "--sorted", own_sorted,
                        "-n", str(n), "-f", "1",
                        "--execution-log", str(tmp_path / f"exec_p{pid}.log"),
                        "--metrics-file", str(tmp_path / f"metrics_p{pid}.gz"),
                        "--metrics-interval", "300",
                    ],
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    text=True,
                    env=cli_env(),
                    cwd=REPO,
                )
            )

        out = run_tool(
            "fantoch_tpu.bin.client",
            [
                "--ids", "1-2",
                "--addresses", f"0=127.0.0.1:{client_ports[1]}",
                "--commands-per-client", "10",
                "--conflict-rate", "50",
                "--payload-size", "8",
                "--metrics-file", str(tmp_path / "client_data.pkl"),
            ],
            timeout=180,
        )
        summary = json.loads(out.strip().splitlines()[-1])
        assert summary["clients"] == 2
        assert summary["commands"] == 20
        assert summary["latency_ms"]["p50"] is not None
        assert (tmp_path / "client_data.pkl").exists()

        # give the metrics logger an interval, then check a snapshot exists
        time.sleep(0.5)
        assert any(tmp_path.glob("metrics_p*.gz"))
    finally:
        for proc in servers:
            proc.send_signal(signal.SIGINT)
        for proc in servers:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()

    # offline replay of a server's execution log through the CLI
    log = tmp_path / "exec_p1.log"
    assert log.exists() and log.stat().st_size > 0
    out = run_tool(
        "fantoch_tpu.bin.replay",
        ["--log", str(log), "--protocol", "epaxos", "--id", "1", "-n", "3", "-f", "1"],
    )
    replayed = json.loads(out.strip().splitlines()[-1])
    assert replayed["results"] == 20  # 20 commands x 1 key


@pytest.mark.slow
def test_cli_device_step_sharded(tmp_path):
    """Partial replication from the shell: one --device-step
    --shard-count 2 server, the stock client with both shards pointed at
    it and two-key (frequently cross-shard) commands."""
    port = free_port()
    server = subprocess.Popen(
        [
            sys.executable, "-m", "fantoch_tpu.bin.server",
            "--protocol", "epaxos",
            "--device-step",
            "--client-port", str(port),
            "--device-batch", "32",
            "--device-key-width", "2",
            "--device-key-buckets", "64",
            "-n", "3", "-f", "1",
            "--shard-count", "2",
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        env=cli_env(),
        cwd=REPO,
    )
    try:
        out = run_tool(
            "fantoch_tpu.bin.client",
            [
                "--ids", "1-2",
                "--addresses", f"0=127.0.0.1:{port},1=127.0.0.1:{port}",
                "--commands-per-client", "10",
                "--keys-per-command", "2",
                "--conflict-rate", "50",
            ],
            timeout=180,
        )
        summary = json.loads(out.strip().splitlines()[-1])
        assert summary["clients"] == 2
        assert summary["commands"] == 20
    finally:
        server.send_signal(signal.SIGINT)
        try:
            server.wait(timeout=10)
        except subprocess.TimeoutExpired:
            server.kill()


def test_cli_device_step_server(tmp_path):
    """The TPU serving path from the shell: one --device-step server, the
    stock client binary against it (same wire protocol).  --multihost
    exercises the topology-aware mesh builder's CLI wiring; on this
    single-process backend it degrades to the stock mesh by contract
    (tests/test_multihost.py pins both layouts)."""
    port = free_port()
    server = subprocess.Popen(
        [
            sys.executable, "-m", "fantoch_tpu.bin.server",
            "--protocol", "epaxos",
            "--device-step",
            "--multihost",
            "--client-port", str(port),
            "--device-batch", "32",
            "-n", "3", "-f", "1",
            "--metrics-file", str(tmp_path / "device_metrics.json"),
            "--metrics-interval", "300",
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        env=cli_env(),
        cwd=REPO,
    )
    try:
        out = run_tool(
            "fantoch_tpu.bin.client",
            [
                "--ids", "1-2",
                "--addresses", f"0=127.0.0.1:{port}",
                "--commands-per-client", "10",
                "--conflict-rate", "50",
                "--payload-size", "8",
            ],
            timeout=180,
        )
        summary = json.loads(out.strip().splitlines()[-1])
        assert summary["clients"] == 2
        assert summary["commands"] == 20
        assert summary["latency_ms"]["p50"] is not None
        time.sleep(0.5)
        snap = json.loads((tmp_path / "device_metrics.json").read_text())
        assert snap["executed"] >= 1 and snap["rounds"] >= 1
    finally:
        server.send_signal(signal.SIGINT)
        try:
            server.wait(timeout=10)
        except subprocess.TimeoutExpired:
            server.kill()


def test_cli_simulation_sweep():
    out = run_tool(
        "fantoch_tpu.bin.simulation",
        [
            "--protocol", "epaxos", "-n", "3", "-f", "1",
            "--clients", "1,2", "--commands-per-client", "5",
        ],
        timeout=240,
    )
    lines = [json.loads(line) for line in out.strip().splitlines() if line.startswith("{")]
    assert len(lines) == 2
    for line in lines:
        assert line["protocol"] == "epaxos"
        assert len(line["latency"]) == 3
        for stats in line["latency"].values():
            assert stats["mean_ms"] >= 0


@pytest.mark.slow
def test_cli_exp_driver(tmp_path):
    """The experiment-harness CLI (fantoch_exp bin/main analog): a
    2-point client sweep through real localhost clusters, one manifest
    line per point.  (ResultsDB indexing of sweep output is covered by
    test_run_sweep_throughput_latency_curve.)"""
    out = run_tool(
        "fantoch_tpu.bin.exp",
        [
            "--protocol", "epaxos", "-n", "3", "-f", "1",
            "--clients-sweep", "1,2", "--commands-per-client", "4",
            "--output-dir", str(tmp_path / "exp"),
        ],
        timeout=420,
    )
    lines = [json.loads(l) for l in out.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 2
    assert lines[0]["outcome"]["commands"] == 3 * 4
    assert lines[1]["outcome"]["commands"] == 3 * 2 * 4


def test_cli_sequencer_bench():
    """The key-clock sequencer microbenchmark CLI (sequencer_bench.rs
    analog): both the host and device implementations report commands/s."""
    out = run_tool(
        "fantoch_tpu.bin.sequencer_bench",
        ["--keys", "16", "--batch", "2000", "--iters", "1"],
        timeout=240,
    )
    line = json.loads(out.strip().splitlines()[-1])
    assert line["device_cmds_per_s"] > 0 and line["host_cmds_per_s"] > 0
    assert line["keys"] == 16 and line["batch"] == 2000


def test_cli_ordering_pool():
    """The multi-process ordering pool CLI (the pool.rs scaling probe):
    reports aggregate commands/s and the host's core count."""
    out = run_tool(
        "fantoch_tpu.bin.ordering_pool",
        ["--commands", "5000", "--workers", "2"],
        timeout=240,
    )
    line = json.loads(out.strip().splitlines()[-1])
    assert line["commands"] == 5000 and line["workers"] == 2
    assert line["cmds_per_s"] > 0 and line["cpus"] >= 1


def test_cli_simulation_leader_based():
    """Regression: the sim CLI must serve the leader-based protocol too
    (it crashed without a leader in the Config; the reference's sim
    configs always set leader = 1 for fpaxos)."""
    out = run_tool(
        "fantoch_tpu.bin.simulation",
        [
            "--protocol", "fpaxos", "-n", "3", "-f", "1",
            "--clients", "1", "--commands-per-client", "5",
        ],
        timeout=240,
    )
    (line,) = [json.loads(l) for l in out.strip().splitlines() if l.startswith("{")]
    assert line["protocol"] == "fpaxos"
    assert all(s["issued"] == 5 for s in line["latency"].values())


@pytest.mark.slow
def test_cli_simulation_sweep_parallel_matches_sequential():
    # --parallel fans points over spawn workers (the rayon analog);
    # deterministic sims must yield identical output either way
    args = [
        "--protocol", "epaxos", "-n", "3", "-f", "1",
        "--clients", "1,2", "--commands-per-client", "5", "--seed", "3",
    ]
    seq = run_tool("fantoch_tpu.bin.simulation", args, timeout=240)
    par = run_tool(
        "fantoch_tpu.bin.simulation", args + ["--parallel", "2"], timeout=240
    )
    keep = lambda s: [l for l in s.strip().splitlines() if l.startswith("{")]
    assert keep(seq) == keep(par)


def test_cli_shard_distribution():
    out = run_tool(
        "fantoch_tpu.bin.shard_distribution",
        ["--shard-count", "4", "--keys-per-command", "2", "--commands", "2000"],
    )
    stats = json.loads(out.strip().splitlines()[-1])
    assert stats["shard_count"] == 4
    assert 0 < stats["multi_shard_pct"] <= 100
    assert stats["multi_key_pct"] >= stats["multi_shard_pct"]
