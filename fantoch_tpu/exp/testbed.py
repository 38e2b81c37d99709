"""Host-list (SSH/baremetal) testbed: plain machines, no cloud API.

Reference: fantoch_exp/src/testbed/baremetal.rs — the reference reads a
machines file, sets each host up over SSH (tsunami's baremetal provider),
launches the protocol/client binaries remotely, and pulls artifacts back.
The analog here:

* ``HostsTestbed([...])`` takes ``user@host`` entries; ``stage()`` rsyncs
  the repo to every distinct host, ``spawn()`` launches a framework
  binary on host *i* via ``ssh host 'cd <dir> && python -m ...'``, and
  ``pull()`` copies result files back.
* ``use_ssh=False`` runs the SAME built command strings through
  ``bash -c`` against a locally staged copy — the whole orchestration
  layer (staging, remote command construction, artifact pull) runs and is
  testable on machines with no sshd (this rig), and a real cluster only
  changes the transport.

``exp.bench.run_experiment(config, out, testbed=HostsTestbed(...))``
drives a whole experiment through it; ``LocalTestbed`` implements the
same interface with plain subprocesses on this machine (the localhost
testbed of testbed/local.rs), so the experiment driver has ONE body.
"""

from __future__ import annotations

import os
import shlex
import shutil
import signal
import subprocess
import sys
from typing import Dict, List, Optional


def cli_env(platform: str = "cpu") -> Dict[str, str]:
    """Environment for framework subprocesses: the repo on PYTHONPATH,
    and ``JAX_PLATFORMS`` (the one platform switch, hostenv.py) — the
    caller's value when set, else ``platform``.  The default is the CPU
    because a localhost cluster is n processes on one host and a chip
    has one owner."""
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", platform)
    repo = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    return env


class LocalTestbed:
    """Subprocesses on this machine behind the HostsTestbed interface."""

    use_ssh = False
    hosts: List[str] = ["localhost"]

    def __init__(self) -> None:
        self._ports: Dict[int, int] = {}
        self._workdir: Optional[str] = None

    def describe(self) -> Dict:
        return {"kind": "localhost"}

    def addr(self, _index: int) -> str:
        return "127.0.0.1"

    def _port(self, slot: int) -> int:
        from fantoch_tpu.run.harness import free_port

        if slot not in self._ports:
            self._ports[slot] = free_port()
        return self._ports[slot]

    def peer_port(self, pid: int) -> int:
        return self._port(pid)

    def client_port(self, pid: int) -> int:
        return self._port(10_000 + pid)

    def stage(self) -> None:
        pass

    def prepare(self, exp_dir: str) -> None:
        """The experiment dir doubles as the (only) workdir: artifacts
        land in place and pull() is a no-op existence check."""
        self._workdir = exp_dir

    def spawn(
        self,
        index: int,
        module: str,
        args: List[str],
        stdout,
        pre_dirs: Optional[List[str]] = None,
        profile_artifact: Optional[str] = None,
        pidfile: Optional[str] = None,
        profile_kind: str = "cprofile",
    ) -> subprocess.Popen:
        """``profile_artifact``: workdir-relative artifact path — the
        server runs under a profiler that writes there on exit (the
        RunMode::Flamegraph/Heaptrack analogs,
        fantoch_exp/src/lib.rs:26-67: a profiler wraps the server binary
        and its artifact is pulled with the results).  ``profile_kind``:
        "cprofile" (CPU, .prof) or "memory" (tracemalloc text report via
        fantoch_tpu.exp.memprof).  ``pidfile`` is unused locally
        (interrupt() signals the child directly)."""
        assert self._workdir is not None, "prepare(exp_dir) first"
        env = cli_env()
        for d in pre_dirs or []:
            os.makedirs(os.path.join(self._workdir, d), exist_ok=True)
        cmd = [sys.executable, "-m", module, *args]
        if profile_artifact is not None:
            wrapper = (
                ["cProfile", "-o"] if profile_kind == "cprofile"
                else ["fantoch_tpu.exp.memprof", "-o"]
            )
            cmd = [
                sys.executable, "-m", *wrapper, profile_artifact,
                "-m", module, *args,
            ]
        return subprocess.Popen(
            cmd,
            stdout=stdout,
            stderr=subprocess.STDOUT,
            env=env,
            cwd=self._workdir,
        )

    def pull(self, _index: int, remote_rel: str, local_path: str) -> bool:
        src = os.path.join(self._workdir or "", remote_rel)
        if not os.path.exists(src):
            return False
        if os.path.abspath(src) != os.path.abspath(local_path):
            shutil.copyfile(src, local_path)
        return True

    def interrupt(self, proc: subprocess.Popen, _index: int, _pidfile_rel: str) -> None:
        """Deliver SIGINT to a spawned server (local: straight to the
        child — cProfile's finally-dump fires on KeyboardInterrupt)."""
        proc.send_signal(signal.SIGINT)

    def cleanup(self) -> None:
        pass

_SSH_OPTS = [
    "-o", "StrictHostKeyChecking=no",
    "-o", "BatchMode=yes",
]
_STAGE_EXCLUDES = [".git", "__pycache__", ".jax_cache", ".pytest_cache"]


class HostsTestbed:
    """A list of SSH-reachable machines serving as the cluster."""

    def __init__(
        self,
        hosts: List[str],
        *,
        use_ssh: bool = True,
        remote_dir: str = "~/fantoch_tpu_run",
        python: str = "python3",
        base_port: int = 7800,
        platform: str = "cpu",
        repo_dir: Optional[str] = None,
    ):
        assert hosts, "a hosts testbed needs at least one host"
        self.hosts = list(hosts)
        self.use_ssh = use_ssh
        self.remote_dir = remote_dir
        self.python = python
        self.base_port = base_port
        # JAX_PLATFORMS of the staged servers (a TPU cluster passes
        # platform="tpu" — the transport is the only other difference from
        # a localhost run)
        self.platform = platform
        self.repo_dir = repo_dir or os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        self._local_dirs: Dict[str, str] = {}  # per-host staged copy (local mode)
        self._local_ports: Dict[int, int] = {}  # local mode: OS-probed ports

    def describe(self) -> Dict:
        return {"kind": "hosts", "hosts": self.hosts, "ssh": self.use_ssh}

    def prepare(self, exp_dir: str) -> None:
        pass  # artifacts live in the per-host workdirs until pull()

    def __enter__(self) -> "HostsTestbed":
        return self

    def __exit__(self, *_exc) -> None:
        self.cleanup()

    # --- addressing ---

    def addr(self, index: int) -> str:
        """The TCP address peers/clients dial for host ``index``."""
        if not self.use_ssh:
            return "127.0.0.1"
        host = self.hosts[index % len(self.hosts)]
        return host.split("@", 1)[-1]

    def peer_port(self, pid: int) -> int:
        return self._derived_port(pid)

    def client_port(self, pid: int) -> int:
        return self._derived_port(1000 + pid)

    def _derived_port(self, slot: int) -> int:
        """Over ssh the ports must be predictable on the remote (base +
        offset).  In local mode all servers share this machine, where
        ``base + offset`` arithmetic can collide with any concurrently
        bound socket (base_port usually comes from free_port(), i.e. the
        ephemeral range a loaded test suite is actively allocating from) —
        probe each port from the OS instead, memoized per slot."""
        if self.use_ssh:
            return self.base_port + slot
        if slot not in self._local_ports:
            from fantoch_tpu.run.harness import free_port

            self._local_ports[slot] = free_port()
        return self._local_ports[slot]

    # --- staging (baremetal.rs setup: clone/sync the tree per machine) ---

    def stage(self) -> None:
        if self.use_ssh:
            for host in dict.fromkeys(self.hosts):
                subprocess.run(
                    [
                        "rsync", "-az", "--delete",
                        *[f"--exclude={e}" for e in _STAGE_EXCLUDES],
                        "-e", "ssh " + " ".join(_SSH_OPTS),
                        f"{self.repo_dir}/",
                        f"{host}:{self.remote_dir}/",
                    ],
                    check=True,
                    capture_output=True,
                    timeout=300,
                )
            return
        # local mode: one staged copy per distinct host entry, so the
        # launched processes genuinely run out of the staged tree
        import tempfile

        for host in dict.fromkeys(self.hosts):
            if host in self._local_dirs:
                continue
            dst = tempfile.mkdtemp(prefix=f"fantoch_stage_{host.replace('@', '_')}_")
            shutil.copytree(
                self.repo_dir,
                dst,
                dirs_exist_ok=True,
                ignore=shutil.ignore_patterns(*_STAGE_EXCLUDES),
            )
            self._local_dirs[host] = dst

    def _workdir(self, index: int) -> str:
        host = self.hosts[index % len(self.hosts)]
        if self.use_ssh:
            return self.remote_dir
        return self._local_dirs[host]

    # --- launch / pull ---

    def _remote_command(
        self,
        index: int,
        module: str,
        args: List[str],
        pre_dirs: Optional[List[str]] = None,
        profile_artifact: Optional[str] = None,
        pidfile: Optional[str] = None,
        profile_kind: str = "cprofile",
    ) -> str:
        """The command string a remote shell runs (identical in both
        transports — that's the point of the local mode)."""
        argv = " ".join(shlex.quote(a) for a in args)
        mkdirs = "".join(
            f"mkdir -p {shlex.quote(d)} && " for d in (pre_dirs or [])
        )
        profile_mod = (
            "cProfile" if profile_kind == "cprofile" else "fantoch_tpu.exp.memprof"
        )
        profile = (
            f"-m {profile_mod} -o {shlex.quote(profile_artifact)} "
            if profile_artifact is not None
            else ""
        )
        # $$ is the shell's pid, which exec turns into the python's pid:
        # the pidfile gives interrupt() an in-band target over ssh (a
        # plain ssh client exit only SIGHUPs the remote, which skips
        # Python's KeyboardInterrupt path and any profiler dump)
        pidf = (
            f"echo $$ > {shlex.quote(pidfile)} && " if pidfile is not None else ""
        )
        # exec: the launched python replaces the shell, so teardown signals
        # (SIGINT locally, kill -INT via the pidfile over ssh) reach it.
        # JAX_PLATFORMS is the testbed's, not the caller's
        return (
            f"cd {self._workdir(index)} && {mkdirs}{pidf}"
            f"exec env PYTHONPATH=. "
            f"JAX_PLATFORMS={shlex.quote(self.platform)} "
            f"{shlex.quote(self._python_for(index))} {profile}-m {module} {argv}"
        )

    def _python_for(self, index: int) -> str:
        # local mode must use THIS interpreter (the remote default python3
        # may not carry the deps)
        return self.python if self.use_ssh else sys.executable

    def spawn(
        self,
        index: int,
        module: str,
        args: List[str],
        stdout,
        pre_dirs: Optional[List[str]] = None,
        profile_artifact: Optional[str] = None,
        pidfile: Optional[str] = None,
        profile_kind: str = "cprofile",
    ) -> subprocess.Popen:
        command = self._remote_command(
            index, module, args, pre_dirs, profile_artifact, pidfile,
            profile_kind,
        )
        if self.use_ssh:
            host = self.hosts[index % len(self.hosts)]
            argv = ["ssh", *_SSH_OPTS, host, command]
        else:
            argv = ["bash", "-c", command]
        return subprocess.Popen(
            argv, stdout=stdout, stderr=subprocess.STDOUT
        )

    def interrupt(self, proc: subprocess.Popen, index: int, pidfile_rel: str) -> None:
        """Deliver SIGINT to the server behind ``proc``: locally the
        exec'd python IS the child; over ssh, in-band via the pidfile
        (connection teardown alone would SIGHUP-kill the remote python
        without raising KeyboardInterrupt, losing profiler artifacts and
        final metrics snapshots)."""
        if not self.use_ssh:
            proc.send_signal(signal.SIGINT)
            return
        host = self.hosts[index % len(self.hosts)]
        pidpath = f"{self.remote_dir}/{pidfile_rel}"
        subprocess.run(
            [
                "ssh", *_SSH_OPTS, host,
                f"kill -INT $(cat {shlex.quote(pidpath)}) 2>/dev/null || true",
            ],
            capture_output=True,
            timeout=30,
        )

    def pull(self, index: int, remote_rel: str, local_path: str) -> bool:
        """Copy one artifact back from host ``index``; False if absent."""
        if self.use_ssh:
            host = self.hosts[index % len(self.hosts)]
            out = subprocess.run(
                [
                    "scp", *_SSH_OPTS,
                    f"{host}:{self.remote_dir}/{remote_rel}",
                    local_path,
                ],
                capture_output=True,
                timeout=120,
            )
            return out.returncode == 0
        src = os.path.join(self._workdir(index), remote_rel)
        if not os.path.exists(src):
            return False
        shutil.copyfile(src, local_path)
        return True

    def cleanup(self) -> None:
        for path in self._local_dirs.values():
            shutil.rmtree(path, ignore_errors=True)
        self._local_dirs.clear()
