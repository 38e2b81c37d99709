"""Compile-wall regression suite (core/compile_cache.py).

Three proofs:

* **Placement from outside** — ``JAX_COMPILATION_CACHE_DIR`` set puts
  the entries there and the code sets no directory; unset, the cache is
  ``<checkout>/.jax_cache``.

* **Compiled-identity discipline** — a multi-point sweep over batch
  sizes routed through the canonicalized (pow2-padded) shapes compiles
  each plane program exactly ONCE (``program_compile_counts`` reads each
  registered jit's compiled-signature count).  A count > 1 names the
  program whose inputs leaked a non-canonical axis into the signature.

* **Persistent-cache collapse** — a cold-then-warm subprocess pair
  against one cache directory: the warm run retrieves every program from
  disk (``cache_hits > 0``), pays ZERO true XLA compiles
  (``recompile_count() == 0`` — the hit/miss-paired counter), and its
  ``compile_ms`` collapses versus cold.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from fantoch_tpu.core import compile_cache


def test_registry_counts_and_identities():
    """register/program_compile_counts round-trip on a toy jit."""
    import jax

    @jax.jit
    def toy(x):
        return x + 1

    compile_cache.register_program("_toy", toy)
    try:
        assert compile_cache.program_compile_counts()["_toy"] == 0
        toy(np.zeros((4,), np.float32))
        toy(np.ones((4,), np.float32))  # same shape: same signature
        assert compile_cache.program_compile_counts()["_toy"] == 1
        toy(np.zeros((8,), np.float32))  # new shape: second signature
        assert compile_cache.program_compile_counts()["_toy"] == 2
        assert compile_cache.compiled_program_identities() >= 2
    finally:
        compile_cache._programs.pop("_toy", None)


def test_listeners_accept_the_keywords_jax_passes():
    """jax 0.9.0 calls monitoring listeners as callback(event,
    [duration,] **kwargs): a compile event carrying ``fun_name=`` must
    be counted, not kill the process at its first compile (the one
    cause of the 194 seed failures)."""
    from jax import monitoring

    from fantoch_tpu.observability import device

    assert device.subscribe_recompiles()
    before_n, before_ms = device.recompile_count(), device.compile_ms()
    before_hits = device.cache_hit_count()
    monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.25, fun_name="toy"
    )
    assert device.recompile_count() == before_n + 1
    assert device.compile_ms() >= before_ms + 250.0
    # a cache hit reclassifies the duration event after it as a retrieval
    monitoring.record_event("/jax/compilation_cache/cache_hits", fun_name="toy")
    monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.01, fun_name="toy"
    )
    assert device.cache_hit_count() == before_hits + 1
    assert device.recompile_count() == before_n + 1


_PLACEMENT = textwrap.dedent(
    """
    import json, os, sys
    from fantoch_tpu.hostenv import force_cpu_platform
    force_cpu_platform()
    import jax

    updates = []
    real_update = jax.config.update
    def spy(name, value):
        updates.append(name)
        return real_update(name, value)
    jax.config.update = spy

    from fantoch_tpu.core.compile_cache import ensure_compile_cache
    in_effect = ensure_compile_cache()
    assert ensure_compile_cache() == in_effect  # idempotent

    import jax.numpy as jnp
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
    print(json.dumps({
        "in_effect": in_effect,
        "config_dir": jax.config.jax_compilation_cache_dir,
        "set_dir_in_code": "jax_compilation_cache_dir" in updates,
    }))
    """
)


def _run_placement(env_dir):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _PLACEMENT],
        capture_output=True, text=True, timeout=300, cwd=repo, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return repo, json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_dir_from_environment(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set => the entries land there and the
    code sets no directory of its own."""
    env_dir = str(tmp_path / "placed")
    _repo, got = _run_placement(env_dir)
    assert got["in_effect"] == got["config_dir"] == env_dir
    assert got["set_dir_in_code"] is False
    assert os.listdir(env_dir), "no cache entry under JAX_COMPILATION_CACHE_DIR"


def test_cache_dir_default_is_the_checkout():
    """JAX_COMPILATION_CACHE_DIR unset => <checkout>/.jax_cache and
    nowhere else."""
    repo, got = _run_placement(None)
    assert got["in_effect"] == got["config_dir"] == os.path.join(repo, ".jax_cache")
    assert got["set_dir_in_code"] is True


def test_plane_sweep_compiles_each_program_once():
    """5-point batch-size sweep through the canonicalized shapes: every
    plane program ends the sweep with exactly ONE compiled signature
    (none new where an earlier test file of the same worker process has
    already compiled that signature: the jit cache counted is the
    process's).

    The sweep drives the real call paths (the table plane's pow2 vote
    padding, the pred/graph planes' pow2 feed chopping) with batch sizes
    chosen to land in one pow2 bucket — the canonicalization the compile
    wall depends on."""
    import random

    from fantoch_tpu.executor.table_plane import DeviceTablePlane
    from tests.test_pred_plane import _plane_executor

    # table plane: 5 batch sizes inside one pow2 pad (vcap 16)
    plane = DeviceTablePlane(3, stability_threshold=2, key_buckets=8)
    for k in range(6):
        plane.bucket(f"k{k}")
    before = compile_cache.program_compile_counts()["votes_commit"]
    r = random.Random(5)
    for batch in (9, 11, 13, 15, 16):
        vk = np.array([r.randrange(0, 6) for _ in range(batch)], np.int64)
        vb = np.array([r.randrange(1, 4) for _ in range(batch)], np.int64)
        # contiguous-from-1 ranges: no residual re-feeds, so V == batch
        # and all five sizes land in the SAME pow2 vote pad (16)
        vs = np.ones(batch, np.int64)
        ve = np.array([r.randrange(1, 10) for _ in range(batch)], np.int64)
        plane.commit_votes(vk, vb, vs, ve)
    after = compile_cache.program_compile_counts()["votes_commit"]
    assert after - before <= 1 <= after, (
        "table-plane sweep minted extra compiled signatures: a batch "
        "axis leaked past the pow2 pad"
    )

    # pred plane: 5 feed sizes inside one pow2 install pad (ucap 8) over
    # a bounded-dep-width chain workload (width growth is a legitimate
    # O(log) axis; this pins the FEED axis)
    from fantoch_tpu.core.ids import Dot
    from fantoch_tpu.executor.pred import PredecessorsExecutionInfo
    from fantoch_tpu.protocol.common.pred_clocks import Clock
    from tests.test_pred_plane import cmd

    def chain_infos(count):
        infos, last = [], {}
        for i in range(count):
            src = (i % 3) + 1
            dot = Dot(src, i + 1)
            k = f"K{i % 2}"
            deps = {last[k]} if k in last else set()
            last[k] = dot
            infos.append(
                PredecessorsExecutionInfo(
                    dot, cmd(i + 1, [k]), Clock(i + 1, src), deps
                )
            )
        return infos

    # the program registers when its ops module is first imported, which
    # the executor below does lazily: until then it has no count
    counts0 = compile_cache.program_compile_counts().get("pred_plane_step", 0)
    ex = _plane_executor()
    infos = chain_infos(40)
    at = 0
    for size in (5, 6, 7, 8, 5):
        ex.handle_batch(infos[at : at + size], None)
        at += size
    counts1 = compile_cache.program_compile_counts()["pred_plane_step"]
    assert counts1 - counts0 <= 1 <= counts1, (
        "pred-plane sweep minted extra compiled signatures: a feed axis "
        "leaked past the pow2 chop"
    )


_SUBPROC = textwrap.dedent(
    """
    import json, sys
    from fantoch_tpu.hostenv import force_cpu_platform
    force_cpu_platform()
    from fantoch_tpu.core.compile_cache import ensure_compile_cache
    from fantoch_tpu.observability.device import (
        cache_hit_count, cache_miss_count, compile_ms, recompile_count,
        subscribe_recompiles,
    )

    subscribe_recompiles()
    ensure_compile_cache()

    import numpy as np
    from fantoch_tpu.ops.table_ops import fused_votes_commit
    import jax.numpy as jnp

    f = jnp.zeros((8, 3), jnp.int32)
    out = fused_votes_commit(
        f, jnp.zeros((8,), jnp.int32), jnp.zeros((8,), jnp.int32),
        jnp.ones((8,), jnp.int32), jnp.ones((8,), jnp.int32),
        jnp.ones((8,), bool), threshold=2,
    )
    [o.block_until_ready() for o in out]
    print(json.dumps({
        "recompiles": recompile_count(),
        "hits": cache_hit_count(),
        "misses": cache_miss_count(),
        "compile_ms": compile_ms(),
    }))
    """
)


@pytest.mark.slow
def test_cold_vs_warm_persistent_cache(tmp_path):
    """Cold run misses and truly compiles; the warm run against the same
    cache dir retrieves from disk (hits > 0), reports ZERO true
    recompiles, and its compile wall collapses."""

    def run():
        out = subprocess.run(
            [sys.executable, "-c", _SUBPROC],
            capture_output=True, text=True, timeout=300,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            env={**os.environ,
                 "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")},
        )
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold = run()
    assert cold["misses"] > 0
    assert cold["recompiles"] > 0
    warm = run()
    assert warm["hits"] > 0
    assert warm["recompiles"] == 0, (
        "warm persistent cache still paid a true XLA compile"
    )
    assert warm["compile_ms"] < max(cold["compile_ms"], 1.0), (
        f"no compile-wall collapse: cold {cold['compile_ms']} ms vs "
        f"warm {warm['compile_ms']} ms"
    )
