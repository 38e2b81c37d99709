"""Compile-wall control: persistent-cache wiring + compiled-program audit.

PR 15's ``jax_compile_ms`` made the wall visible — each new plane
program costs ~50s on the dev rig, and a scenario sweep that perturbs
any shape axis pays it per point.  The defense is two-sided and this
module is the seam for both:

* **Persistent cache** — :func:`ensure_compile_cache` is the one
  idempotent enable, and the directory is placed from OUTSIDE: where
  ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and this
  module sets no directory in code; where it is not, the cache is
  ``<checkout>/.jax_cache`` and nowhere else (a cache under a per-run
  directory starts cold every run).  With the cache warm, a "compile"
  is a disk load: ``observability.device`` pairs the cache hit/miss
  monitoring events with the backend-compile duration events so
  ``jax_recompiles`` counts only TRUE compiles (a warm sweep reports 0)
  while ``jax_cache_hits``/``jax_cache_misses`` expose the retrievals.

* **Program-identity audit** — shape canonicalization (pow2 floors on
  capacity, width, chain length, batch) is only proven by counting: the
  hot jitted programs register here (:func:`register_program`) and
  :func:`program_compile_counts` reads each one's compiled-signature
  count (``jit(f)._cache_size()``), so a multi-point sweep can assert
  every plane program compiled exactly ONCE.  A count > 1 names the
  program whose input shapes leaked a non-canonical axis into the
  compiled signature — the regression test
  (tests/test_compile_cache.py) and the bench smoke both assert on it.
"""

from __future__ import annotations

import os
from typing import Callable, Dict, Optional

# the audited hot programs: name -> jitted callable.  Module-level like
# the recompile counters — registration happens at ops-module import, so
# the registry sees every program the process can dispatch.
_programs: Dict[str, Callable] = {}

_enabled_dir: Optional[str] = None


def register_program(name: str, fn: Callable) -> Callable:
    """Register a jitted program for the compiled-identity audit.
    Returns ``fn`` so registration can wrap a definition in place."""
    _programs[name] = fn
    return fn


def program_compile_counts() -> Dict[str, int]:
    """Compiled-signature count per registered program (0 = never
    dispatched).  Uses the jit cache-size introspection; a program whose
    jit object doesn't expose it reports -1 rather than lying."""
    counts: Dict[str, int] = {}
    for name, fn in _programs.items():
        probe = getattr(fn, "_cache_size", None)
        try:
            counts[name] = int(probe()) if probe is not None else -1
        except Exception:  # noqa: BLE001 — introspection only
            counts[name] = -1
    return counts


def compiled_program_identities() -> int:
    """Total distinct compiled signatures across registered programs —
    the bench counter a canonicalized sweep holds constant."""
    return sum(c for c in program_compile_counts().values() if c > 0)


def clear_program_registry() -> None:
    """Test hook: forget registered programs (NOT their jit caches)."""
    _programs.clear()


def ensure_compile_cache() -> str:
    """Idempotent persistent-cache enable; returns the directory in
    effect.  Safe to call from every start-up seam (CLI binaries, both
    runners, bench, conftest) — only the first call flips jax.config.
    Nothing is swallowed: a cache that fails to enable is an error."""
    global _enabled_dir
    if _enabled_dir is None:
        import jax

        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not cache_dir:
            import fantoch_tpu

            cache_dir = os.path.join(
                os.path.dirname(
                    os.path.dirname(os.path.abspath(fantoch_tpu.__file__))
                ),
                ".jax_cache",
            )
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        # cache every program, however small or quick to compile: a
        # serving loop stalls on each one it has to rebuild
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        _enabled_dir = cache_dir
    return _enabled_dir
