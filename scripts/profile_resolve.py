"""Stage-level profiling of resolve_functional_keyed at B=1M on the live
backend (VERDICT r2 item 1b: nobody profiled the kernel).  Prints one JSON
object with per-stage milliseconds so the <10 ms push targets the real
bottleneck instead of a guess.

Methodology: per-dispatch cost is noisy and non-iid on a shared host,
so each probe chains K data-dependent repetitions inside
ONE dispatch via ``lax.fori_loop`` (single compile, any K) and estimates
per-op time as (min_t(K_HI) - min_t(K_LO)) / (K_HI - K_LO); min over reps
is the standard latency estimator under asymmetric noise.

Run:  python scripts/profile_resolve.py            # default backend (TPU)
      JAX_PLATFORMS=cpu python scripts/profile_resolve.py
"""

from __future__ import annotations

import functools
import json
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from bench import BATCH, CONFLICT, build_workload
from fantoch_tpu.core.compile_cache import ensure_compile_cache
from fantoch_tpu.ops.graph_resolve import (
    TERMINAL,
    _doubling_core,
    _residual_size_for,
    resolve_functional_keyed,
)

ensure_compile_cache()

REPS = 6


def probe(body, ops, k_lo=1, k_hi=None, reps=REPS):
    """body(op_arrays, carry) -> int32 carry.  Returns per-op ms.

    ``ops`` is a tuple of device arrays; the carry data-dependence stops
    XLA from collapsing the fori_loop iterations.
    """

    @jax.jit
    def run_k(k, *ops):
        def step(_i, carry):
            return body(ops, carry)

        return jax.lax.fori_loop(0, k, step, jnp.int32(0))

    def timed(k):
        float(run_k(k, *ops))  # compile/warm (cached across k: k is traced)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            float(run_k(k, *ops))
            best = min(best, (time.perf_counter() - t0) * 1000.0)
        return best

    lo = timed(k_lo)
    hi = timed(k_hi)
    return (hi - lo) / (k_hi - k_lo)


def main():
    key_np, dep_np, src_np, seq_np = build_workload(BATCH, CONFLICT)
    key = jax.device_put(jnp.asarray(key_np))
    dep = jax.device_put(jnp.asarray(dep_np))
    src = jax.device_put(jnp.asarray(src_np))
    seq = jax.device_put(jnp.asarray(seq_np))
    residual = _residual_size_for(BATCH)
    out = {"platform": jax.devices()[0].platform, "batch": BATCH, "residual": residual}
    idx = jnp.arange(BATCH, dtype=jnp.int32)

    def perturb(x, carry):  # runtime zero, data-dependent
        return x + (carry >> jnp.int32(30))

    # --- full kernel (reference point); ~18 ms/op -> K up to 33
    def full(ops, carry):
        k, d, s, q = ops
        r = resolve_functional_keyed(
            perturb(k, carry), d, s, q, residual_size=residual,
            return_structure=False,
        )
        return r.order[0]
    out["full_kernel_ms"] = round(probe(full, (key, dep, src, seq), 1, 17), 3)

    # --- stage 1: the grouping sort alone
    def s1(ops, carry):
        k, d, s, q = ops
        k_s, pos_s, dep_s = jax.lax.sort(
            (perturb(k, carry), idx, d), num_keys=1, is_stable=True
        )
        return pos_s[0]
    out["sort1_ms"] = round(probe(s1, (key, dep, src, seq), 1, 33), 3)

    # --- stage 2 alone: link verification (elementwise/cummax over sorted)
    k_s0, pos_s0, dep_s0 = jax.lax.sort((key, idx, dep), num_keys=1, is_stable=True)
    def s2(ops, carry):
        k_s, pos_s, dep_s = ops
        k_s = perturb(k_s, carry)
        head = jnp.concatenate([jnp.ones((1,), bool), k_s[1:] != k_s[:-1]])
        prev_pos = jnp.roll(pos_s, 1)
        ok = jnp.where(head, dep_s == TERMINAL, dep_s == prev_pos)
        run_start = jax.lax.cummax(jnp.where(head, idx, 0))
        lastbad = jax.lax.cummax(jnp.where(~ok, idx, -1))
        chain_ok = lastbad < run_start
        return chain_ok.astype(jnp.int32).sum()
    out["verify_ms"] = round(probe(s2, (k_s0, pos_s0, dep_s0), 1, 33), 3)

    # --- the residual-compaction sort (binary partition) vs scatter
    cflag0 = jax.device_put(
        jnp.asarray((np.random.default_rng(0).random(BATCH) < 0.98).astype(np.int32))
    )
    def part(ops, carry):
        (cf,) = ops
        a, b = jax.lax.sort(
            (perturb(cf, carry), idx), num_keys=1, is_stable=True
        )
        return b[0]
    out["partition_sort_ms"] = round(probe(part, (cflag0,), 1, 33), 3)

    def part_scatter(ops, carry):
        (cf,) = ops
        bad = perturb(cf, carry) == 0
        rank = jnp.cumsum(bad) - 1
        tgt = jnp.where(bad, rank, residual)
        buf = jnp.full((residual,), -1, jnp.int32).at[tgt].set(idx, mode="drop")
        return buf[0]
    out["partition_scatter_ms"] = round(probe(part_scatter, (cflag0,), 1, 33), 3)

    # --- final sort alone (3 operands, 2 keys)
    def fsort(ops, carry):
        k, d, s, q = ops
        o = jax.lax.sort((perturb(k, carry), d, s), num_keys=2, is_stable=True)
        return o[2][0]
    out["final_sort_ms"] = round(probe(fsort, (key, dep, src, seq), 1, 33), 3)

    # --- B-wide random gather / unique scatter / cumsum (roofline probes)
    perm = jax.device_put(
        jnp.asarray(np.random.default_rng(1).permutation(BATCH).astype(np.int32))
    )
    def gathp(ops, carry):
        p, d = ops
        return d[perturb(p, carry)][0]
    out["random_gather_ms"] = round(probe(gathp, (perm, dep), 1, 65), 3)

    def scatp(ops, carry):
        p, d = ops
        return jnp.zeros((BATCH,), jnp.int32).at[perturb(p, carry)].set(
            d, mode="drop"
        )[0]
    out["random_scatter_ms"] = round(probe(scatp, (perm, dep), 1, 33), 3)
    ident = jax.device_put(jnp.arange(BATCH, dtype=jnp.int32))
    out["ident_scatter_ms"] = round(probe(scatp, (ident, dep), 1, 33), 3)

    def csum(ops, carry):
        (d,) = ops
        return jnp.cumsum(perturb(d, carry))[0]
    out["cumsum_ms"] = round(probe(csum, (dep,), 1, 65), 3)

    # --- doubling core at residual scale
    rdep = jax.device_put(jnp.asarray(dep_np[:residual]))
    def dcore(ops, carry):
        (rd,) = ops
        res, rank, lead, cyc = _doubling_core(perturb(rd, carry))
        return rank[0]
    out["doubling_residual_ms"] = round(probe(dcore, (rdep,), 1, 33), 3)

    print(json.dumps(out))


if __name__ == "__main__":
    main()
