"""Slope-profile the phases of resolve_functional_keyed at 1M on the TPU.

Each variant computes a prefix of the kernel and returns a scalar; the
chained-carry slope method removes the rig's fixed dispatch latency.
"""

import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from bench import BATCH, CONFLICT, build_workload  # noqa: E402
from fantoch_tpu.core.compile_cache import ensure_compile_cache  # noqa: E402
from fantoch_tpu.ops.graph_resolve import (  # noqa: E402
    TERMINAL,
    _doubling_core,
    _residual_size_for,
)

ensure_compile_cache()

RES = _residual_size_for(BATCH)


def phase_fn(stop):
    def fn(key, dep, dot_src, dot_seq):
        batch = dep.shape[0]
        res_n = RES
        idx = jnp.arange(batch, dtype=jnp.int32)
        p_iota = idx
        k_s, pos_s, dep_s = jax.lax.sort(
            (key.astype(jnp.int32), idx, dep), num_keys=1, is_stable=True
        )
        if stop == "s1":
            return k_s[0] + pos_s[0] + dep_s[0]
        head = jnp.concatenate([jnp.ones((1,), bool), k_s[1:] != k_s[:-1]])
        prev_pos = jnp.roll(pos_s, 1)
        ok = jnp.where(head, dep_s == TERMINAL, dep_s == prev_pos)
        run_start = jax.lax.cummax(jnp.where(head, p_iota, 0))
        lastbad = jax.lax.cummax(jnp.where(~ok, p_iota, -1))
        chain_ok = lastbad < run_start
        if stop == "verify":
            return chain_ok.sum()
        cflag = chain_ok.astype(jnp.int32)
        _, p_r_full = jax.lax.sort((cflag, p_iota), num_keys=1, is_stable=True)
        n_residual = batch - cflag.sum()
        if stop == "s2":
            return p_r_full[0] + n_residual
        p_r = p_r_full[:res_n]
        r_iota = jnp.arange(res_n, dtype=jnp.int32)
        valid_r = r_iota < n_residual
        rpos = pos_s[p_r]
        rdep = dep_s[p_r]
        rrs = jnp.where(valid_r, run_start[p_r], jnp.iinfo(jnp.int32).max)
        rsrc = dot_src[rpos]
        rseq = dot_seq[rpos]
        if stop == "rgather":
            return rpos.sum() + rdep.sum() + rrs[0] + rsrc[0] + rseq[0]
        remap = jnp.full((batch,), TERMINAL, dtype=jnp.int32)
        remap = remap.at[jnp.where(valid_r, rpos, batch)].set(r_iota, mode="drop")
        rdep_local = jnp.where(rdep >= 0, remap[jnp.clip(rdep, 0, batch - 1)], rdep)
        rdep_local = jnp.where(valid_r, rdep_local, TERMINAL)
        if stop == "remap":
            return rdep_local.sum()
        l_resolved, l_rank, l_leader, l_on_cycle = _doubling_core(rdep_local)
        if stop == "doubling":
            return l_rank.sum() + l_leader[0]
        g_head = jnp.concatenate([jnp.ones((1,), bool), rrs[1:] != rrs[:-1]])
        firstbad = jax.lax.cummax(jnp.where(g_head, p_r, 0))
        l_unres = (~l_resolved).astype(jnp.int32)
        outs = jax.lax.sort(
            (rrs, l_unres, l_rank, l_leader, rsrc, rseq, p_r, firstbad,
             rpos, l_resolved.astype(jnp.int32), jnp.where(valid_r, l_rank, 0),
             rpos[jnp.clip(l_leader, 0, res_n - 1)], l_on_cycle.astype(jnp.int32)),
            num_keys=6, is_stable=True,
        )
        e_p_r, e_firstbad, e_res = outs[6], outs[7], outs[9]
        if stop == "emit":
            return e_p_r.sum() + e_firstbad[0] + e_res[0]
        rrs_emit = jnp.sort(rrs)
        e_g_head = jnp.concatenate([jnp.ones((1,), bool), rrs_emit[1:] != rrs_emit[:-1]])
        e_group_start = jax.lax.cummax(jnp.where(e_g_head, r_iota, 0))
        emit_local = r_iota - e_group_start
        e_valid = r_iota < n_residual
        target_r = e_firstbad + emit_local
        sc_idx = jnp.where(e_valid, e_p_r, batch)
        tgt_b = p_iota.at[sc_idx].set(target_r, mode="drop")
        unres_b = (~chain_ok).at[sc_idx].set(e_res == 0, mode="drop")
        if stop == "scatter":
            return tgt_b.sum() + unres_b.sum()
        order_sorted = jax.lax.sort(
            (unres_b.astype(jnp.int32), tgt_b, pos_s), num_keys=2, is_stable=True
        )
        return order_sorted[2][0] + (batch - unres_b.sum())

    return fn


def slope(name, base, k_lo=1, k_hi=3, iters=9):
    def chain(k):
        def f(key, dep, src, seq):
            carry = jnp.int32(0)
            for _ in range(k):
                out = base(key + (carry >> jnp.int32(30)), dep, src, seq)
                carry = out.astype(jnp.int32)
            return carry
        return jax.jit(f)

    f_lo, f_hi = chain(k_lo), chain(k_hi)

    def t(f):
        float(f(KEY, DEP, SRC, SEQ))
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            float(f(KEY, DEP, SRC, SEQ))
            ts.append((time.perf_counter() - t0) * 1e3)
        return float(np.median(ts))

    lo, hi = t(f_lo), t(f_hi)
    per = (hi - lo) / (k_hi - k_lo)
    print(f"{name:12s} cumulative = {per:7.3f} ms")
    return per


key_np, dep_np, src_np, seq_np = build_workload(BATCH, CONFLICT)
KEY = jax.device_put(jnp.asarray(key_np))
DEP = jax.device_put(jnp.asarray(dep_np))
SRC = jax.device_put(jnp.asarray(src_np))
SEQ = jax.device_put(jnp.asarray(seq_np))

print("platform:", jax.devices()[0].platform, "residual:", RES)
stops = sys.argv[1:] or ["s1", "verify", "s2", "rgather", "remap", "doubling", "emit", "scatter", "full"]
for stop in stops:
    slope(stop, phase_fn(stop))
