"""CI Pallas-kernel smoke (``make pallas-smoke``): interpret-mode parity
plus compile-cache discipline, per push.

The gate proves the round-19 kernel story end to end on the CPU pin:

1. route-vs-route parity: the ``bench_pallas_resolve`` and
   ``bench_table_pallas`` races assert bit-for-bit equality of every
   round's outputs between the Pallas route (interpret mode on CPU) and
   the composed-XLA route, across all four kernel families (pred step,
   graph step, votes commit, fused table round);
2. served routes: after the races every family has been served by BOTH
   routes (``pallas_status()["served"]``, counted by the router) — a
   race whose Pallas side never ran would pass parity trivially;
3. executor seam: a ``DeviceTablePlane`` served through the forced
   Pallas route matches the composed-route plane's frontiers with the
   SAME upload count (the donation discipline survives the kernel swap);
4. compile-wall discipline: every registered plane program's
   compiled-signature count stays bounded (a leaked non-canonical shape
   axis shows up as a signature explosion), and the hit/miss-paired
   recompile counter is consistent — zero cache misses implies zero
   true recompiles.

Wall cost: a few dozen tiny CPU dispatches, seconds on a laptop.
"""

from __future__ import annotations

import random
import sys
import time

sys.path.insert(0, ".")


def main() -> int:
    from fantoch_tpu.hostenv import force_cpu_platform

    force_cpu_platform()
    started = time.monotonic()

    from fantoch_tpu.core.compile_cache import (
        ensure_compile_cache,
        program_compile_counts,
    )
    from fantoch_tpu.observability.device import (
        cache_miss_count,
        recompile_count,
        subscribe_recompiles,
    )

    subscribe_recompiles()
    ensure_compile_cache()

    # 1. route-vs-route parity (asserted inside the bench rows)
    from bench import bench_pallas_resolve, bench_table_pallas

    row = bench_pallas_resolve(cap=128, width=4, rounds=4)
    row.update(bench_table_pallas(keys=64, batch=256, rounds=4))
    assert row["pallas_resolve_interpret"] is True, row  # the CPU pin
    print(
        "parity: pred/graph/votes/round all bit-for-bit across routes "
        f"(pred {row['pallas_resolve_pred_ms']}ms pallas vs "
        f"{row['pallas_resolve_pred_composed_ms']}ms composed)"
    )

    # 2. every family was served by both routes — parity above must
    # not have raced the composed program against itself
    from fantoch_tpu.ops import pallas_resolve

    served = pallas_resolve.pallas_status()["served"]
    expected = {"pred_plane_step", "graph_plane_step", "votes_commit",
                "table_round"}
    assert expected <= set(served), served
    assert all(
        served[f].get("pallas", 0) > 0 and served[f].get("xla", 0) > 0
        for f in expected
    ), served
    print(f"served routes: {sorted(expected)} ran on pallas and xla")

    # 3. executor seam: the table plane serves identically on either
    # route with the same upload count
    import numpy as np

    from fantoch_tpu.executor.table_plane import DeviceTablePlane

    def drive(enabled):
        pallas_resolve.set_pallas_kernels(enabled)
        try:
            plane = DeviceTablePlane(3, stability_threshold=2, key_buckets=8)
            for k in range(6):
                plane.bucket(f"k{k}")
            rng = random.Random(19)
            for _round in range(4):
                vk, vb, vs, ve = [], [], [], []
                for _ in range(16):
                    vk.append(rng.randrange(0, 6))
                    vb.append(rng.randrange(1, 4))
                    s = rng.randrange(1, 12)
                    vs.append(s)
                    ve.append(s + rng.randrange(0, 4))
                plane.commit_votes(
                    np.array(vk, np.int64), np.array(vb, np.int64),
                    np.array(vs, np.int64), np.array(ve, np.int64),
                )
            return plane
        finally:
            pallas_resolve.set_pallas_kernels(None)

    plane_p, plane_x = drive(True), drive(False)
    assert np.array_equal(plane_p.frontiers(), plane_x.frontiers())
    assert plane_p.resident_uploads == plane_x.resident_uploads == 1
    print("executor seam: frontiers bit-for-bit, one upload on either route")

    # 4. compile-wall discipline
    for name, count in program_compile_counts().items():
        assert count <= 8, (name, count)
    assert cache_miss_count() > 0 or recompile_count() == 0, (
        cache_miss_count(), recompile_count(),
    )
    print(
        f"compile discipline: {len(program_compile_counts())} registered "
        f"programs bounded, {recompile_count()} true compiles / "
        f"{cache_miss_count()} cache misses"
    )

    print(f"pallas smoke OK in {time.monotonic() - started:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
