"""Simulation sweep: deterministic protocol runs over a real planet.

Reference: fantoch_ps/src/bin/simulation.rs:47-584 — sweep protocols and
client counts over the AWS planet, reporting per-region latency stats.
The reference parallelizes with rayon; ``--parallel N`` here fans sweep
points out over worker processes (each sim is a tight single-threaded
event loop, so process-level parallelism is the right grain).

    python -m fantoch_tpu.bin.simulation --protocol newt -n 5 -f 1 \\
        --clients 1,10 --conflict-rate 50 --parallel 4
"""

from __future__ import annotations

import argparse
import json


def _run_point(params: dict) -> str:
    """One sweep point -> its JSON result line.  Module-level and fed by a
    plain dict so ProcessPoolExecutor workers can pickle the call.

    Always CPU: a simulation is a host-side deterministic event loop, and
    concurrent workers cannot share the one chip (hostenv.py)."""
    from fantoch_tpu.hostenv import force_cpu_platform

    force_cpu_platform()

    from fantoch_tpu.bin.common import protocol_by_name
    from fantoch_tpu.client import ConflictRateKeyGen, Workload
    from fantoch_tpu.core import Config
    from fantoch_tpu.core.planet import Planet, Region
    from fantoch_tpu.sim.runner import Runner

    protocol_cls = protocol_by_name(params["protocol"])
    planet = Planet.new(params["dataset"])
    if params["regions"]:
        regions = [Region(name) for name in params["regions"]]
    else:
        regions = sorted(planet.regions())[: params["n"]]
    assert len(regions) == params["n"], "one region per process"

    config = Config(
        n=params["n"],
        f=params["f"],
        gc_interval_ms=100,
        newt_tiny_quorums=params["tiny_quorums"],
        # Newt liveness requires flushing detached votes (the reference's
        # newt_config! macro always sets it, fantoch_ps/src/protocol/
        # mod.rs:65); harmless for the other protocols
        newt_detached_send_interval_ms=100,
        # leader-based protocols need one (the reference's config! macro
        # sets leader = 1 for fpaxos sims, fantoch_ps/src/protocol/
        # mod.rs:698-716); ignored by the leaderless protocols
        leader=params["leader"],
    )
    workload = Workload(
        shard_count=1,
        key_gen=ConflictRateKeyGen(params["conflict_rate"]),
        keys_per_command=params["keys_per_command"],
        commands_per_client=params["commands_per_client"],
        payload_size=1,
    )
    runner = Runner(
        protocol_cls,
        planet,
        config,
        workload,
        params["clients"],
        process_regions=list(regions),
        client_regions=list(regions),
        seed=params["seed"],
    )
    _metrics, _monitors, latencies = runner.run(extra_sim_time_ms=10_000)
    stats = {
        str(region): {
            "issued": issued,
            "mean_ms": round(hist.mean(), 1),
            "p99_ms": hist.percentile(0.99),
        }
        for region, (issued, hist) in sorted(
            latencies.items(), key=lambda kv: str(kv[0])
        )
    }
    return json.dumps(
        {
            "protocol": params["protocol"],
            "n": params["n"],
            "f": params["f"],
            "clients_per_region": params["clients"],
            "latency": stats,
        }
    )


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(
        prog="fantoch_tpu.bin.simulation", description=__doc__
    )
    parser.add_argument("--protocol", required=True)
    parser.add_argument("--processes", "-n", type=int, required=True)
    parser.add_argument("--faults", "-f", type=int, required=True)
    parser.add_argument("--clients", default="1",
                        help="comma list of clients-per-region to sweep")
    parser.add_argument("--conflict-rate", type=int, default=50)
    parser.add_argument("--keys-per-command", type=int, default=1)
    parser.add_argument("--commands-per-client", type=int, default=50)
    parser.add_argument("--dataset", choices=["aws", "gcp"], default="aws")
    parser.add_argument("--regions", default=None,
                        help="comma list of region names (default: first n)")
    parser.add_argument("--newt-tiny-quorums", action="store_true")
    parser.add_argument("--leader", type=int, default=1,
                        help="initial leader process id (leader-based protocols)")
    parser.add_argument("--seed", type=int, default=None)
    parser.add_argument("--parallel", type=int, default=1,
                        help="worker processes for the sweep (rayon analog)")
    args = parser.parse_args(argv)
    if not 1 <= args.leader <= args.processes:
        parser.error(
            f"--leader {args.leader} out of range: process ids are "
            f"1..{args.processes}"
        )

    points = [
        {
            "protocol": args.protocol,
            "n": args.processes,
            "f": args.faults,
            "clients": clients,
            "conflict_rate": args.conflict_rate,
            "keys_per_command": args.keys_per_command,
            "commands_per_client": args.commands_per_client,
            "dataset": args.dataset,
            "regions": args.regions.split(",") if args.regions else None,
            "tiny_quorums": args.newt_tiny_quorums,
            "leader": args.leader,
            "seed": args.seed,
        }
        for clients in [int(c) for c in args.clients.split(",")]
    ]

    if args.parallel > 1 and len(points) > 1:
        import concurrent.futures
        import multiprocessing

        # spawn: workers must not inherit an initialized jax backend
        ctx = multiprocessing.get_context("spawn")
        with concurrent.futures.ProcessPoolExecutor(
            max_workers=min(args.parallel, len(points)), mp_context=ctx
        ) as pool:
            for line in pool.map(_run_point, points):
                print(line, flush=True)
    else:
        for point in points:
            print(_run_point(point), flush=True)


if __name__ == "__main__":
    main()
