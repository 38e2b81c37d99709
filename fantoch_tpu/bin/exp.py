"""Experiment driver CLI: run one experiment or a client sweep end to end.

Reference: fantoch_exp/src/bin/main.rs — the experiment harness entry
that launches a cluster, runs protocol + client binaries with generated
flags, and collects logs/metrics/profiles.  Here the testbed is
localhost subprocesses by default, or an SSH host list (the baremetal.rs
analog); ``--run-mode`` selects the Release/Flamegraph/Heaptrack analog
(release / cprofile / memory).

    python -m fantoch_tpu.bin.exp --protocol epaxos -n 3 -f 1 \\
        --clients-sweep 1,2,4 --commands-per-client 50 \\
        --output-dir ./exp_out --run-mode cprofile

    python -m fantoch_tpu.bin.exp --protocol newt -n 3 -f 1 \\
        --output-dir ./exp_out --hosts h1,h2,h3   # SSH testbed

Each experiment directory gets a manifest.json (config, pulled
artifacts, outcome) — the input `fantoch_tpu.plot.ResultsDB` indexes.
"""

from __future__ import annotations

import argparse
import json


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(
        prog="fantoch_tpu.bin.exp", description=__doc__
    )
    parser.add_argument("--protocol", required=True)
    parser.add_argument("--processes", "-n", type=int, required=True)
    parser.add_argument("--faults", "-f", type=int, required=True)
    parser.add_argument("--shard-count", type=int, default=1)
    clients_group = parser.add_mutually_exclusive_group()
    clients_group.add_argument("--clients", type=int, default=1,
                               help="clients per process (single experiment)")
    clients_group.add_argument("--clients-sweep", default=None,
                               help="comma list of client counts: one "
                               "experiment per point (the "
                               "throughput-latency curve shape)")
    parser.add_argument("--commands-per-client", type=int, default=100)
    parser.add_argument("--conflict-rate", type=int, default=50)
    parser.add_argument("--keys-per-command", type=int, default=1)
    parser.add_argument("--key-gen", choices=["conflict_rate", "zipf"],
                        default="conflict_rate")
    parser.add_argument("--zipf-coefficient", type=float, default=1.0)
    parser.add_argument("--batched-graph-executor", action="store_true")
    parser.add_argument("--device-step", action="store_true",
                        help="run the experiment against one --device-step "
                        "server (the TPU serving path) instead of an "
                        "n-process TCP mesh")
    parser.add_argument("--device-batch", type=int, default=256)
    parser.add_argument("--run-mode",
                        choices=["release", "cprofile", "memory"],
                        default="release")
    parser.add_argument("--output-dir", required=True)
    parser.add_argument("--hosts", default=None,
                        help="comma list of SSH hosts (default: localhost "
                        "subprocesses)")
    parser.add_argument("--client-timeout", type=int, default=600,
                        metavar="S")
    args = parser.parse_args(argv)

    from fantoch_tpu.exp import ExperimentConfig, run_experiment, run_sweep

    base = ExperimentConfig(
        protocol=args.protocol,
        n=args.processes,
        f=args.faults,
        shard_count=args.shard_count,
        clients_per_process=args.clients,
        commands_per_client=args.commands_per_client,
        key_gen=args.key_gen,
        conflict_rate=args.conflict_rate,
        zipf_coefficient=args.zipf_coefficient,
        keys_per_command=args.keys_per_command,
        batched_graph_executor=args.batched_graph_executor,
        device_step=args.device_step,
        device_batch=args.device_batch,
    )
    testbed = "localhost"
    if args.hosts:
        from fantoch_tpu.exp.testbed import HostsTestbed

        testbed = HostsTestbed(args.hosts.split(","))

    if args.clients_sweep:
        sweep = [int(c) for c in args.clients_sweep.split(",")]
        manifests = run_sweep(
            base, args.output_dir, sweep, testbed=testbed,
            client_timeout_s=args.client_timeout, run_mode=args.run_mode,
        )
    else:
        manifests = [
            run_experiment(
                base, args.output_dir, testbed=testbed,
                client_timeout_s=args.client_timeout,
                run_mode=args.run_mode,
            )
        ]
    for manifest in manifests:
        print(json.dumps({
            "name": manifest["name"],
            "run_mode": manifest["run_mode"],
            "outcome": manifest["outcome"],
        }), flush=True)


if __name__ == "__main__":
    main()
