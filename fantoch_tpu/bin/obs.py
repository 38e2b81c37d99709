"""Observability CLI: summarize / convert / diff span logs, watch and
scrape live telemetry.

The lifecycle tracing plane (fantoch_tpu/observability) writes JSONL
span logs and the telemetry plane windowed series; this CLI turns them
into answers:

    # per-stage latency breakdown (p50/p95/p99 per segment, end-to-end)
    python -m fantoch_tpu.bin.obs summarize trace.jsonl [more.jsonl ...]

    # Chrome/Perfetto trace-event JSON (load at ui.perfetto.dev)
    python -m fantoch_tpu.bin.obs to-perfetto trace.jsonl -o trace.json

    # structural diff of two traces (same-seed sim runs must be empty)
    python -m fantoch_tpu.bin.obs diff a.jsonl b.jsonl

    # live terminal view of a cluster's telemetry (series files, an obs
    # dir, or /metrics endpoints; --once renders a single frame); of a
    # device-step server also loop% (its loop's thread outside the
    # selector), cpu% (its two served threads) and stop (neither ran)
    python -m fantoch_tpu.bin.obs watch obs_dir/ 127.0.0.1:9090

    # one exposition scrape (raw Prometheus text, or parsed --json)
    python -m fantoch_tpu.bin.obs scrape 127.0.0.1:9090 --json

``summarize`` accepts several logs at once (a localhost cluster writes
one per process plus the client plane) and assembles spans across them.
No reference counterpart: fantoch's metrics_logger/tracer only ship
aggregates; this is the per-command attribution + live-telemetry layer
on top.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List


def _load(paths: List[str]) -> List[Dict[str, Any]]:
    """Load span logs (JSONL) and/or flight-recorder dumps (.json black
    boxes) into one event stream — the correlator consumes both alike."""
    from fantoch_tpu.observability.recorder import flight_events
    from fantoch_tpu.observability.tracer import read_trace

    events: List[Dict[str, Any]] = []
    for path in paths:
        if path.endswith(".json"):
            try:
                events.extend(flight_events([path]))
                continue
            except (AssertionError, ValueError, KeyError):
                pass  # not a flight dump: fall through to JSONL reading
        events.extend(read_trace(path))
    return events


def cmd_summarize(args) -> int:
    from fantoch_tpu.observability.report import summarize

    out = summarize(_load(args.trace))
    counters = out.get("device_counters")
    if counters and "device_busy_ms" in counters:
        # derived overlap metrics ride the machine-readable payload too,
        # so --json consumers get exactly what the human lines print
        # (CI smokes key on this instead of regex-scraping the text)
        from fantoch_tpu.observability.device import derive_idle_frac

        out["device_counters"] = counters = derive_idle_frac(dict(counters))
    if args.json:
        print(json.dumps(out, sort_keys=True))
        return 0
    print(f"spans: {out['spans']}  events: {out['events']}")
    coverage = ", ".join(
        f"{stage}={count}" for stage, count in out["stage_coverage"].items()
    )
    print(f"stage coverage: {coverage}")
    if out["monotonic_violations"]:
        print(f"MONOTONIC VIOLATIONS: {out['monotonic_violations']}")
    print(f"{'segment':<22}{'count':>8}{'mean':>10}{'p50':>10}{'p95':>10}{'p99':>10}")
    rows = dict(out.get("segments", {}))
    if "end_to_end" in out:
        rows["end_to_end"] = out["end_to_end"]
    for name, row in rows.items():
        print(
            f"{name:<22}{row['count']:>8}"
            f"{row['mean_us'] / 1000:>9.2f}m"
            f"{row['p50_us'] / 1000:>9.2f}m"
            f"{row['p95_us'] / 1000:>9.2f}m"
            f"{row['p99_us'] / 1000:>9.2f}m"
        )
    counters = out.get("device_counters", {})
    for name, value in sorted(counters.items()):
        print(f"counter {name} = {value}")
    _print_overlap(counters)
    _print_planes(counters)
    _print_compile(counters)
    _print_overload(counters)
    _print_audit(counters)
    return 0


def _print_compile(counters) -> int:
    """One-line XLA compile readout: how many backend compiles the run
    paid and their cumulative wall (observability/device.py) — a ~50s
    cold compile starving heartbeats is invisible in a count of 1."""
    if "jax_recompiles" not in counters and "jax_compile_ms" not in counters:
        return 0
    ms = counters.get("jax_compile_ms", 0.0)
    print(
        f"compile: {int(counters.get('jax_recompiles', 0))} XLA backend "
        f"compile(s), {ms / 1000:.1f}s cumulative wall"
    )
    return 0


def _print_planes(counters) -> int:
    """One line per resident device plane (table / pred / graph): how
    many fused dispatches, how many host->device window materializations
    (``resident_uploads`` — the residency invariant: one lazy initial
    plus compaction/grow/restore re-uploads, never one per batch), and
    the current slot capacity gauge."""
    shown = 0
    for prefix, label in (
        ("table_plane", "table plane"),
        ("pred_plane", "pred plane"),
        ("graph_plane", "graph plane"),
    ):
        if f"{prefix}_dispatches" not in counters:
            continue
        parts = [
            f"dispatches {int(counters.get(f'{prefix}_dispatches', 0))}",
            f"uploads {int(counters.get(f'{prefix}_resident_uploads', 0))}",
            f"kernel {counters.get(f'{prefix}_kernel_ms', 0.0):.1f}ms",
        ]
        cap = counters.get(f"{prefix}_slot_capacity")
        if cap is not None:
            parts.append(f"capacity {int(cap)}")
        # accelerator fault tolerance (executor/device_plane.py): the
        # max-folded health gauge plus failover/rebuild tallies and the
        # wall spent serving from the host twin
        health = counters.get(f"{prefix}_health")
        if health is not None:
            from fantoch_tpu.executor.device_plane import HEALTH_NAMES

            parts.append(f"health {HEALTH_NAMES[int(health)]}")
        failovers = int(counters.get(f"{prefix}_failovers", 0))
        rebuilds = int(counters.get(f"{prefix}_rebuilds", 0))
        if failovers or rebuilds:
            parts.append(f"failovers {failovers}")
            parts.append(f"rebuilds {rebuilds}")
            parts.append(
                f"degraded {counters.get(f'{prefix}_degraded_ms', 0.0):.1f}ms"
            )
        print(f"{label}: " + "  ".join(parts))
        shown += 1
    return shown


def _print_audit(counters) -> int:
    """One-line consistency-audit readout from the digest-exchange
    counters (Config.execution_digests): how many peer summaries were
    cross-checked, over how many keys, and whether any mismatch (a
    replica fork -> typed DivergenceError) was ever observed."""
    names = ("digest_checks", "digest_mismatches", "digest_keys")
    if not any(name in counters for name in names):
        return 0
    mismatches = int(counters.get("digest_mismatches", 0))
    parts = [
        f"digest checks {int(counters.get('digest_checks', 0))}",
        f"keys {int(counters.get('digest_keys', 0))}",
        f"mismatches {mismatches}" + (" (DIVERGED)" if mismatches else ""),
    ]
    print("audit: " + "  ".join(parts))
    return 0


def _print_overload(counters) -> int:
    """One-line overload-plane readout from the queue/shed counters
    (run/backpressure.py): worst queue depth high-watermark across
    processes, total sheds, and backpressure pauses — the signal that a
    run was (or was not) operating past its admission edge."""
    names = ("queue_depth_hwm", "shed_submissions", "backpressure_pauses")
    if not any(name in counters for name in names):
        return 0
    parts = [
        f"queue depth hwm {int(counters.get('queue_depth_hwm', 0))}",
        f"sheds {int(counters.get('shed_submissions', 0))}",
        f"backpressure pauses {int(counters.get('backpressure_pauses', 0))}",
    ]
    print("overload: " + "  ".join(parts))
    return 0


def _print_overlap(counters) -> int:
    """One-line dispatch/drain overlap readout from the per-dispatch
    device counters (run/pipeline.py): how the serving wall split
    between host batch assembly (dispatch), host drain (fetch + emit),
    and device-busy time — and the ``device_idle_frac`` the pipelined
    loop is meant to drive toward 0."""
    from fantoch_tpu.observability.device import derive_idle_frac

    if not any(k in counters for k in ("device_dispatch_ms", "device_busy_ms")):
        return 0
    counters = derive_idle_frac(dict(counters))
    dispatch = counters.get("device_dispatch_ms", 0.0)
    drain = counters.get("device_drain_ms", 0.0)
    fetch = counters.get("device_fetch_ms", 0.0)
    busy = counters.get("device_busy_ms", 0.0)
    span = counters.get("device_span_ms", 0.0)
    parts = [
        f"dispatch {dispatch:.1f}ms",
        f"drain {drain:.1f}ms (fetch {fetch:.1f}ms)",
    ]
    if span:
        parts.append(f"device busy {busy:.1f}ms of {span:.1f}ms span")
    if "device_idle_frac" in counters:
        parts.append(f"idle_frac {counters['device_idle_frac']:.3f}")
    depth = counters.get("device_pipeline_depth")
    if depth:
        parts.append(f"depth {int(depth)}")
    pipelined = counters.get("device_pipelined_rounds")
    if pipelined is not None:
        parts.append(f"pipelined_rounds {int(pipelined)}")
    print("device overlap: " + "  ".join(parts))
    return 0


def _scrape_url(target: str, timeout: float = 5.0) -> str:
    """Fetch one exposition endpoint.  ``host:port`` expands to
    ``http://host:port/metrics``."""
    import urllib.request

    url = target
    if "://" not in url:
        url = f"http://{url}"
    if not url.rstrip("/").endswith("/metrics"):
        url = url.rstrip("/") + "/metrics"
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode()


def cmd_scrape(args) -> int:
    """One scrape per target: raw Prometheus text, or parsed ``--json``
    (``{metric: {"label=value,...": value}}``) for scripts."""
    from fantoch_tpu.observability.exposition import parse_prometheus

    out: Dict[str, Any] = {}
    for target in args.target:
        text = _scrape_url(target)
        if not args.json:
            print(text, end="")
            continue
        parsed = parse_prometheus(text)
        out[target] = {
            name: {
                ",".join(f"{k}={v}" for k, v in labels): value
                for labels, value in samples.items()
            }
            for name, samples in parsed.items()
        }
    if args.json:
        print(json.dumps(out, sort_keys=True))
    return 0


def _watch_sources(targets: List[str]) -> Dict[str, Dict[str, Any]]:
    """Latest telemetry window per source across every target: series
    files, obs directories (globbing ``telemetry_*.jsonl``), or live
    ``/metrics`` endpoints (parsed back into a window-shaped row)."""
    import glob

    from fantoch_tpu.observability.exposition import parse_prometheus
    from fantoch_tpu.observability.timeseries import latest_windows, read_series

    latest: Dict[str, Dict[str, Any]] = {}
    for target in targets:
        if os.path.isdir(target):
            paths = sorted(glob.glob(os.path.join(target, "telemetry_*.jsonl")))
        elif os.path.exists(target):
            paths = [target]
        else:
            # an endpoint: synthesize one window row from the live
            # sample.  A failed scrape (server restarting, typo'd path
            # falling through to the URL branch) degrades to an error
            # row — the live view must keep rendering, not die with a
            # traceback mid-watch
            try:
                parsed = parse_prometheus(_scrape_url(target))
            except Exception as exc:  # noqa: BLE001 — any scrape failure degrades
                latest[target] = {"src": target, "ctr": {}, "g": {},
                                  "rate": {}, "h": {}, "t": 0, "seq": -1,
                                  "err": str(exc)}
                continue
            ctr: Dict[str, float] = {}
            gauges: Dict[str, float] = {}
            for name, samples in parsed.items():
                value = next(iter(samples.values()))
                if name.startswith("fantoch_") and name.endswith("_total"):
                    ctr[name[len("fantoch_"):-len("_total")]] = value
                elif name.startswith("fantoch_") and not name.endswith(
                    ("_bucket", "_sum", "_count")
                ):
                    gauges[name[len("fantoch_"):]] = value
            latest[target] = {"src": target, "ctr": ctr, "g": gauges,
                              "rate": {}, "h": {}, "t": 0, "seq": -1}
            continue
        for path in paths:
            for src, window in latest_windows(read_series(path)).items():
                # several files may carry the same source name (one
                # client plane per pool): fall back to the file stem
                key = (
                    src
                    if src not in latest
                    else os.path.splitext(os.path.basename(path))[0]
                )
                latest[key] = window
    return latest


def _render_watch(latest: Dict[str, Dict[str, Any]]) -> str:
    """One table frame: per source, submit/reply rates, the client or
    end-to-end latency window, queue depth, sheds, of a device-step
    server started through ``bin/server`` the share of the window its
    loop's thread spent outside the selector (``loop%``:
    ``loop_busy_ms``'s rate over wall time, 100 is a loop that never
    sleeps; "-" where the counter is absent), device idle, and of a
    device-step server the CPU its two served threads used in the window
    (``host_cpu_ms``'s rate over wall time: 100 is one core) and the
    milliseconds so far in which neither of them ran
    (``loop_stopped_ms``)."""
    lines = [
        f"{'source':<12}{'submit/s':>10}{'reply/s':>10}{'p50ms':>8}"
        f"{'p95ms':>8}{'p99ms':>8}{'queue':>7}{'sheds':>7}{'loop%':>7}"
        f"{'idle':>6}{'cpu%':>6}{'stop':>8}"
    ]
    for src in sorted(latest):
        window = latest[src]
        rate = window.get("rate", {})
        ctr = window.get("ctr", {})
        gauges = window.get("g", {})
        hist = window.get("h", {}).get("latency_ms")
        cpu_rate = rate.get("host_cpu_ms")
        loop_rate = rate.get("loop_busy_ms")

        def _num(value, fmt="{:.1f}"):
            return "-" if value is None else fmt.format(value)

        lines.append(
            f"{src:<12}"
            f"{_num(rate.get('submitted')):>10}"
            f"{_num(rate.get('replied')):>10}"
            f"{_num(hist and hist.get('p50'), '{:.0f}'):>8}"
            f"{_num(hist and hist.get('p95'), '{:.0f}'):>8}"
            f"{_num(hist and hist.get('p99'), '{:.0f}'):>8}"
            f"{_num(gauges.get('queue_depth'), '{:.0f}'):>7}"
            f"{_num(ctr.get('shed_submissions'), '{:.0f}'):>7}"
            # a rate is per second: ms outside the selector a second, over 10, is %
            f"{_num(None if loop_rate is None else loop_rate / 10.0, '{:.0f}'):>7}"
            f"{_num(gauges.get('device_idle_frac'), '{:.2f}'):>6}"
            # a rate is per second: ms of CPU a second, over 10, is %
            f"{_num(cpu_rate and cpu_rate / 10.0, '{:.0f}'):>6}"
            f"{_num(ctr.get('loop_stopped_ms'), '{:.0f}'):>8}"
        )
    errors = [
        f"! {src}: {window['err']}"
        for src, window in sorted(latest.items())
        if "err" in window
    ]
    return "\n".join(lines + errors)


def cmd_watch(args) -> int:
    """Live terminal view of a cluster's telemetry: re-render the latest
    window per source every ``--interval`` seconds (``--once`` renders a
    single frame — the CI spelling)."""
    while True:
        latest = _watch_sources(args.target)
        frame = _render_watch(latest)
        if args.once:
            print(frame)
            return 0 if latest else 1
        # full-frame repaint (clear + home), like watch(1)
        sys.stdout.write("\x1b[2J\x1b[H" + frame + "\n")
        sys.stdout.flush()
        time.sleep(args.interval)


def cmd_critpath(args) -> int:
    """Cross-process critical-path attribution: stitch spans causally
    over the message edges, resolve clock offsets, and print the p99
    blame — which stage, which peer, which dependency."""
    from fantoch_tpu.observability.critpath import critpath_report

    report = critpath_report(
        _load(args.trace), percentile=args.percentile,
        exemplars=args.exemplars,
    )
    if args.json:
        print(json.dumps(report, sort_keys=True))
        return 0
    print(
        f"spans: {report['spans']}  stitched: {report['stitched']} "
        f"({report['stitch_rate'] * 100:.1f}%)  clock: {report['clock']}"
    )
    if report["telescoping_violations"]:
        print(f"TELESCOPING VIOLATIONS: {report['telescoping_violations']}")
    p99 = report["p99"]
    print(
        f"p99 cohort: {p99['count']} span(s) >= "
        f"{p99['threshold_us'] / 1000:.2f}ms"
        + (
            f"; dominant stage {p99['dominant_stage']}"
            if p99["dominant_stage"]
            else ""
        )
    )
    print(f"{'stage':<22}{'all mean':>12}{'p99 mean':>12}")
    all_means = report["stage_means_us"]
    for name in sorted(
        set(all_means) | set(p99["stage_means_us"]),
        key=lambda n: -p99["stage_means_us"].get(n, 0),
    ):
        print(
            f"{name:<22}"
            f"{all_means.get(name, 0) / 1000:>11.2f}m"
            f"{p99['stage_means_us'].get(name, 0) / 1000:>11.2f}m"
        )
    for label, table in (
        ("quorum blame (all)", report["quorum_blame"]),
        ("quorum blame (p99)", report["p99_quorum_blame"]),
    ):
        for pid, row in sorted(
            table.items(), key=lambda kv: -kv[1]["count"]
        ):
            print(
                f"{label}: p{pid} blocking {row['count']}x  "
                f"mean wait {row['mean_wait_us'] / 1000:.2f}ms "
                f"(net {row['mean_net_us'] / 1000:.2f}ms, "
                f"remote {row['mean_remote_us'] / 1000:.2f}ms)"
            )
    for label, row in (
        ("ingest-batching (all)", report["ingest_batching"]),
        ("ingest-batching (p99)", report["p99_ingest_batching"]),
    ):
        if row["spans"]:
            print(
                f"{label}: {row['spans']} span(s)  "
                f"mean hold {row['mean_us'] / 1000:.2f}ms  "
                f"max {row['max_us'] / 1000:.2f}ms"
            )
    for row in report["peers"]:
        print(
            f"peer skew: p{row['pid']} -> p{row['peer']} offset "
            f"{row['offset_us']}us (rtt {row['rtt_us']}us)"
        )
    if report["recovered_spans"]:
        print(f"recovered spans: {report['recovered_spans']}")
    for vector in report["exemplars"]:
        stages = "  ".join(
            f"{name} {us / 1000:.2f}m"
            for name, us in sorted(
                vector["stages"].items(), key=lambda kv: -kv[1]
            )
        )
        quorum = vector["blame"].get("quorum")
        blamed = f" [quorum p{quorum['pid']}]" if quorum else ""
        print(
            f"exemplar rifl {vector['rifl'][0]}.{vector['rifl'][1]} "
            f"total {vector['total_us'] / 1000:.2f}ms{blamed}: {stages}"
        )
    device = report.get("device")
    if device:
        _print_overlap(device)
    return 0


def cmd_to_perfetto(args) -> int:
    from fantoch_tpu.observability.perfetto import write_perfetto

    count = write_perfetto(_load(args.trace), args.output)
    print(f"wrote {count} trace events to {args.output}")
    return 0


def cmd_diff(args) -> int:
    from fantoch_tpu.observability.report import diff_events, diff_stages
    from fantoch_tpu.observability.tracer import read_trace

    if args.stages:
        # tolerance diff of assembled stage latencies: the comparison
        # that works for wall-clock run-layer traces, where byte
        # identity can never hold
        verdict = diff_stages(
            read_trace(args.a), read_trace(args.b),
            tol_frac=args.tol_frac, tol_abs_us=args.tol_abs_us,
        )
        for line in verdict["mismatches"]:
            print(line)
        for side, rifls in (("a", verdict["only_a"]), ("b", verdict["only_b"])):
            if rifls:
                print(f"spans only in {side}: {rifls[:10]}")
        if not verdict["mismatches"] and not verdict["only_a"] and not verdict["only_b"]:
            print(
                f"stage latencies agree within tolerance "
                f"({verdict['matched']} matched spans)"
            )
            return 0
        return 1
    mismatches = diff_events(read_trace(args.a), read_trace(args.b))
    for line in mismatches:
        print(line)
    if not mismatches:
        print("traces identical")
        return 0
    return 1


def cmd_curves(args) -> int:
    """Capacity/SLO report over a scenario curves document: the knee
    table (per curve: points, detected saturation knee, p99 at the knee)
    and every per-cell SLO verdict (typed pass/fail, targets from the
    spec's slo block).  Exit 1 when any verdict fails — the CI shape."""
    import json as _json
    import os

    from fantoch_tpu.plot.db import load_curves

    path = args.curves
    if os.path.isdir(path):
        path = os.path.join(path, "curves.json")
    doc = load_curves(path)
    if args.json:
        print(_json.dumps(doc, indent=1, sort_keys=True))
    else:
        print(f"scenario {doc['scenario']} ({doc['timeline']} timeline, "
              f"seed {doc['seed']})")
        header = (
            f"{'curve':<24} {'points':>6} {'knee offered/s':>14} "
            f"{'knee goodput/s':>14} {'p99@knee ms':>12}"
        )
        print(header)
        for curve in doc["curves"]:
            label = f"{curve['protocol']} n={curve['n']} f={curve['f']}"
            knee = curve.get("knee")
            if knee is None:
                print(f"{label:<24} {len(curve['points']):>6} "
                      f"{'unsaturated':>14} {'-':>14} {'-':>12}")
                continue
            offered = knee["offered_cmds_per_s"]
            print(
                f"{label:<24} {len(curve['points']):>6} "
                f"{offered if offered is not None else '-':>14} "
                f"{knee['goodput_cmds_per_s']:>14} "
                f"{knee['p99_ms'] if knee['p99_ms'] is not None else '-':>12}"
            )
    failed = 0
    checked = 0
    for curve in doc["curves"]:
        for verdict in curve.get("slo", []):
            if not verdict["checks"]:
                continue
            checked += 1
            status = "PASS" if verdict["pass"] else "FAIL"
            if not verdict["pass"]:
                failed += 1
            if not args.json:
                details = ", ".join(
                    f"{name} {check['actual']} vs {check['target']} "
                    f"{'ok' if check['pass'] else 'VIOLATED'}"
                    for name, check in sorted(verdict["checks"].items())
                )
                print(f"  slo {status} {verdict['cell']}: {details}")
    if not args.json and checked == 0:
        print("  (no SLO declared in the spec)")
    return 1 if failed else 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="obs", description="dot-lifecycle trace tooling"
    )
    sub = parser.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("summarize", help="per-stage latency breakdown")
    p.add_argument("trace", nargs="+", help="JSONL span log(s)")
    p.add_argument("--json", action="store_true", help="machine-readable output")
    p.set_defaults(fn=cmd_summarize)

    p = sub.add_parser("scrape", help="fetch /metrics exposition endpoint(s)")
    p.add_argument("target", nargs="+",
                   help="endpoint (host:port or full URL)")
    p.add_argument("--json", action="store_true",
                   help="parse the exposition into JSON per target")
    p.set_defaults(fn=cmd_scrape)

    p = sub.add_parser(
        "watch", help="live terminal view of telemetry series/endpoints"
    )
    p.add_argument("target", nargs="+",
                   help="series file, obs dir, or endpoint (host:port)")
    p.add_argument("--interval", type=float, default=1.0, metavar="S")
    p.add_argument("--once", action="store_true",
                   help="render one frame and exit (CI smoke)")
    p.set_defaults(fn=cmd_watch)

    p = sub.add_parser(
        "critpath",
        help="cross-process critical-path attribution (p99 blame)",
    )
    p.add_argument("trace", nargs="+",
                   help="JSONL span log(s) and/or flight dump(s)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report")
    p.add_argument("--percentile", type=float, default=0.99,
                   help="tail cohort threshold (default 0.99)")
    p.add_argument("--exemplars", type=int, default=3,
                   help="worst spans printed with full vectors")
    p.set_defaults(fn=cmd_critpath)

    p = sub.add_parser("to-perfetto", help="convert to trace-event JSON")
    p.add_argument("trace", nargs="+", help="JSONL span log(s)")
    p.add_argument("-o", "--output", required=True, help="output .json path")
    p.set_defaults(fn=cmd_to_perfetto)

    p = sub.add_parser("diff", help="structural diff of two span logs")
    p.add_argument("a")
    p.add_argument("b")
    p.add_argument("--stages", action="store_true",
                   help="tolerance diff of assembled span stage "
                   "latencies (works for wall-clock traces from two "
                   "different runs; the default byte diff never can)")
    p.add_argument("--tol-frac", type=float, default=0.5,
                   help="relative tolerance per segment (default 0.5)")
    p.add_argument("--tol-abs-us", type=int, default=20_000,
                   help="absolute tolerance per segment (default 20ms)")
    p.set_defaults(fn=cmd_diff)

    p = sub.add_parser(
        "curves",
        help="scenario knee table + per-cell SLO verdicts "
        "(exp/scenarios.py curves.json)",
    )
    p.add_argument("curves",
                   help="curves.json path or a scenario output dir")
    p.add_argument("--json", action="store_true",
                   help="print the raw curves document")
    p.set_defaults(fn=cmd_curves)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
