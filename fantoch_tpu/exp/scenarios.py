"""Scenario observatory: declarative sweep factory + saturation curves.

The fantoch_exp/fantoch_plot multiplier (PAPER.md L7): every protocol,
nemesis, plane, and knob already in the repo becomes *comparable* only
when it rides a swept throughput-latency curve, not a single point.  A
:class:`ScenarioSpec` declares the whole cross product once — protocol
x (n, f) x fault plan (incl. device faults) x key skew x read/write mix
x multi-key txn mix x offered open-loop rate x Config knobs (pipeline /
ingest / planes) x placement — and :func:`expand` turns it into
a deterministic run matrix:

  * same spec + seed => byte-identical expansion
    (:func:`canonical_expansion`), and on the sim timeline byte-identical
    per-cell traces (every cell seed is a stable hash of the spec seed
    and the cell name — never Python's randomized ``hash``);
  * placement is a config *output*: ``{"mode": "search"}`` runs the
    planner (:meth:`fantoch_tpu.planner.Search.best_placement`) under the
    scenario's latency objective and records the chosen regions (plus the
    identity-placement baseline it beat) in the expansion manifest;
  * zipf specs report the expected multi-shard / multi-key command
    fraction (``bin/shard_distribution.compute_distribution``) as the
    partial-replication planner input.

:func:`run_scenario` executes each cell through the existing harnesses —
the deterministic sim runner (virtual-time open-loop Poisson arrivals,
trace + telemetry capture into the per-cell obs dir) or the localhost
TCP ``run_overload_phase`` — then sweeps the offered-rate axis into full
throughput-latency CURVES: p50/p95/p99 vs goodput per point, saturation
knee detection (:func:`detect_knee`), shed/degraded annotations from the
overload (PR 8) and accelerator-fault (PR 17) counters, and typed
per-cell SLO verdicts (target p99 / min goodput declared in the spec).
Results land as ``plot/db.py``-indexable per-cell manifests plus one
machine-readable ``curves.json`` (``plot.db.save_curves``) rendered by
``plot.plots.saturation_curves``.

Saturation on the sim timeline is real, not simulated noise: goodput is
measured over the client-reconstructed serving span (first submit ->
last completion), and as the offered rate grows the arrival window
compresses below the fixed commit-latency tail, capping goodput at
``total_commands / completion_span`` — a deterministic knee.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

# spec protocol name -> lazy export in fantoch_tpu.protocol
_PROTOCOLS = {
    "basic": "Basic",
    "epaxos": "EPaxos",
    "atlas": "Atlas",
    "newt": "Newt",
    "fpaxos": "FPaxos",
    "caesar": "Caesar",
}


def protocol_class(name: str):
    import fantoch_tpu.protocol as protocol

    if name not in _PROTOCOLS:
        raise ValueError(
            f"unknown protocol {name!r} (know {sorted(_PROTOCOLS)})"
        )
    return getattr(protocol, _PROTOCOLS[name])


@dataclass(frozen=True)
class ScenarioSpec:
    """One declarative scenario: the full sweep cross product + SLO.

    JSON round-trips via :meth:`to_dict` / :meth:`from_dict` (and
    :func:`load_spec` for files), so a spec file IS the experiment."""

    name: str
    protocols: Tuple[str, ...] = ("epaxos",)
    # (n, f) pairs
    sites: Tuple[Tuple[int, int], ...] = ((3, 1),)
    timeline: str = "sim"  # "sim" (virtual time) | "run" (localhost TCP)
    seed: int = 0
    planet: str = "gcp"
    # workload axes
    clients_per_process: int = 2
    commands_per_client: int = 20
    key_gen: str = "conflict_rate"  # or "zipf"
    conflict_rate: int = 50
    zipf_coefficient: float = 1.0
    keys_per_shard: int = 1_000_000
    keys_per_command: int = 1
    payload_size: int = 0
    read_only_percentage: int = 0
    # partial-replication planner input (ROADMAP item 2 prep): the shard
    # count the zipf multi-shard fraction is *reported* for in the
    # expansion manifest; execution stays single-shard
    planner_shard_count: int = 1
    # offered open-loop rate axis (cluster cmds/s).  Explicit points, or
    # a geometric ladder {"start_cmds_per_s", "factor", "points"} swept
    # toward saturation; both empty = one closed-loop cell
    rates: Tuple[float, ...] = ()
    rate_sweep: Optional[Dict[str, Any]] = None
    # sim-only fault schedule (sim/faults.FaultPlan.to_dict shape,
    # device faults included)
    fault_plan: Optional[Dict[str, Any]] = None
    # Config.with_ overrides (pipeline depth, ingest deadline, device
    # planes, admission limit, trace/telemetry knobs, ...)
    knobs: Dict[str, Any] = field(default_factory=dict)
    # placement: {"mode": "regions", "regions": [...], "clients": [...]}
    # pins it; {"mode": "search", "candidates": [...], "clients": [...],
    # "objective": "mean"|"p95"|"p99"|"max", "colocated": bool} makes it
    # a planner OUTPUT; {"mode": "closest"} (default) takes the planet's
    # first n regions (sorted)
    placement: Dict[str, Any] = field(
        default_factory=lambda: {"mode": "closest"}
    )
    # {"p99_ms": float, "min_goodput_cmds_per_s": float} — either key
    # optional; verdicts are typed pass/fail per cell
    slo: Optional[Dict[str, Any]] = None
    extra_sim_time_ms: int = 0

    def __post_init__(self):
        if self.timeline not in ("sim", "run"):
            raise ValueError(f"timeline must be sim|run, got {self.timeline!r}")
        if self.key_gen not in ("conflict_rate", "zipf"):
            raise ValueError(f"unknown key_gen {self.key_gen!r}")
        for name in self.protocols:
            if name not in _PROTOCOLS:
                raise ValueError(f"unknown protocol {name!r}")
        if self.timeline == "run" and self.fault_plan is not None:
            raise ValueError(
                "fault_plan is sim-only (the run timeline has no nemesis "
                "hook in run_overload_phase)"
            )

    def to_dict(self) -> Dict[str, Any]:
        out = dataclasses.asdict(self)
        out["protocols"] = list(self.protocols)
        out["sites"] = [list(site) for site in self.sites]
        out["rates"] = list(self.rates)
        return out

    @staticmethod
    def from_dict(data: Dict[str, Any]) -> "ScenarioSpec":
        data = dict(data)
        data["protocols"] = tuple(data.get("protocols", ("epaxos",)))
        data["sites"] = tuple(
            tuple(site) for site in data.get("sites", ((3, 1),))
        )
        data["rates"] = tuple(data.get("rates", ()))
        known = {f.name for f in dataclasses.fields(ScenarioSpec)}
        unknown = set(data) - known
        if unknown:
            raise ValueError(f"unknown spec field(s): {sorted(unknown)}")
        return ScenarioSpec(**data)


def load_spec(path: str) -> ScenarioSpec:
    with open(path) as fh:
        return ScenarioSpec.from_dict(json.load(fh))


# --- deterministic expansion ---


def cell_seed(spec_seed: int, cell_name: str) -> int:
    """Stable per-cell seed: sha256 over ``"<seed>:<cell>"`` — never
    Python's per-process-randomized ``hash`` (same spec + seed must
    derive the same seeds on every machine, every run)."""
    digest = hashlib.sha256(f"{spec_seed}:{cell_name}".encode()).digest()
    return int.from_bytes(digest[:4], "big")


def resolve_rates(spec: ScenarioSpec) -> List[Optional[float]]:
    """The offered-rate axis: explicit points win; else the geometric
    ladder; else one closed-loop cell (rate None)."""
    if spec.rates:
        return [float(r) for r in spec.rates]
    if spec.rate_sweep:
        start = float(spec.rate_sweep["start_cmds_per_s"])
        factor = float(spec.rate_sweep.get("factor", 2.0))
        points = int(spec.rate_sweep.get("points", 4))
        assert start > 0 and factor > 1 and points >= 1, spec.rate_sweep
        return [start * factor**i for i in range(points)]
    return [None]


def _rate_tag(rate: Optional[float]) -> str:
    if rate is None:
        return "closed"
    text = f"{rate:g}".replace(".", "_")
    return f"r{text}"


def _planet(spec: ScenarioSpec, planet=None):
    if planet is not None:
        return planet
    from fantoch_tpu.core.planet import Planet

    return Planet.new(spec.planet)


def _region_names(regions) -> List[str]:
    return [r.name for r in regions]


def _resolve_placement(
    spec: ScenarioSpec, protocol: str, n: int, f: int, planet
) -> Dict[str, Any]:
    """Server + client regions for one (protocol, n, f) — searched under
    the scenario's latency objective when the spec asks for it, so
    placement is an expansion OUTPUT recorded in the manifest."""
    from fantoch_tpu.core.planet import Region

    mode = spec.placement.get("mode", "closest")
    if mode == "regions":
        servers = [Region(name) for name in spec.placement["regions"][:n]]
        assert len(servers) == n, (
            f"placement pins {len(servers)} regions, cell needs n={n}"
        )
        clients = [
            Region(name) for name in spec.placement.get("clients", [])
        ] or list(servers)
        return {
            "mode": "regions",
            "regions": _region_names(servers),
            "clients": _region_names(clients),
        }
    if mode == "closest":
        servers = sorted(planet.regions())[:n]
        return {
            "mode": "closest",
            "regions": _region_names(servers),
            "clients": _region_names(servers),
        }
    if mode == "search":
        from fantoch_tpu.planner import Search

        names = spec.placement.get("candidates")
        candidates = (
            [Region(name) for name in names]
            if names
            else sorted(planet.regions())
        )
        client_names = spec.placement.get("clients")
        clients = (
            [Region(name) for name in client_names]
            if client_names
            else list(candidates)
        )
        objective = spec.placement.get("objective", "mean")
        colocated = bool(spec.placement.get("colocated", False))
        search = Search(planet, candidates, clients)
        best = search.best_placement(
            protocol, n, f, objective=objective, colocated=colocated
        )
        identity = search.placement_objective(
            candidates[:n], protocol, f, objective=objective,
            colocated=colocated,
        )
        return {
            "mode": "search",
            "objective": objective,
            "objective_ms": best.value,
            "identity_regions": _region_names(candidates[:n]),
            "identity_objective_ms": identity,
            "regions": _region_names(best.regions),
            "clients": _region_names(clients) if not colocated
            else _region_names(best.regions),
        }
    raise ValueError(f"unknown placement mode {mode!r}")


def _workload_report(spec: ScenarioSpec) -> Dict[str, Any]:
    """The expansion manifest's workload section.  Zipf specs carry the
    expected multi-shard / multi-key fraction at the spec's planner
    shard count (bin/shard_distribution) — the partial-replication
    planner input the sweep exists to feed."""
    out: Dict[str, Any] = {
        "key_gen": spec.key_gen,
        "keys_per_command": spec.keys_per_command,
        "read_only_percentage": spec.read_only_percentage,
        "payload_size": spec.payload_size,
    }
    if spec.key_gen == "zipf":
        from fantoch_tpu.bin.shard_distribution import compute_distribution

        out["zipf_coefficient"] = spec.zipf_coefficient
        out.update(
            compute_distribution(
                shard_count=spec.planner_shard_count,
                keys_per_command=spec.keys_per_command,
                coefficient=spec.zipf_coefficient,
                keys_per_shard=spec.keys_per_shard,
                commands=2000,
                seed=spec.seed,
            )
        )
    else:
        out["conflict_rate"] = spec.conflict_rate
    return out


def expand(spec: ScenarioSpec, planet=None) -> Dict[str, Any]:
    """Spec -> run matrix.  Pure of wall clock and process state: the
    manifest depends only on (spec, planet dataset), so re-expansion is
    byte-identical (:func:`canonical_expansion`)."""
    planet = _planet(spec, planet)
    rates = resolve_rates(spec)
    placements: Dict[str, Dict[str, Any]] = {}
    cells: List[Dict[str, Any]] = []
    for protocol in spec.protocols:
        for n, f in spec.sites:
            site_key = f"{protocol}_n{n}_f{f}"
            placement = _resolve_placement(spec, protocol, n, f, planet)
            placements[site_key] = placement
            for rate in rates:
                name = f"{site_key}_{_rate_tag(rate)}"
                cells.append(
                    {
                        "index": len(cells),
                        "name": name,
                        "protocol": protocol,
                        "n": n,
                        "f": f,
                        "rate_cmds_per_s": rate,
                        "seed": cell_seed(spec.seed, name),
                        "regions": placement["regions"],
                        "client_regions": placement["clients"],
                    }
                )
    return {
        "scenario": spec.name,
        "spec": spec.to_dict(),
        "workload": _workload_report(spec),
        "placements": placements,
        "cells": cells,
    }


def canonical_expansion(spec: ScenarioSpec, planet=None) -> str:
    """The byte-identity contract: canonical JSON (sorted keys, fixed
    separators) of :func:`expand` — same spec + seed => same bytes."""
    return json.dumps(
        expand(spec, planet), sort_keys=True, separators=(",", ":")
    )


# --- cell execution ---


def _build_config(spec: ScenarioSpec, n: int, f: int):
    from fantoch_tpu.core.config import Config

    config = Config(
        n=n,
        f=f,
        shard_count=1,
        gc_interval_ms=100,
        executor_executed_notification_interval_ms=100,
    )
    if spec.knobs:
        config = config.with_(**spec.knobs)
    return config


def _build_workload(spec: ScenarioSpec):
    from fantoch_tpu.client.key_gen import ZipfKeyGen
    from fantoch_tpu.client.workload import Workload
    from fantoch_tpu.client import ConflictRateKeyGen

    if spec.key_gen == "zipf":
        key_gen = ZipfKeyGen(spec.zipf_coefficient, spec.keys_per_shard)
    else:
        key_gen = ConflictRateKeyGen(spec.conflict_rate)
    return Workload(
        shard_count=1,
        key_gen=key_gen,
        keys_per_command=spec.keys_per_command,
        commands_per_client=spec.commands_per_client,
        payload_size=spec.payload_size,
        read_only_percentage=spec.read_only_percentage,
    )


def _percentile_ms(latencies_us: Sequence[int], q: float) -> Optional[float]:
    if not latencies_us:
        return None
    index = min(len(latencies_us) - 1, int(len(latencies_us) * q))
    return round(latencies_us[index] / 1000.0, 3)


def _run_sim_cell(
    spec: ScenarioSpec, cell: Dict[str, Any], cell_dir: str, planet
) -> Dict[str, Any]:
    from fantoch_tpu.core.planet import Region
    from fantoch_tpu.sim.faults import FaultPlan
    from fantoch_tpu.sim.runner import Runner

    config = _build_config(spec, cell["n"], cell["f"])
    regions = [Region(name) for name in cell["regions"]]
    client_regions = [Region(name) for name in cell["client_regions"]]
    rate = cell["rate_cmds_per_s"]
    client_count = spec.clients_per_process * len(client_regions)
    per_client = rate / client_count if rate is not None else None
    fault_plan = (
        FaultPlan.from_dict(spec.fault_plan)
        if spec.fault_plan is not None
        else None
    )
    trace_path = (
        os.path.join(cell_dir, "trace.jsonl")
        if config.trace_sample_rate > 0
        else None
    )
    runner = Runner(
        protocol_class(cell["protocol"]),
        planet,
        config,
        _build_workload(spec),
        spec.clients_per_process,
        process_regions=regions,
        client_regions=client_regions,
        seed=cell["seed"],
        fault_plan=fault_plan,
        trace_path=trace_path,
        open_loop_rate_per_s=per_client,
        telemetry_path=os.path.join(cell_dir, "telemetry.jsonl"),
    )
    runner.run(spec.extra_sim_time_ms or None)
    summary = runner.serving_summary()
    latencies = summary["latencies_us"]
    span_s = summary["span_ms"] / 1000.0
    goodput = (
        round(summary["completed"] / span_s, 2) if span_s > 0 else 0.0
    )
    device = summary["device"]
    return {
        "commands": summary["completed"],
        "offered_cmds_per_s": rate,
        "goodput_cmds_per_s": goodput,
        # plots.heatmap/throughput_latency compatibility key
        "throughput_cmds_per_s": goodput,
        "span_s": round(span_s, 4),
        "latency_ms": {
            "p50": _percentile_ms(latencies, 0.50),
            "p95": _percentile_ms(latencies, 0.95),
            "p99": _percentile_ms(latencies, 0.99),
        },
        # overload/degraded annotations: the sim has no admission plane
        # (sheds live in the run layer), the device-fault counters fold
        # across every process's planes
        "sheds": 0,
        "queue_depth_hwm": 0,
        "degraded_ms": round(device.get("degraded_ms", 0.0), 3),
        "failovers": int(device.get("failovers", 0)),
    }


def _run_tcp_cell(
    spec: ScenarioSpec, cell: Dict[str, Any], cell_dir: str
) -> Dict[str, Any]:
    from fantoch_tpu.run.harness import run_overload_phase

    config = _build_config(spec, cell["n"], cell["f"])
    rate = cell["rate_cmds_per_s"]
    client_count = spec.clients_per_process * cell["n"]
    row = run_overload_phase(
        protocol_class(cell["protocol"]),
        config,
        _build_workload(spec),
        spec.clients_per_process,
        arrival_rate_per_s=(
            rate / client_count if rate is not None else None
        ),
        arrival_seed=cell["seed"],
    )
    device = row["device"] or {}
    return {
        "commands": row["completed"],
        "offered_cmds_per_s": rate,
        "goodput_cmds_per_s": row["goodput_cmds_per_s"],
        "throughput_cmds_per_s": row["goodput_cmds_per_s"],
        "latency_ms": {
            "p50": row["p50_ms"],
            "p95": row["p95_ms"],
            "p99": row["p99_ms"],
        },
        "sheds": row["sheds"] + row["shed_commands"],
        "queue_depth_hwm": row["queue_depth_hwm"],
        "degraded_ms": round(device.get("degraded_ms", 0.0), 3),
        "failovers": int(device.get("failovers", 0)),
    }


def run_cell(
    spec: ScenarioSpec, cell: Dict[str, Any], out_dir: str, planet=None
) -> Dict[str, Any]:
    """Execute one cell into ``<out_dir>/<cell name>/``: telemetry +
    trace capture (sim), and a ``plot.db.ResultsDB``-indexable
    ``manifest.json``.  Returns the outcome dict."""
    cell_dir = os.path.join(out_dir, cell["name"])
    os.makedirs(cell_dir, exist_ok=True)
    if spec.timeline == "sim":
        outcome = _run_sim_cell(spec, cell, cell_dir, _planet(spec, planet))
    else:
        outcome = _run_tcp_cell(spec, cell, cell_dir)
    manifest = {
        "config": {
            "scenario": spec.name,
            "timeline": spec.timeline,
            "protocol": cell["protocol"],
            "n": cell["n"],
            "f": cell["f"],
            "clients_per_process": spec.clients_per_process,
            "key_gen": spec.key_gen,
            "conflict_rate": spec.conflict_rate,
            "zipf_coefficient": spec.zipf_coefficient,
            "keys_per_command": spec.keys_per_command,
            "read_only_percentage": spec.read_only_percentage,
            "rate_cmds_per_s": cell["rate_cmds_per_s"],
            "seed": cell["seed"],
        },
        "outcome": outcome,
    }
    with open(os.path.join(cell_dir, "manifest.json"), "w") as fh:
        json.dump(manifest, fh, sort_keys=True, indent=1)
        fh.write("\n")
    return outcome


# --- saturation-knee detection ---


def detect_knee(
    points: Sequence[Dict[str, Any]],
    efficiency: float = 0.75,
    min_gain: float = 0.05,
    min_offered_growth: float = 0.2,
) -> Optional[int]:
    """Index (into offered-rate order) of the first saturated point, or
    None for an unsaturated curve.  A point is saturated when either

      * its serving efficiency (goodput / offered) fell below
        ``efficiency`` x the FIRST point's efficiency (capped at 1) —
        relative, because a finite open-loop run's serving span always
        carries a fixed straggler-arrival + commit-latency tail, so even
        an unsaturated point sits below offered by a workload-dependent
        constant the lightest point calibrates out; or
      * the offered rate grew by ``min_offered_growth`` over the previous
        point while goodput gained less than ``min_gain`` (the curve went
        flat: extra offered load buys nothing).

    The calibration point itself can never trip the efficiency rule (a
    one-point curve carries no saturation evidence).  Pure and
    deterministic — callers sort points by offered rate; points without
    an offered rate (closed loop) never saturate."""
    prev = None
    reference_eff = None
    for index, point in enumerate(points):
        offered = point.get("offered_cmds_per_s")
        goodput = point.get("goodput_cmds_per_s") or 0.0
        if offered is None or offered <= 0:
            prev = None
            continue
        eff = goodput / offered
        if reference_eff is None:
            reference_eff = min(1.0, eff)
        elif eff < efficiency * reference_eff:
            return index
        if prev is not None:
            prev_offered, prev_goodput = prev
            if prev_goodput > 0 and prev_offered > 0:
                growth = (offered - prev_offered) / prev_offered
                gain = (goodput - prev_goodput) / prev_goodput
                if growth >= min_offered_growth and gain < min_gain:
                    return index
        prev = (offered, goodput)
    return None


def _slo_verdict(
    spec: ScenarioSpec, cell_name: str, point: Dict[str, Any]
) -> Dict[str, Any]:
    """Typed pass/fail for one cell against the spec's SLO block."""
    checks: Dict[str, Any] = {}
    slo = spec.slo or {}
    if "p99_ms" in slo:
        actual = point["p99_ms"]
        checks["p99_ms"] = {
            "target": slo["p99_ms"],
            "actual": actual,
            "pass": actual is not None and actual <= slo["p99_ms"],
        }
    if "min_goodput_cmds_per_s" in slo:
        actual = point["goodput_cmds_per_s"]
        checks["min_goodput_cmds_per_s"] = {
            "target": slo["min_goodput_cmds_per_s"],
            "actual": actual,
            "pass": actual >= slo["min_goodput_cmds_per_s"],
        }
    return {
        "cell": cell_name,
        "checks": checks,
        "pass": all(c["pass"] for c in checks.values()),
    }


def build_curves(
    spec: ScenarioSpec,
    expansion: Dict[str, Any],
    outcomes: Dict[str, Dict[str, Any]],
) -> Dict[str, Any]:
    """Assemble the per-(protocol, n, f) throughput-latency curves from
    executed cells: points sorted by offered rate, knee detection, SLO
    verdicts.  This document IS ``curves.json``."""
    groups: Dict[Tuple[str, int, int], List[Dict[str, Any]]] = {}
    for cell in expansion["cells"]:
        outcome = outcomes.get(cell["name"])
        if outcome is None:
            continue
        point = {
            "cell": cell["name"],
            "offered_cmds_per_s": cell["rate_cmds_per_s"],
            "goodput_cmds_per_s": outcome["goodput_cmds_per_s"],
            "commands": outcome["commands"],
            "p50_ms": outcome["latency_ms"]["p50"],
            "p95_ms": outcome["latency_ms"]["p95"],
            "p99_ms": outcome["latency_ms"]["p99"],
            "sheds": outcome["sheds"],
            "queue_depth_hwm": outcome["queue_depth_hwm"],
            "degraded_ms": outcome["degraded_ms"],
            "failovers": outcome["failovers"],
        }
        key = (cell["protocol"], cell["n"], cell["f"])
        groups.setdefault(key, []).append(point)
    curves = []
    for (protocol, n, f), points in sorted(groups.items()):
        points.sort(
            key=lambda p: (
                p["offered_cmds_per_s"] is not None,
                p["offered_cmds_per_s"] or 0.0,
            )
        )
        knee_index = detect_knee(points)
        verdicts = [
            _slo_verdict(spec, p["cell"], p) for p in points
        ]
        curves.append(
            {
                "protocol": protocol,
                "n": n,
                "f": f,
                "points": points,
                "knee_index": knee_index,
                "knee": points[knee_index] if knee_index is not None else None,
                "slo": verdicts,
            }
        )
    return {
        "scenario": spec.name,
        "timeline": spec.timeline,
        "seed": spec.seed,
        "slo": spec.slo,
        "workload": expansion["workload"],
        "placements": expansion["placements"],
        "curves": curves,
    }


def run_scenario(
    spec: ScenarioSpec, out_dir: str, planet=None, render: bool = True
) -> Dict[str, Any]:
    """Expand, execute every cell, assemble + persist the curves.

    Writes ``expansion.json`` (canonical bytes), per-cell obs dirs, and
    ``curves.json`` under ``out_dir``; renders ``curves.png`` through
    ``plot.plots.saturation_curves`` unless ``render=False``.  Returns
    the curves document."""
    from fantoch_tpu.plot.db import save_curves

    planet = _planet(spec, planet)
    os.makedirs(out_dir, exist_ok=True)
    canonical = canonical_expansion(spec, planet)
    with open(os.path.join(out_dir, "expansion.json"), "w") as fh:
        fh.write(canonical)
        fh.write("\n")
    expansion = json.loads(canonical)
    outcomes: Dict[str, Dict[str, Any]] = {}
    for cell in expansion["cells"]:
        outcomes[cell["name"]] = run_cell(spec, cell, out_dir, planet)
    doc = build_curves(spec, expansion, outcomes)
    save_curves(doc, os.path.join(out_dir, "curves.json"))
    if render:
        from fantoch_tpu.plot import plots

        plots.saturation_curves(doc, os.path.join(out_dir, "curves.png"))
    return doc
