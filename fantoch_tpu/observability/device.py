"""Device-plane counters: per-dispatch tallies and XLA recompile events.

The device planes (the resident votes-table plane, the serving drivers,
the batched graph resolver) do their work in fused dispatches, so
per-item latency attribution stops at the batch boundary — what remains
observable is *per-dispatch*: how many dispatches, how full each batch
was, how much kernel wall time, and whether XLA recompiled mid-run (the
classic silent latency cliff).  These counters ride two channels:

- folded into the periodic metrics snapshot
  (:class:`fantoch_tpu.run.observe.ProcessMetrics.device`);
- emitted as tracer counter events so a Perfetto timeline shows them
  next to the spans of the batches they carried.

Recompiles are counted by subscribing to ``jax.monitoring`` duration
events; the subscription is process-global and idempotent.  With the
persistent compilation cache on (core/compile_cache.py), the raw
``.../backend_compile_duration`` event is ambiguous — it wraps
``compile_or_get_cached``, so it fires for disk retrievals too.  The
listener therefore PAIRS each duration event with the cache hit/miss
event that jax emits immediately before it: a duration event preceded
by a cache hit is a retrieval (counted in :func:`cache_hit_count`, its
wall in :func:`compile_ms` — retrieval stalls serving just like a
compile, only shorter), everything else is a TRUE compile.  That makes
``jax_recompiles == 0`` the proof a warm-cache sweep never paid XLA.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

_recompiles = 0
_compile_ms = 0.0
_cache_hits = 0
_cache_misses = 0
_pending_hits = 0
_subscribed = False


def subscribe_recompiles() -> bool:
    """Start counting XLA backend compiles and persistent-cache traffic
    (idempotent; returns whether the jax.monitoring hooks installed).
    Safe to call before any jax work — the listeners cost nothing until
    a compile happens."""
    global _subscribed
    if _subscribed:
        return True
    from jax import monitoring

    # jax calls a listener as callback(event, [duration,] **kwargs); the
    # keywords (fun_name=..., ...) are not used here
    def _on_event(key: str, **_kwargs) -> None:
        global _cache_hits, _cache_misses, _pending_hits
        # the persistent-cache outcome events fire BEFORE the duration
        # event of the compile-or-retrieve they describe (verified on the
        # pinned jax); a pending hit reclassifies that duration event as
        # a retrieval
        if key.endswith("compilation_cache/cache_hits"):
            _cache_hits += 1
            _pending_hits += 1
        elif key.endswith("compilation_cache/cache_misses"):
            _cache_misses += 1

    def _on_duration(key: str, secs: float, **_kwargs) -> None:
        global _recompiles, _compile_ms, _pending_hits
        if key.endswith("backend_compile_duration"):
            if _pending_hits > 0:
                _pending_hits -= 1
            else:
                _recompiles += 1
            # cumulative compile WALL, not just the count: one ~50s cold
            # compile starves heartbeats/serving for its whole duration
            # (PR 14's resolve_graph_plane_step programs) — a count of 1
            # hides that; the milliseconds name it.  Retrieval wall is
            # included: a warm run's compile_ms is the disk-load cost.
            _compile_ms += secs * 1000.0

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _subscribed = True
    return True


def recompile_count() -> int:
    """TRUE XLA backend compiles observed since
    :func:`subscribe_recompiles` (0 when never subscribed); persistent-
    cache retrievals are excluded — see the module docstring."""
    return _recompiles


def compile_ms() -> float:
    """Cumulative XLA backend compile-or-retrieve wall milliseconds since
    :func:`subscribe_recompiles` — host-process-global like
    :func:`recompile_count` (co-hosted runtimes must not sum it)."""
    return round(_compile_ms, 1)


def cache_hit_count() -> int:
    """Persistent-compilation-cache hits (disk retrievals instead of XLA
    compiles) since :func:`subscribe_recompiles`."""
    return _cache_hits


def cache_miss_count() -> int:
    """Persistent-compilation-cache misses (programs that went to XLA)
    since :func:`subscribe_recompiles`."""
    return _cache_misses


# fold semantics per counter kind: most keys are monotone tallies and
# SUM across executors; gauges would be nonsense summed — ratios are
# dropped (derive_idle_frac recomputes from the folded walls) and
# configuration gauges fold by max
_RATIO_KEYS = frozenset({"device_idle_frac"})
_GAUGE_MAX_KEYS = frozenset(
    {
        "device_pipeline_depth",
        "pred_plane_slot_capacity",
        "graph_plane_slot_capacity",
        # plane health gauge (0 healthy / 1 rebuilding / 2 suspect /
        # 3 failed — ordered by numeric severity, so the max IS the
        # worst health across co-hosted executors)
        "table_plane_health",
        "pred_plane_health",
        "graph_plane_health",
    }
)


def merge_counters(
    into: Dict[str, float], add: Optional[Dict[str, float]]
) -> Dict[str, float]:
    """Accumulate one executor's counter dict into a process-level one
    (used by the metrics snapshot fold): tallies sum, ratio keys are
    skipped (:func:`derive_idle_frac` recomputes them from the folded
    busy/span walls), configuration gauges (pipeline depth) fold by
    max."""
    if add:
        for name, value in add.items():
            if name in _RATIO_KEYS:
                continue
            if name in _GAUGE_MAX_KEYS:
                into[name] = max(into.get(name, 0), value)
            else:
                into[name] = into.get(name, 0) + value
    return into


def derive_idle_frac(counters: Dict[str, float]) -> Dict[str, float]:
    """Recompute ``device_idle_frac`` from (possibly folded)
    ``device_busy_ms`` / ``device_span_ms`` wall totals: the fraction of
    the serving span the device sat idle waiting on host assembly/emit —
    the number the pipelined serving loop (run/pipeline.py) exists to
    drive toward 0.  Spans of co-hosted executors overlap in wall time,
    so after a fold this is an approximation (busy and span inflate
    together); per-driver counters are exact."""
    span = counters.get("device_span_ms", 0.0)
    if span and span > 0:
        busy = counters.get("device_busy_ms", 0.0)
        counters["device_idle_frac"] = round(
            max(0.0, 1.0 - busy / span), 4
        )
    return counters
