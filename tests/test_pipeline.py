"""The shared dispatch/drain pipeline core (run/pipeline.py), tested
host-only: a fake driver stands in for the device planes so depth
semantics, flush ordering, the ingest ring's reuse discipline, and the
busy/idle counters are covered on every jax pin (the real-driver twin
lives in tests/test_device_runner.py, which needs jax >= 0.5)."""

import numpy as np
import pytest

from fantoch_tpu.run.pipeline import (
    DEFAULT_PIPELINE_DEPTH,
    IngestRing,
    PipelineCore,
    resolve_pipeline_depth,
)


class _FakeDriver(PipelineCore):
    """dispatch() records the batch; drain() 'executes' it.  Tokens are
    (round_index, batch); results are (round_index, item) tuples — enough
    to assert ordering and lag exactly."""

    def __init__(self, flush_at=None):
        self.batch_size = 8
        self._init_pipeline()
        self._round = 0
        self.drained = []
        self.flush_at = flush_at or set()

    def dispatch(self, batch):
        tok = (self._round, list(batch))
        self._round += 1
        return tok

    def drain(self, tok):
        r, batch = tok
        self.drained.append(r)
        return [(r, item) for item in batch]

    def _pipeline_flush_needed(self, batch):
        return any(item in self.flush_at for item in batch)


def test_resolve_depth_precedence(monkeypatch):
    """One home: the Config field, else the module's default; the
    environment is not a rung."""
    from fantoch_tpu.core import Config

    monkeypatch.setenv("FANTOCH_SERVING_PIPELINE_DEPTH", "3")
    assert resolve_pipeline_depth(Config(3, 1)) == DEFAULT_PIPELINE_DEPTH == 1
    assert resolve_pipeline_depth(Config(3, 1, serving_pipeline_depth=2)) == 2
    # the field is the only argument: no explicit rung beside it
    with pytest.raises(TypeError):
        resolve_pipeline_depth(5, Config(3, 1))


def test_config_serving_pipeline_depth_validates():
    from fantoch_tpu.core import Config

    assert Config(3, 1, serving_pipeline_depth=2).serving_pipeline_depth == 2
    with pytest.raises(ValueError):
        Config(3, 1, serving_pipeline_depth=0)


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_depth_k_lag_and_order(depth):
    """serve under overlap returns results exactly ``depth`` calls late, in
    dispatch order, and flush_pipeline retires the tail oldest-first."""
    d = _FakeDriver()
    d.pipeline_depth = depth
    rounds = [[f"r{i}a", f"r{i}b"] for i in range(6)]
    outs = [d.serve([b], overlap=True) for b in rounds]
    # the first `depth` calls return nothing; call k returns round k-depth
    for k, out in enumerate(outs):
        if k < depth:
            assert out == []
        else:
            r = k - depth
            assert out == [(r, item) for item in rounds[r]]
    assert len(d._inflight) == depth and d.has_outstanding
    tail = d.flush_pipeline()
    expected = [
        (r, item) for r in range(6 - depth, 6) for item in rounds[r]
    ]
    assert tail == expected
    assert not d.has_outstanding and d._undrained == 0
    assert d.drained == sorted(d.drained)  # strict FIFO retirement
    assert d.pipelined_rounds == 5  # every dispatch after the first


def test_step_flushes_pipeline_first():
    """A synchronous step retires every in-flight round before its own,
    so mixing step and serve under overlap can never reorder results."""
    d = _FakeDriver()
    d.pipeline_depth = 2
    assert d.serve([["a"]], overlap=True) == []
    assert d.serve([["b"]], overlap=True) == []
    out = d.step(["c"])
    assert out == [(0, "a"), (1, "b"), (2, "c")]
    assert not d.has_outstanding


def test_flush_needed_retires_all_before_dispatch():
    """When a dispatch would rebase state in-flight rounds reference,
    every outstanding round drains FIRST and the new round dispatches
    into an empty pipeline (the window-rebase early-flush contract)."""
    d = _FakeDriver(flush_at={"RESET"})
    d.pipeline_depth = 3
    for i in range(3):
        assert d.serve([[f"x{i}"]], overlap=True) == []
    out = d.serve([["RESET"]], overlap=True)
    assert out == [(0, "x0"), (1, "x1"), (2, "x2")]
    assert len(d._inflight) == 1  # the RESET round went in flight
    assert d.flush_pipeline() == [(3, "RESET")]


def test_counters_sane_and_idle_frac_bounded():
    d = _FakeDriver()
    d.pipeline_depth = 2
    for i in range(5):
        d.serve([[f"v{i}", f"w{i}"]], overlap=True)
    d.flush_pipeline()
    c = d.device_counters()
    assert c["device_dispatches"] == 5
    assert c["device_dispatched_rows"] == 10
    assert c["device_batch_capacity"] == 5 * d.batch_size
    assert c["device_pipeline_depth"] == 2
    assert c["device_pipelined_rounds"] == 4
    assert 0.0 <= c["device_idle_frac"] <= 1.0
    assert c["device_busy_ms"] <= c["device_span_ms"] + 1e-6
    assert c["device_dispatch_ms"] >= 0 and c["device_drain_ms"] >= 0


def test_counters_snapshot_mid_flight():
    """device_counters must be readable with rounds still in flight (the
    periodic metrics task does) without perturbing the instrument."""
    d = _FakeDriver()
    d.pipeline_depth = 2
    d.serve([["a"]], overlap=True)
    c = d.device_counters()
    assert c["device_dispatches"] == 1
    assert 0.0 <= c["device_idle_frac"] <= 1.0
    assert d.flush_pipeline() == [(0, "a")]
    c2 = d.device_counters()
    assert c2["device_busy_ms"] <= c2["device_span_ms"] + 1e-6


def test_ingest_ring_cycles_and_resets():
    ring = IngestRing(
        3,
        (
            ("key", (4, 2), np.int32, -1),
            ("src", (4,), np.int32, 0),
        ),
    )
    assert ring.slots == 3
    key0, src0 = ring.acquire()
    key0[0, 0] = 7
    src0[1] = 9
    key1, _src1 = ring.acquire()
    assert key1 is not key0  # distinct slots back to back
    _ = ring.acquire()
    key0b, src0b = ring.acquire()  # wrapped: slot 0 again, reset
    assert key0b is key0 and src0b is src0
    assert (key0b == -1).all() and (src0b == 0).all()


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_serving_a_chain_parity_with_unbatched(depth):
    """A chain through the base PipelineCore (S grouped rounds, no
    fusion) is bit-for-bit the unbatched loop — same results in the same
    order at every depth, the chain only a grouping hint."""
    rounds = [[f"r{i}a", f"r{i}b", f"r{i}c"] for i in range(12)]
    groups = [rounds[i * 3 : (i + 1) * 3] for i in range(4)]

    plain = _FakeDriver()
    plain.pipeline_depth = depth
    expect = [r for b in rounds for r in plain.serve([b], overlap=True)]
    expect += plain.flush_pipeline()

    chained = _FakeDriver()
    chained.pipeline_depth = depth
    got = [r for g in groups for r in chained.serve(g, overlap=True)]
    got += chained.flush_pipeline()
    assert got == expect
    assert chained.dispatches == plain.dispatches == 12

    sync = _FakeDriver()
    got_sync = [r for g in groups for r in sync.serve(g)]
    assert got_sync == expect
    assert not sync.has_outstanding


def test_ingest_knob_precedence(monkeypatch):
    """The three r16 knobs have one home each: the Config field, read
    with the module's default; the environment is not a rung."""
    from fantoch_tpu.core import Config
    from fantoch_tpu.run import ingest

    monkeypatch.setenv("FANTOCH_INGEST_DEADLINE_MS", "7.5")
    monkeypatch.setenv("FANTOCH_SERVING_CHAIN_MAX", "4")

    # unset fields: the defaults (the opt-in surfaces test the field
    # itself for None and stay immediate)
    unset = Config(3, 1)
    assert unset.ingest_deadline_ms is None
    assert (
        ingest.resolve_ingest_deadline_ms(unset)
        == ingest.DEFAULT_INGEST_DEADLINE_MS == 2.0
    )
    assert (
        ingest.resolve_serving_chain_max(unset)
        == ingest.DEFAULT_SERVING_CHAIN_MAX == 8
    )

    cfg = Config(3, 1, ingest_deadline_ms=3, serving_chain_max=2)
    deadline = ingest.resolve_ingest_deadline_ms(cfg)
    assert deadline == 3.0 and isinstance(deadline, float)
    assert ingest.resolve_serving_chain_max(cfg) == 2
    # 0 is a valid deadline (batching off), not "unset"
    assert ingest.resolve_ingest_deadline_ms(
        Config(3, 1, ingest_deadline_ms=0.0)
    ) == 0.0

    # the rungs that went: no requested_* twin, no target resolver, no
    # variable names, no second copy of a range check (Config's is the
    # one, test_config_ingest_knobs_validate)
    for name in ("requested_ingest_deadline_ms", "resolve_ingest_target",
                 "ENV_INGEST_DEADLINE_MS", "ENV_INGEST_TARGET",
                 "ENV_SERVING_CHAIN_MAX"):
        assert not hasattr(ingest, name), name


def test_config_ingest_knobs_validate():
    from fantoch_tpu.core import Config

    cfg = Config(3, 1, ingest_deadline_ms=1.5, ingest_target=64,
                 serving_chain_max=4)
    assert cfg.ingest_deadline_ms == 1.5
    assert cfg.ingest_target == 64
    assert cfg.serving_chain_max == 4
    with pytest.raises(ValueError):
        Config(3, 1, ingest_deadline_ms=-0.5)
    with pytest.raises(ValueError):
        Config(3, 1, ingest_target=0)
    with pytest.raises(ValueError):
        Config(3, 1, serving_chain_max=0)


def test_batcher_release_causes():
    """The three release causes: fast (idle system, lone command), size
    (queued >= EWMA target), deadline (budget exhausted)."""
    from fantoch_tpu.run.ingest import AdaptiveIngestBatcher

    b = AdaptiveIngestBatcher(deadline_ms=2.0, max_target=1024)

    # lone closed-loop command on an idle system: immediate release
    b.note_arrivals(0.0, 1)
    release, wait = b.poll(0.0, 1, idle_system=True)
    assert release and wait is None
    b.note_release(0.0, 1)
    assert b.releases_fast == 1

    # cold EWMA: target 1, so even a busy system releases a lone command
    assert b.target() == 1
    b.note_arrivals(10.0, 1)
    release, _ = b.poll(10.0, 1)
    assert release
    b.note_release(10.0, 1)
    assert b.releases_size == 1

    # sustained 100/ms raises the target; the backlog itself goes out
    # by size
    t = 20.0
    for _ in range(50):
        t += 0.1
        b.note_arrivals(t, 10)
    assert b.target() > 1
    release, _ = b.poll(t, 500)
    assert release
    b.note_release(t, 500)
    assert b.releases_size == 2

    # a fresh below-target window holds with the remaining budget; the
    # full budget forces a deadline release
    t += 0.1
    b.note_arrivals(t, 1)
    release, wait = b.poll(t, 1)
    assert not release and 0 < wait <= 2.0
    release, wait = b.poll(t + 2.0, 1)
    assert release
    b.note_release(t + 2.0, 1)
    assert b.releases_deadline == 1

    c = b.counters()
    assert c["ingest_releases"] == 4
    assert c["ingest_arrivals"] == 2 + 500 + 1
    assert (
        c["ingest_releases_fast"] + c["ingest_releases_size"]
        + c["ingest_releases_deadline"] == c["ingest_releases"]
    )


def test_batcher_ewma_target_and_hard_reset():
    """The size target tracks expected arrivals per deadline window
    (EWMA rate x deadline, clamped), and an idle gap SNAPS the rate
    down instead of decaying it — the first command after idle must not
    inherit a stale high target."""
    from fantoch_tpu.run.ingest import AdaptiveIngestBatcher

    b = AdaptiveIngestBatcher(deadline_ms=2.0, max_target=256)
    t = 0.0
    for _ in range(200):
        t += 0.1
        b.note_arrivals(t, 10)  # 100/ms sustained
    # converged: ~100/ms * 2ms = 200 rows
    assert 150 <= b.target() <= 256
    assert b.rate_per_s() == pytest.approx(100_000.0, rel=0.15)

    # a gap past ~8 deadline windows ends the regime: the single
    # arrival after it sees target 1 at once
    b.note_arrivals(t + 1000.0, 1)
    assert b.target() == 1

    # fixed_target pins the knob regardless of the EWMA
    fixed = AdaptiveIngestBatcher(2.0, max_target=256, fixed_target=32)
    for i in range(100):
        fixed.note_arrivals(i * 0.1, 10)
    assert fixed.target() == 32

    # deadline 0 = batching off: always release, target 1
    off = AdaptiveIngestBatcher(0.0, max_target=256)
    off.note_arrivals(0.0, 5)
    assert off.target() == 1
    release, _ = off.poll(0.0, 5)
    assert release


def test_chain_autotuner_convergence():
    """Under a synthetic fixed-overhead driver (O ms host overhead per
    dispatch, C ms device time per round) the tuner doubles S while the
    per-round overhead ratio O/(S*C) exceeds grow_frac, then holds —
    and the [shrink_frac, grow_frac] hysteresis band keeps S stable."""
    from fantoch_tpu.run.ingest import ChainAutoTuner

    O, C = 1.0, 0.5  # ratio at S: (O/S)/C = 2/S
    tuner = ChainAutoTuner(chain_max=8)
    counters = [0.0, 0.0, 0.0, 0.0]  # dispatches, wall, busy, rounds

    def feed(n_dispatches):
        S = tuner.chain
        counters[0] += n_dispatches
        counters[1] += n_dispatches * O
        counters[2] += n_dispatches * S * C
        counters[3] += n_dispatches * S
        return tuner.observe(*counters)

    assert feed(8) == 1  # first observation only seeds the baseline
    seen = [feed(8) for _ in range(6)]
    # S: 1 -> 2 (ratio 2.0) -> 4 (1.0) -> 8 (0.5) -> stays (0.25 not >)
    assert seen == [2, 4, 8, 8, 8, 8]
    assert tuner.adjustments == 3

    # overhead collapses far under shrink_frac: S halves down the pow2
    # ladder (never a decrement — each chain length is a distinct
    # compiled program, so the tuner only emits pow2 values; see the
    # ChainAutoTuner docstring) — and an observation under
    # min_dispatches new dispatches is deferred, folding into the next
    # qualifying delta
    O = 0.01
    before = tuner.chain
    assert feed(3) == before
    assert feed(8) == 4
    assert feed(8) == 2

    # hysteresis: a ratio inside [shrink, grow] leaves S alone
    O = 2 * C * 0.1  # ratio 0.1 at S=2
    assert feed(8) == 2
    assert feed(8) == 2


def test_chain_autotuner_pow2_only():
    """Every S the tuner can emit is a power of two, and the ceiling is
    the pow2 FLOOR of an arbitrary chain_max — a non-pow2 ceiling would
    bake a fresh compiled chain program the moment the tuner hit it."""
    from fantoch_tpu.run.ingest import ChainAutoTuner

    tuner = ChainAutoTuner(chain_max=13)
    assert tuner.chain_max == 8
    counters = [0.0, 0.0, 0.0, 0.0]
    O, C = 4.0, 0.5

    def feed(n):
        S = tuner.chain
        counters[0] += n
        counters[1] += n * O
        counters[2] += n * S * C
        counters[3] += n * S
        return tuner.observe(*counters)

    feed(8)  # seed
    seen = set()
    for _ in range(12):
        seen.add(feed(8))
    O = 0.001  # collapse: walk back down
    for _ in range(12):
        seen.add(feed(8))
    assert seen <= {1, 2, 4, 8}
    assert tuner.chain == 1


def test_plan_ingest_releases_oracle():
    """The offline replay (OrderingPool's coalescer and the online
    loops' oracle): releases partition the arrival column, a deadline
    expiring between two arrivals releases at the deadline instant
    WITHOUT the later arrival, and the tail releases at its window's
    deadline."""
    from fantoch_tpu.run.ingest import (
        AdaptiveIngestBatcher,
        plan_ingest_releases,
    )

    # trickle: each arrival 10ms apart, deadline 2ms — the cold/reset
    # EWMA targets 1, so every lone command releases at its own arrival
    # instant (batching never engages without measured sustained load)
    b = AdaptiveIngestBatcher(2.0, max_target=64)
    arrivals = [0.0, 10.0, 20.0]
    plan = plan_ingest_releases(arrivals, b)
    assert plan == [(0.0, 0, 1), (10.0, 1, 2), (20.0, 2, 3)]
    assert b.releases == 3 and b.released_rows == 3

    # a fixed target groups a dense burst into size releases plus a
    # deadline tail
    b2 = AdaptiveIngestBatcher(2.0, max_target=64, fixed_target=4)
    dense = [i * 0.1 for i in range(10)]
    plan2 = plan_ingest_releases(dense, b2)
    starts = [s for _t, s, _e in plan2]
    ends = [e for _t, _s, e in plan2]
    assert starts == [0] + ends[:-1] and ends[-1] == 10  # partition
    assert plan2[0] == (pytest.approx(0.3), 0, 4)
    assert plan2[1] == (pytest.approx(0.7), 4, 8)
    # tail: 2 rows < target, released at the window's deadline
    assert plan2[2] == (pytest.approx(0.8 + 2.0), 8, 10)
    assert b2.releases_size == 2 and b2.releases_deadline == 1

    # empty column: empty plan
    assert plan_ingest_releases([], AdaptiveIngestBatcher(2.0, 64)) == []


def test_ingest_ring_slot_never_reused_while_in_flight():
    """The driver contract: with PipelineCore._staging (the production
    ring sizing: slots = depth + 1), the staging columns of any round
    still in flight are never handed out again — the zero-copy-alias
    safety argument for jnp.asarray staging."""

    class RingDriver(_FakeDriver):
        def __init__(self):
            super().__init__()
            self.live = {}  # round -> staging array it aliases

        def dispatch(self, batch):
            (col,) = self._staging(("col", (4,), np.int32, 0))
            col[: len(batch)] = batch
            tok = (self._round, col, list(batch))
            self._round += 1
            # no OTHER in-flight round may alias this slot
            for r, other in self.live.items():
                assert other is not col, f"slot of round {r} reused in flight"
            self.live[tok[0]] = col
            return tok

        def drain(self, tok):
            r, col, batch = tok
            # the round's staging columns are untouched at drain time
            assert list(col[: len(batch)]) == batch
            del self.live[r]
            self.drained.append(r)
            return [(r, v) for v in batch]

    for depth in (1, 2, 3):
        d = RingDriver()
        d.pipeline_depth = depth
        outs = []
        for i in range(8):
            outs.extend(d.serve([[10 * i + 1, 10 * i + 2]], overlap=True))
        outs.extend(d.flush_pipeline())
        assert [v for _r, v in outs] == [
            10 * i + j for i in range(8) for j in (1, 2)
        ]


# --- round-stage spans (observability/device.py StageRecorder) ---


class _HalvesDriver(PipelineCore):
    """A driver that implements the halves, as the device drivers do:
    ``dispatch`` / ``drain`` are PipelineCore's own, under its spans."""

    def __init__(self):
        self.batch_size = 8
        self._init_pipeline()
        self._round = 0

    def _assemble(self, batch):
        return list(batch)

    def _enqueue(self, staged):
        self._round += 1
        return (self._round, staged)

    def _execute(self, tok, fetched):
        assert fetched == tok  # device_get of host values is the identity
        r, batch = tok
        return [(r, item) for item in batch]


def test_drain_wall_is_fetch_plus_execute_and_dispatch_is_its_halves():
    """``device_drain_ms`` is the whole of a drain, so it already holds
    the blocking fetch that ``device_fetch_ms`` reports again: the
    witness of the double count in dispatch + fetch + drain sums.  The
    three keys are reads of the stage recorder."""
    d = _HalvesDriver()
    for i in range(5):
        assert d.step([i, i + 10]) == [(i + 1, i), (i + 1, i + 10)]
    c, s = d.device_counters(), d.stages.counters()
    assert s["stage_fetch_n"] == s["stage_execute_n"] == 5
    assert s["stage_assemble_n"] == s["stage_enqueue_n"] == c["device_dispatches"] == 5
    assert c["device_fetch_ms"] == s["stage_fetch_ms"] > 0
    assert c["device_drain_ms"] == pytest.approx(
        s["stage_fetch_ms"] + s["stage_execute_ms"], abs=0.002)
    assert c["device_dispatch_ms"] == pytest.approx(
        s["stage_assemble_ms"] + s["stage_enqueue_ms"], abs=0.002)
    assert c["device_drain_ms"] > c["device_fetch_ms"]


def test_a_pipelined_drains_spans_carry_the_retired_rounds_id():
    d = _HalvesDriver()
    d.pipeline_depth = 1
    assert d.serve([["a"]], overlap=True) == []          # dispatch 1 stays in flight
    assert d.serve([["b"]], overlap=True) == [(1, "a")]  # dispatch 2 retires round 1
    assert d.flush_pipeline() == [(2, "b")]
    rounds = [(name, round_id) for name, _t0, _t1, round_id, *_ in d.stages.ring]
    assert rounds == [
        ("assemble", 1), ("enqueue", 1),
        ("assemble", 2), ("enqueue", 2), ("fetch", 1), ("execute", 1),
        ("fetch", 2), ("execute", 2),
    ]
    for _name, t0, t1, _round, _thread, parent, _reads in d.stages.ring:
        assert t1 >= t0 and parent is None  # no step span above a bare driver


def test_stage_counters_are_numeric_and_monotone_and_the_ring_is_bounded():
    from fantoch_tpu.observability.device import ROUND_STAGES, StageRecorder

    rec = StageRecorder(ring=8)
    first = rec.counters()
    assert all(f"stage_{name}_ms" in first and f"stage_{name}_n" in first
               for name in ROUND_STAGES)  # every stage is in the first snapshot
    assert set(first.values()) == {0}
    for i in range(20):
        with rec.span("step", i):
            with rec.span("assemble", i) as inner:
                pass
        rec.record("handoff", 5, 9, i, "round")
    assert len(rec.ring) == 8 and inner.parent == "step" and inner.t1 >= inner.t0 > 0
    assert rec.ring[-1] == ("handoff", 5, 9, 19, rec.ring[-1][4], "round", None)
    second = rec.counters()
    assert all(isinstance(value, (int, float)) for value in second.values())
    assert all(second[key] >= first[key] for key in first)
    assert second["stage_step_n"] == second["stage_assemble_n"] == second["stage_handoff_n"] == 20
    assert second["stage_handoff_ms"] == pytest.approx(20 * 4e-6, abs=1e-3)
    assert second["stage_step_ms"] >= second["stage_assemble_ms"] > 0
    assert second["stage_step_cpu_ms"] >= 0


def test_the_ring_dumps_as_json(tmp_path):
    import json

    d = _HalvesDriver()
    d.step(["x"])
    path = tmp_path / "round_spans.json"
    d.stages.dump(str(path))
    blob = json.loads(path.read_text())
    assert blob["clock"] == "monotonic_ns"
    assert blob["columns"] == ["name", "t0_ns", "t1_ns", "round", "thread", "parent", "read_rows"]
    assert [row[0] for row in blob["spans"]] == ["assemble", "enqueue", "fetch", "execute"]
