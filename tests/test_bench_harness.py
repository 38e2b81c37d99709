"""Unit tests for bench.py's measurement harness.

The median-of-rounds slope fit exists because one jitter-swamped
two-point fit once published a 0.129 ms primary where three same-day
runs of the identical build said 2.3-3.0 ms.  These tests pin it without
touching a device: the clock is scripted via monkeypatched
``time.perf_counter``.
"""

import bench


def _scripted_clock(monkeypatch, durations_ms):
    """perf_counter returns cumulative times so consecutive (t0, t1)
    pairs measure exactly the scripted durations, in order."""
    ticks = [0.0]
    for d in durations_ms:
        ticks.append(ticks[-1])  # t0 of the next measurement
        ticks.append(ticks[-2] + d / 1000.0)  # t1 = t0 + duration
    it = iter(ticks[1:])
    monkeypatch.setattr(bench.time, "perf_counter", lambda: next(it))


def test_slope_timed_median_of_rounds(monkeypatch):
    # rounds=3, iters=1: measurement order is lo,hi, lo,hi, lo,hi after
    # two untimed warm calls.  One wild hi outlier must not drag the
    # slope: per-round slopes are (2.0, 42.0, 2.0) ms/step -> median 2.0.
    durations = [100.0, 108.0, 100.0, 268.0, 100.0, 108.0]
    _scripted_clock(monkeypatch, durations)
    slope, lo, hi = bench.slope_timed(lambda k: 0.0, 1, 5, iters=1, rounds=3)
    assert slope is not None
    assert abs(slope - 2.0) < 1e-9
    assert abs(lo - 100.0) < 1e-9
    assert abs(hi - 108.0) < 1e-9


def test_slope_timed_noise_negative_returns_none(monkeypatch):
    # hi consistently BELOW lo (pure jitter): the fit must refuse to
    # fabricate a near-zero latency and signal failure instead
    durations = [100.0, 99.0, 100.0, 98.0, 100.0, 99.5]
    _scripted_clock(monkeypatch, durations)
    slope, lo, hi = bench.slope_timed(lambda k: 0.0, 1, 5, iters=1, rounds=3)
    assert slope is None
    assert lo > hi


def test_row_failure_is_recorded_and_the_run_goes_on(capsys):
    """A secondary row that raises costs neither the rows after it nor
    the exit code's honesty: ``<name>_error`` lands in the record (full
    mode exits non-zero on any such key)."""
    record = {}

    def boom():
        raise RuntimeError("kernel refused")

    bench._row(record, "table", boom)
    bench._row(record, "pred", lambda: {"pred_plane_dispatches": 3})
    assert "kernel refused" in record["table_error"]
    assert record["pred_plane_dispatches"] == 3
    assert "table bench failed" in capsys.readouterr().err
