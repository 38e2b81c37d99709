"""The generator's inputs are a function of the seed: schedule, keys, values."""

import numpy as np
import pytest

from benchmark.generators import kv_loop

MIX = {"clients": 8, "rate_per_s": 400.0,
       "key_gen": {"kind": "zipf", "coefficient": 1.0, "keys_per_shard": 1000}}
BIG_SEED = 2**31 + 12345  # the driver's seeds do not fit 32 signed bits


def test_open_schedule_is_a_function_of_the_seed_and_the_client():
    own = kv_loop.own_clients(8, 0, 2)
    assert list(own) == [1, 3, 5, 7]
    a = kv_loop.open_schedule(BIG_SEED, 13, MIX, own, 5.0)
    b = kv_loop.open_schedule(BIG_SEED, 13, MIX, own, 5.0)
    c = kv_loop.open_schedule(BIG_SEED + 1, 13, MIX, own, 5.0)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0][:50], c[0][:50])
    # one client's arrivals do not depend on which process carries it
    alone = kv_loop.open_schedule(BIG_SEED, 13, MIX, np.array([3]), 5.0)
    assert np.array_equal(alone[0], a[0][a[1] == 3])
    assert np.all(np.diff(a[0]) >= 0) and a[0][-1] < 5.0
    assert 0.8 * 1000 < len(a[0]) < 1.2 * 1000  # 4 of 8 clients x 400/s x 5 s


def test_a_burst_keeps_the_mean_rate_and_moves_arrivals_into_the_bursts():
    mix = {**MIX, "burst": {"period_s": 1.0, "duty": 0.25, "factor": 3.0}}
    own = kv_loop.own_clients(8, 0, 1)
    times, _ = kv_loop.open_schedule(7, 13, mix, own, 20.0)
    assert 0.9 * 8000 < len(times) < 1.1 * 8000
    in_burst = np.count_nonzero((times % 1.0) < 0.25) / len(times)
    assert 0.70 < in_burst < 0.80  # 0.25 x 3 = three quarters of all arrivals


def test_values_name_their_write_and_anything_else_is_refused():
    pads = kv_loop.client_pads(BIG_SEED, 8, 100)
    assert pads == kv_loop.client_pads(BIG_SEED, 8, 100) != kv_loop.client_pads(1, 8, 100)
    value = kv_loop.value_of(pads, 100, 5, 123)
    assert len(value) == 100 and value.startswith("5:123:")
    assert kv_loop.parse_value(pads, 100, value) == (5, 123)
    assert kv_loop.parse_value(pads, 100, None) == (kv_loop.NONE_VALUE,) * 2
    for foreign in ("5:123:" + "x" * 94, "hello", "9:1:", value[:-1], 17):
        assert kv_loop.parse_value(pads, 100, foreign) == (kv_loop.BAD_VALUE,) * 2
    values = {kv_loop.value_of(pads, 100, c, s) for c in range(1, 9) for s in range(1, 200)}
    assert len(values) == 8 * 199


@pytest.mark.parametrize("key_gen,low,high", [
    ({"kind": "zipf", "coefficient": 1.0, "keys_per_shard": 1000}, 1, 1000),
    ({"kind": "conflict_rate", "rate": 50}, -1, 0),
])
def test_key_streams_are_seeded_and_in_range(key_gen, low, high):
    a = kv_loop.key_stream(BIG_SEED, key_gen, 0, size=20000)
    assert np.array_equal(a, kv_loop.key_stream(BIG_SEED, key_gen, 0, size=20000))
    assert not np.array_equal(a, kv_loop.key_stream(BIG_SEED, key_gen, 1, size=20000))
    assert a.min() >= low and a.max() <= high
    if key_gen["kind"] == "zipf":  # rank 1 draws 1 / H(1000) = 13.4% of the requests
        assert 0.11 < np.mean(a == 1) < 0.16
    else:
        assert 0.45 < np.mean(a == 0) < 0.55
