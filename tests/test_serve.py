"""The one entry a driver runs rounds through (`PipelineCore.serve`, PR 44),
held to the synchronous `step` on every driver of the served path: the four
drivers x `overlap` x a chain of 1, 2 and 4 rounds.  (Its own file, so that
`--dist loadfile` does not lengthen `tests/test_device_runner.py`'s worker.)"""

import pytest

from tests.test_device_runner import DRAIN_DRIVERS, _flat, _puts


@pytest.mark.parametrize("length", [1, 2, 4])
@pytest.mark.parametrize("overlap", [False, True], ids=["drained", "overlap"])
@pytest.mark.parametrize("protocol", DRAIN_DRIVERS)
def test_serve_is_that_many_steps_in_order(protocol, overlap, length):
    """The one entry, every driver: ``serve`` of a chain gives, in order,
    what that many ``step``s give a twin driver, each call its own rounds'
    where it drains at once and the tail with the flush under overlap; a
    chain is one dispatch where the driver fuses it (Newt), a dispatch a
    round elsewhere, and counts its rounds either way."""
    cls, _walk, n, extra = DRAIN_DRIVERS[protocol]
    kw = {"batch_size": 8, "key_buckets": 64, "monitor_execution_order": True, **extra}
    served, stepped = cls(n, **kw), cls(n, **kw)
    got, want, seq = [], [], 0
    for fill in (8, 5, 8):
        chain = []
        for _round in range(length):
            chain.append(_puts(range(seq + 1, seq + 1 + fill)))
            seq += fill
        call = served.serve([mine for mine, _ in chain], overlap=overlap)
        steps = [r for _, theirs in chain for r in stepped.step(theirs)]
        if not overlap:
            assert _flat(call) == _flat(steps) and not served.has_outstanding
        got += call
        want += steps
    assert served.has_outstanding == overlap
    got += served.flush_pipeline()
    assert _flat(got) == _flat(want) and len(got) == seq
    assert served.executed == stepped.executed == seq and served.in_flight == 0
    assert served.rounds == stepped.rounds == 3 * length
    fused = length if served.fuses_chains else 1
    assert served.dispatches * fused == stepped.dispatches == 3 * length
    assert served.device_counters()["serving_chain_len"] == fused
    for key in stepped.store.monitor.keys():
        assert served.store.monitor.get_order(key) == stepped.store.monitor.get_order(key)
