"""The check decides `correct` from the answers alone: it accepts a correct
recorded history, whatever failed or ran late, and rejects a fork, a
duplicate acknowledgement, a real-time inversion and a lost acknowledged
write.  The control's mutations (benchmark/control.py) are held to the same
at a size a test run can hold."""

import numpy as np
import pytest

from benchmark.check import check_history
from benchmark.control import mutations
from benchmark.generators.kv_loop import (
    BAD_VALUE, GET, MEASURED, NONE_VALUE, OK, OVERLOADED, PUT, READBACK,
    RECORD_FIELDS, REJECTED, UNANSWERED, WARM,
)


def history(seed=0, clients=6, keys=5, writes=300, fail_every=0, late_s=0.0):
    """A sequential store served one operation at a time: operation i is
    sent at i ms, applied at i + 0.3 ms and acknowledged at i + 0.6 ms (so
    consecutive operations never overlap), then every key is read back."""
    rng = np.random.default_rng(seed)
    store, rows, seq = {}, [], {}
    for i in range(writes):
        client = int(rng.integers(1, clients + 1))
        seq[client] = seq.get(client, 0) + 1
        key = int(rng.integers(1, keys + 1))
        sent = i * 1e-3
        failed = fail_every and i % fail_every == fail_every - 1
        applied = not failed or i % (2 * fail_every) == fail_every - 1  # fate open
        prev = store.get(key)
        if applied:
            store[key] = (client, seq[client])
        rows.append(dict(
            client=client, seq=seq[client], key=key, op=PUT,
            phase=WARM if i < 20 else MEASURED,
            status=[UNANSWERED, OVERLOADED, REJECTED][i % 3] if failed else OK,
            due=sent - late_s, sent=sent, acked=np.nan if failed else sent + 6e-4,
            ret_client=NONE_VALUE if failed or prev is None else prev[0],
            ret_seq=NONE_VALUE if failed or prev is None else prev[1],
        ))
    reader, end = clients + 1, writes * 1e-3 + 1.0
    for n, key in enumerate(sorted(store)):
        value = store[key]
        rows.append(dict(client=reader, seq=n + 1, key=key, op=GET, phase=READBACK, status=OK,
                         due=end, sent=end, acked=end + 1e-3,
                         ret_client=value[0], ret_seq=value[1]))
    return {name: np.array([row[name] for row in rows], dtype) for name, dtype in RECORD_FIELDS}


def first_check(verdict):
    return verdict["witnesses"][0]["check"]


def test_accepts_a_correct_history():
    verdict = check_history(history())
    assert verdict["correct"] and not verdict["witnesses"]
    assert verdict["stats"]["acked_writes"] == 300 and verdict["stats"]["gets_checked"] == 5


@pytest.mark.parametrize("kwargs", [
    dict(fail_every=7),               # unanswered, refused and rejected writes, fate open
    dict(late_s=5.0),                 # every request sent five seconds late
    dict(fail_every=3, late_s=1.0),   # a third failed, all late
    dict(fail_every=2),               # half of all writes failed
])
def test_failed_late_and_unacknowledged_requests_do_not_make_a_run_incorrect(kwargs):
    verdict = check_history(history(seed=3, **kwargs))
    assert verdict["correct"], verdict["witnesses"]
    if kwargs.get("fail_every"):
        assert verdict["stats"]["open_writes"] > 0
        assert verdict["stats"]["keys_with_open_writes"] > 0


def test_rejects_a_fork():
    rec = history()
    rows = np.flatnonzero((rec["key"] == 2) & (rec["op"] == PUT))
    rec["ret_client"][rows[5]], rec["ret_seq"][rows[5]] = rec["ret_client"][rows[3]], rec["ret_seq"][rows[3]]
    verdict = check_history(rec)
    assert not verdict["correct"] and first_check(verdict) == "fork"
    assert verdict["witnesses"][0]["key"] == 2 and len(verdict["witnesses"][0]["ops"]) == 3


def test_rejects_a_duplicate_acknowledgement_and_one_never_sent():
    rec = history()
    again = [[rec["client"][10], rec["seq"][10], 0.5]]
    assert first_check(check_history(rec, again)) == "ack_unmatched"
    assert not check_history(rec, [[99, 1, 0.5]])["correct"]


def test_rejects_a_real_time_inversion():
    rec = history()
    rows = np.flatnonzero((rec["key"] == 1) & (rec["op"] == PUT))
    rec["acked"][rows[6]] = rec["sent"][rows[2]] - 1e-4   # sixth in the chain, acknowledged
    rec["sent"][rows[6]] = rec["acked"][rows[6]] - 1e-4   # before the second was sent
    verdict = check_history(rec)
    assert not verdict["correct"] and first_check(verdict) == "real_time"


def test_overlapping_operations_are_not_an_inversion():
    rec = history()
    rows = np.flatnonzero((rec["key"] == 1) & (rec["op"] == PUT))
    rec["sent"][rows[6]] = rec["sent"][rows[5]] - 1e-4    # sent early, answered in chain order
    assert check_history(rec)["correct"]


def test_rejects_a_lost_acknowledged_write():
    rec = history()
    read = np.flatnonzero((rec["op"] == GET) & (rec["key"] == 3))[0]
    last = np.flatnonzero((rec["op"] == PUT) & (rec["key"] == 3))[-1]
    rec["ret_client"][read], rec["ret_seq"][read] = rec["ret_client"][last], rec["ret_seq"][last]
    verdict = check_history(rec)
    assert not verdict["correct"] and first_check(verdict) == "stale_read"


def test_a_failed_write_may_be_read_back_but_nothing_older_than_the_last_acknowledged():
    rec = history(fail_every=5)
    key = 4
    writes = np.flatnonzero((rec["op"] == PUT) & (rec["key"] == key))
    read = np.flatnonzero((rec["op"] == GET) & (rec["key"] == key))[0]
    acked = [w for w in writes if rec["status"][w] == OK]
    failed_after = rec["client"][writes[-1]], rec["seq"][writes[-1]]
    rec["status"][writes[-1]], rec["acked"][writes[-1]] = UNANSWERED, np.nan
    rec["ret_client"][read], rec["ret_seq"][read] = failed_after   # applied after all: fine
    assert check_history(rec)["correct"]
    older = acked[-3]
    rec["ret_client"][read], rec["ret_seq"][read] = rec["client"][older], rec["seq"][older]
    assert not check_history(rec)["correct"]


def test_rejects_a_value_nobody_wrote_and_a_value_from_another_key():
    rec = history()
    rec["ret_client"][50] = rec["ret_seq"][50] = BAD_VALUE
    assert first_check(check_history(rec)) == "unknown_value"
    rec = history()
    other = np.flatnonzero((rec["op"] == PUT) & (rec["key"] != rec["key"][50]))[0]
    rec["ret_client"][50], rec["ret_seq"][50] = rec["client"][other], rec["seq"][other]
    assert first_check(check_history(rec)) == "unknown_value"


def test_rejects_writes_that_return_each_others_values():
    rec = history()
    a, b = np.flatnonzero((rec["key"] == 5) & (rec["op"] == PUT))[[4, 5]]
    rec["ret_client"][a], rec["ret_seq"][a] = rec["client"][b], rec["seq"][b]
    assert not check_history(rec)["correct"]


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("name", ["fork", "duplicate_ack", "inversion", "lost_write", "foreign_value"])
def test_every_mutation_of_the_control_is_rejected(seed, name):
    rec = history(seed=seed, writes=400)
    found = {mutation: (records, strays) for mutation, records, strays in mutations(rec, [], seed)}
    assert set(found) == {"fork", "duplicate_ack", "inversion", "lost_write", "foreign_value"}
    verdict = check_history(*found[name])
    assert not verdict["correct"] and verdict["witnesses"]
