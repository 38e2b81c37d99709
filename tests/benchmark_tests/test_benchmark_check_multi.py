"""The check over commands that write several keys: one order of commands
across keys.  It accepts a sound history, whatever failed or overlapped, and
rejects a cross-key swap, a cycle through three keys, a torn command, a
real-time inversion that only a path over two keys shows, and a shard that
answered twice, each with a witness that names the commands and the keys.
The control's mutations, old and new, are held to the same."""

import numpy as np
import pytest

from benchmark.check import MORE_FIELDS, check_history
from benchmark.control import mutations
from benchmark.generators.kv_loop import (
    GET, MEASURED, NONE_VALUE, OK, PUT, READBACK, RECORD_FIELDS, UNANSWERED, WARM,
)

NAMES = ["fork", "duplicate_ack", "inversion", "lost_write", "foreign_value",
         "cross_key_swap", "torn_command", "cross_key_inversion"]


def history(seed=0, clients=6, keys=6, commands=300, per_command=2, fail_every=0, plan=None):
    """A sequential store served one command at a time, as in
    ``test_benchmark_check.py``: command i is sent at i ms and acknowledged at
    i + 0.6 ms, and writes ``per_command`` distinct keys (``plan``: the keys
    of each command, instead of seeded draws); then every key is read back."""
    rng = np.random.default_rng(seed)
    store, rows, more, seq = {}, [], [], {}
    plan = plan or [rng.choice(np.arange(1, keys + 1), per_command, replace=False).tolist()
                    for _ in range(commands)]
    for i, own in enumerate(plan):
        client = int(rng.integers(1, clients + 1))
        seq[client] = seq.get(client, 0) + 1
        sent = i * 1e-3
        failed = bool(fail_every) and i % fail_every == fail_every - 1
        applied = not failed or i % (2 * fail_every) == fail_every - 1  # fate open
        before = [store.get(key) for key in own]
        if applied:
            store.update((key, (client, seq[client])) for key in own)
        returned = [(NONE_VALUE, NONE_VALUE) if failed or prev is None else prev for prev in before]
        rows.append(dict(
            client=client, seq=seq[client], key=own[0], op=PUT,
            phase=WARM if i < 20 else MEASURED, status=UNANSWERED if failed else OK,
            due=sent, sent=sent, acked=np.nan if failed else sent + 6e-4,
            ret_client=returned[0][0], ret_seq=returned[0][1]))
        more += [dict(more_client=client, more_seq=seq[client], more_key=key,
                      more_ret_client=ret[0], more_ret_seq=ret[1], more_answers=0 if failed else 1)
                 for key, ret in zip(own[1:], returned[1:])]
    reader, end = clients + 1, len(plan) * 1e-3 + 1.0
    for n, key in enumerate(sorted(store)):
        rows.append(dict(client=reader, seq=n + 1, key=key, op=GET, phase=READBACK, status=OK,
                         due=end, sent=end, acked=end + 1e-3,
                         ret_client=store[key][0], ret_seq=store[key][1]))
    return {**{name: np.array([row[name] for row in rows], dtype) for name, dtype in RECORD_FIELDS},
            **{name: np.array([row[name] for row in more], dtype) for name, dtype in MORE_FIELDS}}


def entry(rec, command, key):
    """Where command ``command`` (its index in the plan) keeps what ``key``
    returned: (column prefix, row)."""
    if rec["key"][command] == key:
        return "", command
    rows = np.flatnonzero((rec["more_client"] == rec["client"][command])
                          & (rec["more_seq"] == rec["seq"][command]) & (rec["more_key"] == key))
    return "more_", int(rows[0])


def returned(rec, command, key):
    prefix, row = entry(rec, command, key)
    return int(rec[prefix + "ret_client"][row]), int(rec[prefix + "ret_seq"][row])


def set_returned(rec, command, key, value):
    prefix, row = entry(rec, command, key)
    rec[prefix + "ret_client"][row], rec[prefix + "ret_seq"][row] = value


def rifl(rec, command):
    return int(rec["client"][command]), int(rec["seq"][command])


def named(witness):
    """The rifls a witness names."""
    return {op.get("write") or op.get("get") for op in witness["ops"] if isinstance(op, dict)}


def only(verdict, check):
    found = [w for w in verdict["witnesses"] if w["check"] == check]
    assert found, [w["check"] for w in verdict["witnesses"]]
    return found[0]


def test_accepts_a_sound_history_of_commands_over_two_and_three_keys():
    for per_command in (2, 3):
        verdict = check_history(history(per_command=per_command))
        assert verdict["correct"] and not verdict["witnesses"]
        stats = verdict["stats"]
        assert stats["requests"] == 306 and stats["multi_key_commands"] == 300
        assert stats["acked_writes"] == 300 * per_command and stats["gets_checked"] == 6
        assert stats["cross_key_edges"] > 300 and stats["check_seconds"] > 0


def test_a_history_of_single_key_commands_has_no_graph_to_check():
    rec = history(per_command=1)
    assert not len(rec["more_key"])
    stats = check_history(rec)["stats"]
    assert stats["multi_key_commands"] == 0 and stats["cross_key_edges"] == 0
    bare = {name: col for name, col in rec.items() if not name.startswith("more_")}
    before = check_history(bare)["stats"]
    assert {k: v for k, v in stats.items() if k != "check_seconds"} == \
        {k: v for k, v in before.items() if k != "check_seconds"}


@pytest.mark.parametrize("kwargs", [dict(fail_every=7), dict(fail_every=3), dict(fail_every=2)])
def test_failed_commands_over_several_keys_keep_their_fate_open(kwargs):
    verdict = check_history(history(seed=3, **kwargs))
    assert verdict["correct"], verdict["witnesses"]
    assert verdict["stats"]["open_writes"] > 0 and verdict["stats"]["keys_with_open_writes"] > 0


def test_rejects_two_commands_in_one_order_on_one_key_and_the_other_on_the_second():
    # commands 0..3 all write keys 1 and 2; commands 1 and 2 overlap, and on key 2
    # they change places
    rec = history(plan=[[1, 2]] * 4)
    rec["sent"][2] = rec["due"][2] = rec["sent"][1] - 1e-4
    set_returned(rec, 2, 2, rifl(rec, 0))
    set_returned(rec, 1, 2, rifl(rec, 2))
    set_returned(rec, 3, 2, rifl(rec, 1))
    verdict = check_history(rec)
    assert not verdict["correct"]
    witness = only(verdict, "cross_key_cycle")
    assert named(witness) == {"%d:%d" % rifl(rec, 1), "%d:%d" % rifl(rec, 2)}
    assert sorted(witness["keys"]) == [1, 2] and witness["key"] is None
    assert all(set(op["keys"]) == {"1", "2"} for op in witness["ops"])
    # every key's own chain is a sound chain: nothing but the graph shows it
    assert {w["check"] for w in verdict["witnesses"]} == {"cross_key_cycle"}


def test_rejects_a_cycle_of_three_commands_through_three_keys():
    # a writes (1, 2), b writes (2, 3), c writes (3, 1), each key written by two of
    # them: a before b on 2, b before c on 3, and then c before a on 1
    rec = history(plan=[[1, 2], [2, 3], [3, 1]])
    set_returned(rec, 2, 1, (NONE_VALUE, NONE_VALUE))
    set_returned(rec, 0, 1, rifl(rec, 2))
    read = int(np.flatnonzero((rec["op"] == GET) & (rec["key"] == 1))[0])
    rec["ret_client"][read], rec["ret_seq"][read] = rifl(rec, 0)
    verdict = check_history(rec)
    witness = only(verdict, "cross_key_cycle")
    assert named(witness) == {"%d:%d" % rifl(rec, c) for c in range(3)}
    assert sorted(witness["keys"]) == [1, 2, 3]


def test_rejects_a_torn_command():
    # command 1's write on key 2 is gone: command 2 returns there what command 1 did
    rec = history(plan=[[1, 2]] * 3)
    set_returned(rec, 2, 2, returned(rec, 1, 2))
    verdict = check_history(rec)
    witness = only(verdict, "fork")
    assert witness["key"] == 2 and "%d:%d" % rifl(rec, 1) in named(witness)
    assert {w["key"] for w in verdict["witnesses"]} == {2}  # key 1 is intact
    # or it was never answered on that key at all
    rec = history(plan=[[1, 2]] * 3)
    rec["more_answers"][1] = 0
    witness = only(check_history(rec), "partial_answer")
    assert witness["key"] == 2 and named(witness) == {"%d:%d" % rifl(rec, 1)}


def test_rejects_a_shard_that_answered_twice_and_a_row_of_no_command():
    rec = history(plan=[[1, 2]] * 3)
    rec["more_answers"][2] = 2
    verdict = check_history(rec)
    assert not verdict["correct"] and only(verdict, "partial_answer")["key"] == 2
    rec = history(plan=[[1, 2]] * 3)
    rec["more_seq"][0] = 99
    assert only(check_history(rec), "partial_answer")["key"] is None


def test_rejects_an_inversion_that_only_a_path_over_two_keys_shows():
    # x writes key 1; c writes keys 1 and 2, sent before x and applied after it;
    # d writes key 2 after c, and is stamped as acknowledged before x was sent
    rec = history(plan=[[1, 3], [1, 2], [2, 4]])
    x, c, d = 0, 1, 2
    rec["sent"][c] = rec["due"][c] = rec["sent"][x] - 5e-3
    rec["acked"][d] = rec["sent"][x] - 1e-3
    rec["sent"][d] = rec["due"][d] = rec["sent"][x] - 2e-3
    verdict = check_history(rec)
    assert not verdict["correct"]
    assert [w["check"] for w in verdict["witnesses"]] == ["real_time"]
    witness = verdict["witnesses"][0]
    assert witness["key"] is None and witness["keys"] == [1, 2]
    assert named(witness) == {"%d:%d" % rifl(rec, x), "%d:%d" % rifl(rec, d)}


def test_overlapping_commands_over_several_keys_are_not_an_inversion():
    rec = history(plan=[[1, 3], [1, 2], [2, 4]])
    x, c, d = 0, 1, 2
    rec["sent"][c] = rec["due"][c] = rec["sent"][x] - 5e-3   # c overlaps x
    rec["sent"][d] = rec["due"][d] = rec["sent"][x] - 2e-3   # d sent early, answered in order
    assert check_history(rec)["correct"]


def test_an_inversion_inside_one_key_is_named_once():
    rec = history(plan=[[1, 2]] * 8)
    rec["acked"][6] = rec["sent"][2] - 1e-4
    rec["sent"][6] = rec["acked"][6] - 1e-4
    verdict = check_history(rec)
    assert not verdict["correct"]
    assert all(w["check"] == "real_time" and w["key"] in (1, 2) for w in verdict["witnesses"])


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("name", NAMES)
def test_every_mutation_of_the_control_is_rejected_on_a_history_over_several_keys(seed, name):
    rec = history(seed=seed, commands=400)
    assert check_history(rec)["correct"]
    found = {mutation: (records, strays) for mutation, records, strays in mutations(rec, [], seed)}
    assert list(found) == NAMES
    verdict = check_history(*found[name])
    assert not verdict["correct"] and verdict["witnesses"]
    assert all(np.array_equal(col, history(seed=seed, commands=400)[key]) for key, col in rec.items())


def test_the_new_mutations_break_what_only_the_order_across_keys_holds():
    rec = history(seed=5, commands=400)
    found = {mutation: records for mutation, records, _ in mutations(rec, [], 5)}
    swapped = check_history(found["cross_key_swap"])
    assert "cross_key_cycle" in {w["check"] for w in swapped["witnesses"]}
    torn = check_history(found["torn_command"])
    assert {w["check"] for w in torn["witnesses"]} <= {"fork", "stale_read"}
    inverted = check_history(found["cross_key_inversion"])
    assert "real_time" in {w["check"] for w in inverted["witnesses"]}
