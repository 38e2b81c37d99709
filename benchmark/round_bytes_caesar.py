"""The bytes one Caesar round must move through a device's memory, computed
from the deployment's shape alone: the numerator of that round's share of the
memory roofline (``benchmark/readers/caesar_round_hbm_share.py``).

"Must" is the algorithm's, not the program's, as in ``benchmark/round_bytes.py``:
the clock table is touched where a command of the round has a key, and nowhere
else.  Who numbers a command, in which order a replica meets the round, who
waits for whom and who is retried change what is computed from those entries,
not which entries are touched, so the count is the same with one coordinator
and with one at every site, and does not change when the program does.  What
the program moves beyond it (the sorts' passes, the views of every replica row,
the recursion's passes over the working rows, the gate's fill of a table as long
as the key space) is the gap the share shows.
"""

from __future__ import annotations

INT32 = 4


def caesar_round_min_bytes(rows_on_device: int, batch: int, pending: int,
                           key_width: int) -> int:
    """One Caesar round over ``W = pending + batch`` working rows of
    ``key_width`` key slots, on a device that holds ``rows_on_device`` replica
    rows (``parallel/mesh_step.py`` ``caesar_protocol_step``).  Read and
    written once each, int32 unless said:

    * the batch's columns in: keys ``[batch, key_width]``, source, sequence;
    * the pending buffer, in and out: keys, source, sequence, clock a slot;
    * ``key_clock``: one entry a (row held, working row, key slot), read for
      the proposal and the answers and written with what the replica occupied
      and learnt;
    * the round's outputs over ``W``: order, clock, source, sequence (int32)
      and the executed, committed and fast-path flags (a byte each).
    """
    work = pending + batch
    columns_in = batch * (key_width + 2) * INT32
    pending_in_out = 2 * pending * (key_width + 3) * INT32
    key_clock = 2 * rows_on_device * work * key_width * INT32
    outputs = work * (4 * INT32 + 3)
    return columns_in + pending_in_out + key_clock + outputs


def round_min_bytes(config: dict, replica_axis: int) -> int | None:
    """The bytes of one round on a device of a deployment whose configuration
    file is ``config``, on a mesh whose replica axis is ``replica_axis`` (it
    divides ``n``: ``caesar_protocol_step`` deals the rows evenly or not at
    all); nothing for a protocol whose round is not Caesar's."""
    deployment = config["deployment"]
    if deployment["protocol"] != "caesar":
        return None
    words = config["server_flags"]
    flags = {word: words[at + 1] for at, word in enumerate(words) if word.startswith("-")}
    return caesar_round_min_bytes(
        rows_on_device=deployment["n"] // replica_axis,
        batch=int(flags["--device-batch"]), pending=int(flags["--device-pending"]),
        key_width=int(flags.get("--device-key-width", 1)))
