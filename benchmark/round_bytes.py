"""The bytes one consensus round must move through a device's memory,
computed from the deployment's shape: the numerator of a round's share of
the memory roofline (``benchmark/readers/device_round_hbm_share.py``).

"Must" is the algorithm's, not the program's: the tables are touched where a
command of the round has a key, and nowhere else.  What the program moves
beyond that (today the whole ``[rows, buckets]`` vote table is gathered and
sorted every round for the stability order statistic) is the gap the share
shows.
"""

from __future__ import annotations

INT32 = 4


def newt_round_min_bytes(rows_on_device: int, n: int, batch: int, pending: int,
                         key_width: int) -> int:
    """One Newt (Tempo) round over ``W = pending + batch`` working rows of
    ``key_width`` key slots, on a device that holds ``rows_on_device``
    replica rows of shards of ``n`` members (``parallel/mesh_step.py``
    ``newt_protocol_step``).  Read and written once each, int32 unless said:

    * the batch's columns in: keys ``[batch, key_width]``, source, sequence;
    * the pending buffer, in and out: keys, source, sequence, clock a slot;
    * ``key_clock``: one entry a (row held, working row, key slot), read for
      the proposal and written with the committed clock;
    * ``vote_frontier``: the same entries, read and written by the votes;
    * stability: for every key slot the ``n`` frontiers of its shard, read
      (those of rows held elsewhere arrive over the interconnect and are
      read from memory all the same);
    * the round's outputs over ``W``: order, clock, source, sequence (int32)
      and the executed, committed and fast-path flags (a byte each).
    """
    work = pending + batch
    slots = work * key_width
    columns_in = batch * (key_width + 2) * INT32
    pending_in_out = 2 * pending * (key_width + 3) * INT32
    key_clock = 2 * rows_on_device * slots * INT32
    vote_frontier = 2 * rows_on_device * slots * INT32
    stability = n * slots * INT32
    outputs = work * (4 * INT32 + 3)
    return columns_in + pending_in_out + key_clock + vote_frontier + stability + outputs


def round_min_bytes(config: dict, replica_axis: int) -> int | None:
    """The bytes of one round on the fullest device of a deployment whose
    configuration file is ``config``, on a mesh whose replica axis is
    ``replica_axis`` (the rows are dealt evenly over it, so every device is
    the fullest); nothing for a protocol whose round has no function here."""
    deployment = config["deployment"]
    if deployment["protocol"] != "newt":
        return None
    words = config["server_flags"]
    flags = {word: words[at + 1] for at, word in enumerate(words) if word.startswith("-")}
    rows = deployment["n"] * deployment["shards"]
    return newt_round_min_bytes(
        rows_on_device=rows // replica_axis, n=deployment["n"],
        batch=int(flags["--device-batch"]), pending=int(flags["--device-pending"]),
        key_width=int(flags.get("--device-key-width", 1)))
