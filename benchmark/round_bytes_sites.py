"""The bytes one dependency round with a coordinator at every site must move
through a device's memory, computed from the deployment's shape alone: the
numerator of that round's share of the memory roofline
(``benchmark/readers/sites_round_hbm_share.py``).

"Must" is the algorithm's, not the program's, as in ``benchmark/round_bytes.py``:
the clock tables are touched where a command of the round has a key, and
nowhere else; the committed dependencies are written once; how the components
of the round's graph are found costs nothing here, because nothing says they
have to be found through memory.  What the program moves beyond that (whole
tables staged by their scatters, the views of every replica row, the
resolver's passes) is the gap the share shows, and a later kernel for the
scatters or the resolver moves the share without moving this count.
"""

from __future__ import annotations

INT32 = 4
DEP_COMMIT = ("epaxos", "atlas")


def sites_round_min_bytes(shard_rows_on_device: int, batch: int, pending: int,
                          key_width: int, fast_quorum: int) -> int:
    """One dep-commit round with a coordinator at every site over ``W =
    pending + batch`` working rows of ``key_width`` key slots, on a device
    that holds ``shard_rows_on_device`` replica rows of any one shard
    (``parallel/mesh_step.py`` ``protocol_step(sites=n)``).  Read and written
    once each, int32 unless said:

    * the batch's columns in: keys ``[batch, key_width]``, source, sequence,
      and the read flag (a byte);
    * the pending buffer, in and out: keys, source, sequence, gid and the
      read flag (a byte) a slot;
    * the two clock tables (latest write, latest read): one entry each a (row
      held of the slot's shard, working row, key slot), read for the row's
      word and written with what executed; a key slot belongs to one shard,
      so only that shard's rows among the rows held have it;
    * the committed dependencies, written once: ``2 * key_width *
      fast_quorum`` a working row;
    * the round's other outputs over ``W``: order and gid (int32) and the
      executed, fast-path and finish flags (a byte each).
    """
    work = pending + batch
    slots = work * key_width
    columns_in = batch * ((key_width + 2) * INT32 + 1)
    pending_in_out = 2 * pending * ((key_width + 3) * INT32 + 1)
    clocks = 2 * 2 * shard_rows_on_device * slots * INT32
    dependencies = work * 2 * key_width * fast_quorum * INT32
    outputs = work * (2 * INT32 + 3)
    return columns_in + pending_in_out + clocks + dependencies + outputs


def fast_quorum_size(protocol: str, n: int, f: int) -> int:
    """``fantoch/src/config.rs``: Atlas's ``n / 2 + f``; EPaxos's, whatever
    ``f`` says, ``m + (m + 1) / 2`` with ``m = n / 2``."""
    if protocol == "atlas":
        return n // 2 + f
    minority = n // 2
    return minority + (minority + 1) // 2


def round_min_bytes(config: dict, replica_axis: int) -> int | None:
    """The bytes of one such round on the fullest device of a deployment whose
    configuration file is ``config``, on a mesh whose replica axis is
    ``replica_axis`` (the rows are dealt evenly over it); nothing for a
    protocol whose round is not the dependency round."""
    deployment = config["deployment"]
    if deployment["protocol"] not in DEP_COMMIT:
        return None
    words = config["server_flags"]
    flags = {word: words[at + 1] for at, word in enumerate(words) if word.startswith("-")}
    rows = deployment["n"] * deployment["shards"]
    return sites_round_min_bytes(
        shard_rows_on_device=min(deployment["n"], rows // replica_axis),
        batch=int(flags["--device-batch"]), pending=int(flags["--device-pending"]),
        key_width=int(flags.get("--device-key-width", 1)),
        fast_quorum=fast_quorum_size(deployment["protocol"], deployment["n"], deployment["f"]))
