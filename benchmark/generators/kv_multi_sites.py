"""The load generator for commands over several keys and shards from clients
at every site: ``kv_multi``'s closed loop, its engine, records and read-back,
with a hello that names a site.

Upstream runs clients in every region, each attached to its closest process,
which coordinates its commands in every shard they touch (``fantoch_exp``'s
rig, ``main.rs:31-32``; ``fantoch_ps/src/protocol/partial.rs:8``: the
coordinator forwards the submit to its own site's process of every other
shard).  Here a generator process is a site's clients, as in ``kv_sites``:
process ``p`` registers its share of the mix's clients (``own_clients``) at
site ``p % client_sites`` in its ``ClientHi`` and writes the site into its
records as a column ``site``, beside ``kv_multi``'s ``shards``.  Everything
else is ``kv_multi``'s: ``main`` is its ``main``, run with the engine below in
the place of its own (``kv_multi.main`` builds its engine by the module's name
``MultiEngine``, and this process is no one else's).

Run as ``python -m benchmark.generators.kv_multi_sites <plan.json>`` by
``benchmark.run``.  Mix parameters beside ``kv_multi``'s: ``client_sites``.
Against a program that refuses the site (a round with one coordinator) the
server ends the session before the ack and the process ends before
``READY``."""

from __future__ import annotations

import functools
import json
import sys

import numpy as np

from benchmark.generators import kv_multi
from benchmark.generators.kv_multi import MultiEngine
from benchmark.generators.kv_sites import SiteEngine, site_of


class SiteMultiEngine(SiteEngine, MultiEngine):
    """``kv_multi``'s engine with its clients at ``site``: ``kv_sites``'s
    hello and ``site`` column over ``kv_multi``'s commands and records."""


def read_back_mix(host: str, port: int, seed: int, mix: dict, payload: int,
                  keys, limit_s: float) -> dict:
    """``kv_multi``'s read-back, by a client of its own at site 0."""
    history = kv_multi.read_back_mix(host, port, seed, mix, payload, keys, limit_s)
    history["site"] = np.zeros(len(history["client"]), np.int32)
    return history


def main(plan_path: str) -> None:
    with open(plan_path) as fh:
        plan = json.load(fh)
    kv_multi.MultiEngine = functools.partial(
        SiteMultiEngine, site=site_of(plan["proc_index"], plan["mix"]))
    kv_multi.main(plan_path)


if __name__ == "__main__":
    main(sys.argv[1])
