"""The load generator for clients at every site: ``kv_loop``'s closed loop,
its engine, records and read-back, with a hello that names a site.

Upstream runs clients in every region, each attached to its closest process,
which coordinates its commands (``fantoch_exp``'s rig, ``main.rs:31-32``; the
EPaxos paper's s7: clients co-located with each of five replicas).  Here a
generator process is a site's clients: process ``p`` registers its share of
the mix's clients (``own_clients``: ids ``1 + p, 1 + p + n_procs, ...``) at
site ``p % client_sites`` in its ``ClientHi``, so the server's replica at that
site coordinates what they send, and writes the site into its records as a
column ``site`` (carried with the rest; ``benchmark/readers/record_share``
reads it).  Everything else is ``kv_loop``'s: ``main`` is its ``main``, run
with the engine below in the place of its own (``kv_loop.main`` builds its
engine by the module's name ``Engine``, and this process is no one else's).

Run as ``python -m benchmark.generators.kv_sites <plan.json>`` by
``benchmark.run``.  Mix parameters beside ``kv_loop``'s: ``client_sites``.
Against a program whose ``ClientHi`` takes no site the engine's hello raises
and the process ends before ``READY``."""

from __future__ import annotations

import functools
import json
import sys

import numpy as np

from benchmark.generators import kv_loop
from benchmark.generators.kv_loop import ClientHi, Engine


class SiteEngine(Engine):
    """``kv_loop``'s engine with its clients at ``site``."""

    def __init__(self, *args, site: int = 0, **kwargs):
        self.site = site
        super().__init__(*args, **kwargs)

    def _queue(self, message) -> None:
        if isinstance(message, ClientHi):
            message = ClientHi(message.client_ids, site=self.site)
        super()._queue(message)

    def history(self) -> dict:
        history = super().history()
        history["site"] = np.full(len(history["client"]), self.site, np.int32)
        return history


def site_of(proc_index: int, mix: dict) -> int:
    return proc_index % int(mix["client_sites"])


def read_back(host: str, port: int, seed: int, clients: int, payload: int,
              keys, limit_s: float) -> dict:
    """``kv_loop``'s read-back, by a client of its own at site 0."""
    history = kv_loop.read_back(host, port, seed, clients, payload, keys, limit_s)
    history["site"] = np.zeros(len(history["client"]), np.int32)
    return history


def main(plan_path: str) -> None:
    with open(plan_path) as fh:
        plan = json.load(fh)
    kv_loop.Engine = functools.partial(SiteEngine, site=site_of(plan["proc_index"], plan["mix"]))
    kv_loop.main(plan_path)


if __name__ == "__main__":
    main(sys.argv[1])
