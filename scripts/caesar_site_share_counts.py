"""What the plain reference of Caesar's round with a coordinator at every site
(``tests/caesar_sites_reference.py``) reads when a round's sites are not an
equal share of it: counts from the CPU for ``PERF.md`` §6 (PR 59), no speed.

    JAX_PLATFORMS=cpu python3 scripts/caesar_site_share_counts.py [seed]

Six rounds of 2,700 commands at n = 7 (what a served round of the cell
``caesar_n7_1m_7site.conflict50_7site_sat`` holds), key 0 at 50%, the sites in
turn in a shuffled order of first appearance; a round's site shares are equal,
or drawn from a Dirichlet distribution of the given concentration (the lower,
the more uneven).  Seed 7 is the one ``PERF.md`` quotes.  A simulation: no
counter of a served round's site shares exists yet, so that a served round's
shares look like any of these draws is not measured.
"""

import os
import sys
from itertools import zip_longest

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from tests.caesar_sites_reference import CaesarSitesReference  # noqa: E402

N, ROWS, BATCH, ROUNDS = 7, 2700, 4096, 6


def shares(rng, concentration):
    """% of the executed commands that were retried / of whose ring a member waited."""
    reference = CaesarSitesReference(N, BATCH, 1)
    executed = slow = waited = 0
    next_seq = [1] * N
    for _ in range(ROUNDS):
        if concentration is None:
            counts = [ROWS // N] * N
        else:
            counts = rng.multinomial(ROWS, rng.dirichlet([concentration] * N))
        by_site = [[site] * count for site, count in enumerate(counts) if count]
        rng.shuffle(by_site)
        in_turn = [site for turn in zip_longest(*by_site) for site in turn if site is not None]
        key, src, seq = [], [], []
        for site in in_turn:
            key.append(0 if rng.random() < 0.5 else 1000 + len(key) + 7919 * next_seq[site])
            src.append(1 + site)
            seq.append(next_seq[site])
            next_seq[site] += 1
        pad = BATCH - len(key)
        got = reference.round(key + [-1] * pad, src + [0] * pad, seq + [0] * pad)
        executed += len(got.order)
        slow += got.slow_paths
        waited += got.tallies["wait_rows"]
    return round(100 * slow / executed, 2), round(100 * waited / executed, 2), executed


if __name__ == "__main__":
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 7
    rng = np.random.default_rng(seed)
    for name, concentration in (("equal", None), ("dirichlet 1.0", 1.0),
                                ("dirichlet 0.5", 0.5), ("dirichlet 0.25", 0.25)):
        print(name, "slow %, waited %, executed:", shares(rng, concentration), flush=True)
