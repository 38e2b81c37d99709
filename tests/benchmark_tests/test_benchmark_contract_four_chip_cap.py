"""The cap on four-chip cells (at most half of the cells, rounded down, and
one always), held whatever the number of cells the tree has.

`test_benchmark_contract.py`'s case `too_many_four_chip_cells` puts two more
cells on four chips and expects the rule to refuse; that breaches the cap
only while the copy has at most seven cells (it was written at five: "three
cells of five").  With the seventh cell of the tree its copy has eight, four
of them on four chips, which the cap allows, so the case can no longer fail
the rule, and fails ("DID NOT RAISE").  A PR that adds a cell may not edit
that file, and this one does not: the case is left failing, for a `benchmark`
PR to repair by counting its breach from the cap (`PERF.md` s7, `CHANGES.md`
PR 34).  This file holds the same refusal counted from the cap, on the tree
and on a copy with each of `next_cell.py`'s cells."""

import json
import os

import pytest

from tests.benchmark_tests import contract_rules as rules
from tests.benchmark_tests import next_cell


def _with_four_chip_cells(root, count):
    """``root``'s `BENCHMARK.json` rewritten so that ``count`` of its cells
    ask for four chips: those that do already, then the first of the rest."""
    path = os.path.join(root, "BENCHMARK.json")
    spec = rules.bench(root)
    cells = sorted(spec["workloads"], key=lambda cell: cell["chips"] != 4)  # stable
    assert sum(cell["chips"] == 4 for cell in cells) <= count <= len(cells)
    for at, cell in enumerate(cells):
        cell["chips"] = 4 if at < count else 1
    with open(path, "w") as fh:
        json.dump(spec, fh)


@pytest.mark.parametrize("added", [None, *next_cell.CELLS], ids=["tree", *next_cell.CELLS])
def test_the_rule_takes_half_the_cells_on_four_chips_and_refuses_one_more(tmp_path, added):
    root = next_cell.copy_tree(str(tmp_path))
    if added is not None:
        next_cell.CELLS[added](root)
    rules.the_file_has_the_contracts_keys_and_forms(root)  # holds as it is
    cap = max(1, len(rules.cells(root)) // 2)
    _with_four_chip_cells(root, cap)
    rules.the_file_has_the_contracts_keys_and_forms(root)  # at the cap: taken
    _with_four_chip_cells(root, cap + 1)
    with pytest.raises(AssertionError):
        rules.the_file_has_the_contracts_keys_and_forms(root)
