"""``root``: the tree itself, and a copy of it for each cell that
``next_cell.py`` describes, added there as a later PR adds it (new files,
entries appended to the ends of their lists).  A test that reads the tree's
``BENCHMARK.json`` takes ``root`` and so says of every one of them what it
says of the tree: an appended configuration, cell or metric breaks none."""

import pytest

from benchmark import run
from tests.benchmark_tests import next_cell


@pytest.fixture(params=["tree", *next_cell.CELLS])
def root(request, tmp_path):
    if request.param == "tree":
        return run.ROOT
    copy = next_cell.copy_tree(str(tmp_path))
    next_cell.CELLS[request.param](copy)
    return copy
