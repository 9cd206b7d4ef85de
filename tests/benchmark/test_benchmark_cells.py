"""Every cell through the real command in rehearsal (CPU, tiny sizes,
about a second), end-to-end metrics (--trace 0). Rehearsal numbers are no
measurements."""

import pytest

from bench_testlib import cell_names, check_cell_rehearses


@pytest.mark.parametrize("cell", cell_names())
def test_cell_rehearses(cell):
    check_cell_rehearses(cell, trace=0)
