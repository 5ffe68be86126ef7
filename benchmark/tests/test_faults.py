"""A run with the timed path broken underneath reports ``correct``
false: once for each fault a cell can have. One chip has no exchange
between chips to leave out."""

import pytest

from benchmark import faults
from benchmark.tests.helpers import APPS_AND_CHIPS, run_cell

CASES = [(cell, f) for cell, (app, chips) in APPS_AND_CHIPS.items()
         for f in faults.APPLIES[app] if f != "no_exchange" or chips > 1]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_caught(tiny, capsys, cell, fault):
    with faults.plant(fault, APPS_AND_CHIPS[cell][0]):
        res = run_cell(tiny, capsys, cell)
    assert res["correct"] is False, (fault, res["checks"])
    # The comparison said why: some number is over its limit.
    assert any(c["value"] is None or c["value"] > c["limit"]
               for c in res["checks"].values())
