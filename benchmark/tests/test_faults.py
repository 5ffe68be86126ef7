"""A run with the timed path broken underneath reports ``correct``
false: once for each fault that the ``FAULTS`` of a cell's app declares.
One chip has no exchange between chips to leave out."""

import types

import pytest

from benchmark import faults
from benchmark.tests.helpers import assert_fault_caught, fault_cases

CASES = fault_cases()


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_caught(tiny, capsys, cell, fault):
    assert_fault_caught(tiny, capsys, cell, fault)


def test_faults_have_no_table_of_apps():
    """``faults.py`` keeps only the generic patches: the rest come from
    the app, and a fault an app does not declare is refused."""
    assert set(faults.GENERIC) == {"half", "no_exchange"}

    app = types.SimpleNamespace(__name__="some_app",
                                FAULTS={"half": None, "unchanged": None})
    with pytest.raises(ValueError, match="does not apply to 'some_app'"):
        faults.plant("altered", app)
    with pytest.raises(ValueError, match="has to give its own"):
        faults.plant("unchanged", app)
