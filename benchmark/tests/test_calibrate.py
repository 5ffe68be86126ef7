"""``calibrate.py`` at a tiny size: the program reads at float32 rounding
against the reference, the control and every planted fault (the generic
``half`` and ``altered``, and the app's ``REFERENCE_FAULTS``) read far
above it."""

import pytest

from benchmark.tests.helpers import CELLS, calibrate_readings

UNITS = {"als-rmat18": 0}


@pytest.mark.parametrize("cell", CELLS)
def test_calibrate_readings(tiny, capsys, cell):
    calibrate_readings(tiny, capsys, cell, UNITS.get(cell, 1))
