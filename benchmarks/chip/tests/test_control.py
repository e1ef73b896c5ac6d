"""The control (the reference one weight bit coarser, in the program's
place) fails every cell's checks, at a size a test run holds."""
import pytest

import control
from harness import spec
from rehearse import tiny


@pytest.mark.parametrize("name", ["imdb-serve", "imdb-batch", "mnist-batch"])
def test_control_fails(name):
    cell = tiny(spec.load_cell(name))
    found = control.control_checks(cell, seed=2 ** 33 + 21, seconds=2.0)
    assert not all(c.ok for c in found), [(c.name, c.value) for c in found]
