"""A whole run of each cell on the CPU at a small size, past the harness's
look for a chip: sound, it is correct; with the timed path broken
underneath (``faults.py``), ``correct`` comes out false."""
import pytest

import faults

pytestmark = pytest.mark.tier1

ONE_CHIP = [
    ("fig9_10.single", "none"),
    ("fig9_10.single", "state_unchanged"),
    ("fig9_10.single", "answer_altered"),
    ("fig4.sweep", "none"),
    ("fig4.sweep", "state_unchanged"),
    ("fig4.sweep", "half_batch"),
    ("fig4.sweep", "answer_altered"),
    ("fig9_10.sweep", "none"),
    ("fig9_10.sweep", "state_unchanged"),
    ("fig9_10.sweep", "half_batch"),
    ("fig9_10.sweep", "answer_altered"),
]


@pytest.mark.parametrize("cell,fault", ONE_CHIP)
def test_one_chip_cell(cell, fault):
    result = faults.run(cell, fault)
    assert result["attempted"] > 0
    assert result["correct"] is (fault == "none"), result["checks"]
    assert list(result)[-1] == "checks"
