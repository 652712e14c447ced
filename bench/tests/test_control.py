"""The comparison that decides ``correct`` passes the program and fails
the control: the reference put in the program's place, computed one
precision below the program's (bfloat16 for the float32 relaxation;
float32 for the float64 timeline).

This is the test-sized copy of the control runs made on the chip at the
cells' own sizes (``python3 -m bench.control``)."""

import pytest

SEEDS = [3, 2**31 + 5]

# the number the control breaks in each kind, by three times its limit
BROKEN = {"search": "mapping_violation", "suite": "texec_gap"}


@pytest.mark.parametrize("kind", ["search", "suite"])
@pytest.mark.parametrize("seed", SEEDS)
def test_program_passes_and_control_fails(harness, tiny_cell, search_cell,
                                          kind, seed):
    cell = search_cell if kind == "search" else tiny_cell(kind)
    result = harness.execute(cell, seed, 0.5, False, control=True)
    assert result["correct"], result["checks"]
    control = result["control_checks"]
    c = control[BROKEN[kind]]
    assert c["value"] >= 3 * c["limit"], control
