"""Each plain reference agrees with the program at a small size, and its
lower-precision control does not: the control is the program's own bfloat16
path (K-means) or the reference with float8 operands (SGD-MF)."""

import pytest

from benchmark import compare, harness, readings
from tests.benchmark import tiny


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.build(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell_name", tiny.CELLS)
@pytest.mark.parametrize("seed", (5, tiny.SEED + 3, 977))
def test_program_passes_and_control_fails(tree, cell_name, seed):
    cell, _, _ = harness.open_cell(cell_name, tree, require_accelerator=False)
    data = harness.make_data(cell, seed)
    first, reference = harness.follow_reference(cell, data)
    limits = cell.limits

    program = compare.numbers(
        first, readings.program_record(cell, data), reference)
    ok, _ = compare.verdict(program, limits)
    assert ok, program

    control = compare.numbers(
        first, readings.control_record(cell, data), reference)
    ok, _ = compare.verdict(control, limits)
    assert not ok, control
    # the contract's room: the control reads three times the program or more
    assert control["step1_diff"] >= 3 * program["step1_diff"]


def test_reference_imports_nothing_of_the_program():
    import glob
    import os

    for path in glob.glob(os.path.join(tiny.BENCH, "configs", "*.reference.py")):
        with open(path) as fh:
            text = fh.read()
        assert "harp_tpu" not in text.replace("``harp_tpu/", "")
        assert "import benchmark" not in text and "from benchmark" not in text
