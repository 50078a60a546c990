"""``correct`` comes out false when the timed path of a one-chip cell is
broken underneath: a step that returns its state unchanged, half of the
batch left out, an answer altered where it is produced, and the control (the
reference in the program's place with its activations, or its activations
and parameters, in bfloat16). A sound run at the same size comes out
true."""
import pytest

from bench.tests import bench_faults


@pytest.mark.parametrize("fault,correct", [
    ("sound", True), ("unchanged", False), ("half_batch", False),
    ("answer", False), ("control", False), ("control_params", False)])
def test_one_chip_cells_catch_each_fault(fault, correct):
    r = bench_faults.drive("resnet50.b128", fault)
    assert r["correct"] is correct, r["checks"]
