"""The control, the reference computed in bfloat16, read against the
float32 reference at a size the CPU holds: it must fail one of the cell's
limits, as it does on the chip at the cell's size (PERF.md); so must each
planted fault, the worse schedule included."""
import pytest

from bench import calibrate
from bench.tests.conftest import tiny_cell


@pytest.mark.parametrize("workload", ["cnn-mnist.paper-5x8.fedleo",
                                      "unet-deepglobe.paper-5x8.fedleo"])
def test_control_and_faults_fail_the_limits(workload):
    cell = tiny_cell(workload)
    out = calibrate.calibrate_seed(cell, seed=2_147_483_659, faults=True)

    def fails(numbers):
        return any(numbers[k] > lim for k, lim in cell.limits.items())

    assert not fails(out["program"])
    assert fails(out["control_bfloat16"])
    assert fails(out["state_unchanged"])
    assert fails(out["half_batch"])
    assert fails(out["worse_schedule"])
    assert out["program"]["schedule_gap"] == 0.0
