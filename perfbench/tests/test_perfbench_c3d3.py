"""A whole run of the 3C3D cells at a size a test run holds: the program
passes the comparison at the engine's per-sample, per-pair and per-leaf
structure, and the control (the reference in bfloat16 in the program's
place: the CPU ignores the cell's own "high" matmul precision) and every
fault planted under the timed path fail it, under the real cells'
limits.  With every extension, the exact GGN sweep among them, the
program agrees with the reference at the CPU's float32 precision."""
import pytest

import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("c3d3"),
                          names=("c3d3.tiny", "c3d3.small",
                                 "c3d3.first_order.tiny", "c3d3.all_ext.tiny"))


@pytest.mark.parametrize("name", ["c3d3.small", "c3d3.first_order.tiny",
                                  "c3d3.all_ext.tiny"])
def test_program_is_correct(root, name):
    res = tiny.run_cell(root, name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 6
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"step_ms", "grad_step_ms", "peak_hbm_gib",
                                   "setup_s"}


def test_control_fails(root):
    res = tiny.run_cell(root, "c3d3.tiny", mode="control")
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("mode", ["frozen", "half_batch", "altered",
                                  "permuted"])
def test_faults_fail(root, mode):
    res = tiny.run_cell(root, "c3d3.small", mode=mode)
    assert not res["correct"], res["checks"]
