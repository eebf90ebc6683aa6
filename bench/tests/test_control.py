"""The control (the bf16 reference in the program's place) fails the
check at test size, by the limits the cells use."""
import pytest

import control
from conftest import small_cell


@pytest.mark.parametrize("workload", ["hpcg27.cg", "fem_tet.step"])
def test_control_is_not_correct(run_small, workload):
    res = run_small(workload, entries=control.entries(small_cell(workload)))
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
