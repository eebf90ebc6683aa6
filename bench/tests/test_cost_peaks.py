"""Byte counts of the roofline and the peak table."""
import pytest

import cost
import peaks
from conftest import small_cell


def test_csrc_spmv_bytes_counts_each_array_once():
    cell = small_cell("hpcg27.cg")
    n, ia, ja, ad, al, au = cell.config_mod.arrays(cell.config)
    x_and_y = 2 * n * 4
    arrays = sum(a.nbytes for a in (ia, ja, ad, al, au))
    assert cost.csrc_spmv_bytes(n, ja.size) == arrays + x_and_y


def test_csrc_spmv_bytes_hpcg_size():
    # 104^3 rows, (29,791,000 - n) / 2 lower slots
    n = 104 ** 3
    k = (29_791_000 - n) // 2
    assert cost.csrc_spmv_bytes(n, k) == 4 * (n + 2 * k) + 4 * (n + 1 + k) \
        + 8 * n


def test_assembly_bytes():
    # 2 tets of 4 nodes into 5 rows with 9 lower slots
    assert cost.assembly_bytes(2, 4, 5, 9) == 4 * 32 + 4 * 8 + 4 * 23


def test_roofline_share():
    assert cost.roofline_share(819e9 * 1e-3, 2e-3, 819e9) == pytest.approx(
        50.0)


def test_known_kind_and_unknown_kind():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peak table entry"):
        peaks.peaks_for("TPU v99")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
