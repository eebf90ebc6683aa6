"""The benchmark's own tests run on the CPU at sizes a test run can hold;
a TPU trace they need is recorded in ``data/``."""
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

import harness  # noqa: E402

SMALL = {"hpcg27": {"nx": 10, "ny": 9, "nz": 8},
         "fem_tet": {"nx": 4, "ny": 3, "nz": 3}}


def small_cell(workload: str) -> harness.Cell:
    """A cell of BENCHMARK.json with its grid cut to test size."""
    cell = harness.find_cell(harness.load_spec(), workload)
    cell.config = dict(cell.config, **SMALL[cell.config["name"]])
    return cell


@pytest.fixture
def run_small(tmp_path):
    """Run a cell at test size on the CPU, without the look for a chip."""
    def run(workload, seconds=1.0, seed=2 ** 33 + 7, entries=None):
        cell = small_cell(workload)
        return harness.run_cell(cell, seed, seconds, False,
                                time.perf_counter(), require_chip=False,
                                entries=entries, cache_root=tmp_path)
    return run
