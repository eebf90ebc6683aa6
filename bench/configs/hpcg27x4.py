"""HPCG's 27-point stencil on four ranks: the global grid of a 1x1x4
process grid, whose rows the mesh splits into one 104^3 slab a chip.

The matrix, the right-hand sides and the float64 reference are
``hpcg27.py``'s, which take the grid from ``nx``, ``ny`` and ``nz``: this
configuration only states the global grid (``nz`` = 4 ranks x 104).
"""
from pathlib import Path

from harness import load_module

_base = load_module(Path(__file__).with_name("hpcg27.py"),
                    "bench_config_hpcg27_of_hpcg27x4")

arrays = _base.arrays
rhs_ring = _base.rhs_ring
reference = _base.reference
