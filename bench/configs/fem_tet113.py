"""P1 tetrahedral heat conduction at StocF-1465's own size: the Kuhn mesh
of 113^3 cubes, 1,481,544 nodes and 8,657,382 tets.

The mesh, the conductivities and the float64 reference are
``fem_tet.py``'s, which take the box from ``nx``, ``ny`` and ``nz``: this
configuration states the uncut box.  The six element tables are
``fem_tet.py``'s too, stated in float16, in which each of their entries
(a multiple of 1/64 below 1) is exact, so every float32 element matrix
the runner makes from them is the same to the bit.  The runner gathers
``tables[etype]`` into an (ne, 4, 4) intermediate whose minor axis a
v5e pads to 128 lanes: 17.7 GB at 8.66 M tets in float32, over the
chip's 16 GiB, and 8.9 GB in float16.
"""
from pathlib import Path

import numpy as np

from harness import load_module

_base = load_module(Path(__file__).with_name("fem_tet.py"),
                    "bench_config_fem_tet_of_fem_tet113")

mesh = _base.mesh
kappa_ring = _base.kappa_ring
lumped_mass = _base.lumped_mass
reference = _base.reference


def element_tables(cfg: dict):
    """``fem_tet``'s quantized element Laplacians (6, 4, 4), in float16
    (exact), and the lumped-mass diagonal entry."""
    lap, mass = _base.element_tables(cfg)
    half = lap.astype(np.float16)
    assert np.array_equal(half.astype(np.float32), lap)
    return half, mass
