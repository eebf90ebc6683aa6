"""Production mesh construction.

A function, not a module-level constant — importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).

Single pod: (data=16, model=16) = 256 chips (one v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the `pod` axis carries
cross-pod data parallelism (its collectives cross DCI, which is why it is a
separate axis — the roofline charges them separately).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the model code constrains shardings inside jit, which
    # Explicit axes (jax.make_mesh's default) refuse
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(devices: int = 8, model: int = 2):
    """Small mesh over fake devices for subprocess tests."""
    data = devices // model
    return _auto_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
