"""Bytes a sharded CSRC product needs on one chip of a row mesh, from its
shapes alone (see cost.py for the single-chip product)."""
from __future__ import annotations

from cost import csrc_spmv_bytes


def mesh_spmv_shard_bytes(n: int, k: int, p: int, band: int,
                          value_bytes: int = 4) -> int:
    """One chip's share of a product y = A·x split into p row blocks: the
    CSRC bytes of ceil(n/p) rows with ceil(k/p) strictly-lower slots (the
    mean shard), plus the halo the effective accumulation exchanges with
    a neighbour: ``band`` rows of x in and ``band`` rows of y out."""
    rows, slots = -(-n // p), -(-k // p)
    halo = 2 * value_bytes * band if p > 1 else 0
    return csrc_spmv_bytes(rows, slots, value_bytes=value_bytes) + halo
