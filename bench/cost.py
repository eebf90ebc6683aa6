"""Bytes and operations the algorithms need, from their shapes alone.

These count what the algorithm has to move, not what an implementation
streams, so a roofline share built on them stays comparable across
implementations.  Both products are bound by memory: the CSRC product
does 2 flops per 12 bytes, assembly none but additions.
"""
from __future__ import annotations


def csrc_spmv_bytes(n: int, k: int, value_bytes: int = 4,
                    index_bytes: int = 4) -> int:
    """One product y = A·x of a square CSRC matrix with k strictly-lower
    non-zeros: read ad (n), ia (n+1), ja (k), al (k), au (k) and x (n),
    write y (n)."""
    return (value_bytes * (n + 2 * k) + index_bytes * (n + 1 + k)
            + value_bytes * 2 * n)


def assembly_bytes(ne: int, nen: int, n: int, k: int,
                   value_bytes: int = 4, index_bytes: int = 4) -> int:
    """One assembly of ne elements with nen nodes each into a CSRC matrix
    with n rows and k strictly-lower slots: read the element matrices
    (ne·nen² values) and the connectivity (ne·nen indices), write the
    values [ad | al | au] (n + 2k)."""
    return (value_bytes * ne * nen * nen + index_bytes * ne * nen
            + value_bytes * (n + 2 * k))


def roofline_share(least_bytes: float, device_s: float,
                   hbm_bytes_per_s: float) -> float:
    """Per cent of the memory roofline: the least time the bytes take at
    the chip's peak bandwidth over the measured device time."""
    return 100.0 * (least_bytes / hbm_bytes_per_s) / device_s
