"""Pallas kernel allclose sweeps against the pure-jnp oracle (interpret
mode), as required per kernel: shapes × dtypes × tile sizes + hypothesis."""
import numpy as np
import jax.numpy as jnp
import pytest
from _propshim import given, settings, st

from repro.core import csrc, blockell
from repro.kernels import ref, ops
from repro.kernels.csrc_spmv import blockell_spmv, blockell_spmv_windows


def _check(M, tm=16, k_step=1024, rtol=2e-4):
    A = csrc.to_dense(M)
    x = np.random.default_rng(7).standard_normal(M.n).astype(np.float32)
    pack = blockell.pack(M, tm=tm, k_step=k_step)
    y_k = np.asarray(blockell_spmv(pack, jnp.asarray(x), interpret=True))
    y_ref = np.asarray(ref.csrc_spmv(M, jnp.asarray(x),
                                     use_numeric_symmetry=False))
    y_dense = A @ x
    scale = max(1.0, np.abs(y_dense).max())
    np.testing.assert_allclose(y_k / scale, y_dense / scale,
                               rtol=rtol, atol=rtol)
    np.testing.assert_allclose(y_k / scale, np.asarray(y_ref) / scale,
                               rtol=rtol, atol=rtol)
    return pack


@pytest.mark.parametrize("n,band,tm", [
    (64, 3, 8), (100, 9, 8), (256, 17, 16),
    pytest.param(300, 40, 16, marks=pytest.mark.slow),
    pytest.param(512, 50, 64, marks=pytest.mark.slow),
    pytest.param(1000, 100, 128, marks=pytest.mark.slow),
    (130, 5, 128),   # n < tm*2 edge
])
def test_kernel_shape_sweep(n, band, tm):
    M = csrc.fem_band(n, band, seed=n + band)
    _check(M, tm=tm)


@pytest.mark.parametrize("sym", [False, True])
def test_kernel_symmetry_modes(sym):
    """Numerically symmetric packs stream al only (paper's one-fewer-load);
    both modes must agree with dense."""
    M = csrc.fem_band(200, 12, seed=5, numeric_symmetric=sym)
    pack = _check(M, tm=16)
    assert pack.num_symmetric == sym


def test_kernel_poisson():
    _check(csrc.poisson2d(20), tm=32)


def test_kernel_multi_ktile():
    """Force several k-steps per row tile (grid dim 2 > 1) to exercise the
    revisited-output accumulation."""
    M = csrc.fem_band(256, 60, seed=9, fill=0.95)
    pack = blockell.pack(M, tm=64, k_step=1024)
    assert pack.s // 1024 > 1
    _check(M, tm=64)


def test_pack_rejects_unbanded():
    M = csrc.random_symmetric_pattern(512, 6, seed=1)
    with pytest.raises(ValueError):
        blockell.pack(M, tm=16, w_cap=256)


def test_operator_auto_fallback():
    """SpmvOperator falls back to segment-sum for unbanded matrices (the
    paper's cage15/F1 case) and still matches dense."""
    M = csrc.random_symmetric_pattern(300, 5, seed=2)
    op = ops.SpmvOperator(M, path="auto", w_cap=256)
    assert op.path == "segment"
    A = csrc.to_dense(M)
    x = np.random.default_rng(1).standard_normal(M.n).astype(np.float32)
    np.testing.assert_allclose(np.asarray(op(jnp.asarray(x))), A @ x,
                               rtol=2e-4, atol=2e-4)


def test_windows_before_accumulation():
    """The kernel's per-tile windows must sum (overlap-add) to the product —
    the two-phase structure mirrors the paper's compute/accumulate split."""
    M = csrc.fem_band(128, 10, seed=3)
    pack = blockell.pack(M, tm=16)
    x = np.random.default_rng(2).standard_normal(M.n).astype(np.float32)
    wins = blockell_spmv_windows(pack, jnp.asarray(x), interpret=True)
    assert wins.shape == (pack.nt, pack.w_pad)
    y = blockell.overlap_add(pack, wins)
    np.testing.assert_allclose(np.asarray(y), csrc.to_dense(M) @ x,
                               rtol=2e-4, atol=2e-4)


def test_transpose_product():
    M = csrc.fem_band(80, 6, seed=4)
    A = csrc.to_dense(M)
    x = np.random.default_rng(3).standard_normal(80).astype(np.float32)
    y = np.asarray(ops.spmv_transpose(M, jnp.asarray(x)))
    np.testing.assert_allclose(y, A.T @ x, rtol=1e-4, atol=1e-4)


def test_spmm_multi_rhs():
    M = csrc.fem_band(64, 5, seed=6)
    A = csrc.to_dense(M)
    X = np.random.default_rng(4).standard_normal((64, 7)).astype(np.float32)
    Y = np.asarray(ops.spmm(M, jnp.asarray(X)))
    np.testing.assert_allclose(Y, A @ X, rtol=1e-4, atol=1e-4)


@settings(max_examples=5, deadline=None)
@given(st.integers(16, 120), st.integers(1, 12), st.integers(0, 10_000),
       st.booleans())
def test_property_kernel_matches_dense(n, band, seed, sym):
    M = csrc.fem_band(n, min(band, n - 1), seed=seed,
                      numeric_symmetric=sym)
    _check(M, tm=8)


@pytest.mark.parametrize("nrhs", [1, 4, 8])
def test_spmm_kernel_matches_dense(nrhs):
    """Multi-RHS Pallas kernel vs dense, across RHS widths."""
    from repro.kernels.csrc_spmv import blockell_spmm
    M = csrc.fem_band(200, 12, seed=11)
    pack = blockell.pack(M, tm=16)
    A = csrc.to_dense(M)
    X = np.random.default_rng(5).standard_normal((200, nrhs)).astype(
        np.float32)
    Y = np.asarray(blockell_spmm(pack, jnp.asarray(X), interpret=True))
    ref_y = A @ X
    scale = max(1.0, np.abs(ref_y).max())
    np.testing.assert_allclose(Y / scale, ref_y / scale, rtol=2e-4,
                               atol=2e-4)


def test_spmm_kernel_symmetric_stream():
    from repro.kernels.csrc_spmv import blockell_spmm
    M = csrc.fem_band(128, 8, seed=12, numeric_symmetric=True)
    pack = blockell.pack(M, tm=16)
    A = csrc.to_dense(M)
    X = np.random.default_rng(6).standard_normal((128, 3)).astype(np.float32)
    Y = np.asarray(blockell_spmm(pack, jnp.asarray(X), interpret=True))
    np.testing.assert_allclose(Y, A @ X, rtol=2e-4, atol=2e-4)


def test_int16_index_pack():
    """16-bit local indices (paper §1 index-compression lever): halves the
    index stream, bit-identical results."""
    M = csrc.fem_band(300, 20, seed=13)
    p32 = blockell.pack(M, tm=16)
    p16 = blockell.pack(M, tm=16, index_dtype=jnp.int16)
    assert p16.col_local.dtype == jnp.int16
    assert p16.streamed_bytes() < p32.streamed_bytes()
    x = np.random.default_rng(8).standard_normal(300).astype(np.float32)
    y32 = np.asarray(blockell_spmv(p32, jnp.asarray(x), interpret=True))
    y16 = np.asarray(blockell_spmv(p16, jnp.asarray(x), interpret=True))
    np.testing.assert_array_equal(y32, y16)


class TestFlatKernel:
    """Flattened 1-D grid kernel (scalar-prefetched tile ids): removes
    cross-tile ELL padding; allclose vs dense across shapes."""

    @pytest.mark.parametrize("n,band,tm", [
        (128, 5, 16), (300, 20, 16), (512, 40, 64),
    ])
    def test_matches_dense(self, n, band, tm):
        from repro.kernels.csrc_spmv_flat import pack_flat, flat_spmv
        M = csrc.fem_band(n, band, seed=n)
        A = csrc.to_dense(M)
        x = np.random.default_rng(0).standard_normal(n).astype(np.float32)
        pack = pack_flat(M, tm=tm)
        y = np.asarray(flat_spmv(pack, jnp.asarray(x), interpret=True))
        ref_y = A @ x
        scale = max(1.0, np.abs(ref_y).max())
        np.testing.assert_allclose(y / scale, ref_y / scale,
                                   rtol=2e-4, atol=2e-4)

    def test_beats_rect_padding_on_skew(self):
        """Skew strong enough that the densest tile needs several k-steps:
        the rectangular grid pads every tile to it, the flat grid
        doesn't."""
        from repro.kernels.csrc_spmv_flat import pack_flat, flat_spmv
        rows, cols, vals = [], [], []
        n = 1024
        rng = np.random.default_rng(1)
        for i in range(n):
            rows.append(i); cols.append(i); vals.append(50.0)
            width = 60 if i < 64 else 3
            for j in range(max(0, i - width), i):
                vl, vu = rng.standard_normal(2)
                rows += [i, j]; cols += [j, i]; vals += [vl, vu]
        M = csrc.from_coo(np.array(rows), np.array(cols),
                          np.array(vals, np.float64), n=n,
                          pad_pattern=False)
        rect = blockell.pack(M, tm=64, k_step=1024)
        flat = pack_flat(M, tm=64)
        assert flat.pad_ratio < rect.pad_ratio
        assert flat.streamed_bytes() < rect.streamed_bytes()
        # and it stays correct on the same matrix
        x = np.random.default_rng(2).standard_normal(n).astype(np.float32)
        y = np.asarray(flat_spmv(flat, jnp.asarray(x), interpret=True))
        ref_y = csrc.to_dense(M) @ x
        scale = max(1.0, np.abs(ref_y).max())
        np.testing.assert_allclose(y / scale, ref_y / scale,
                                   rtol=2e-4, atol=2e-4)


def test_bf16_value_stream():
    """Mixed-precision lever: bf16 values (fp32 accumulation) halve the
    value stream; accuracy within bf16 tolerance."""
    M = csrc.fem_band(256, 16, seed=21)
    A = csrc.to_dense(M)
    x = np.random.default_rng(9).standard_normal(256).astype(np.float32)
    pack = blockell.pack(M, tm=16, dtype=jnp.bfloat16,
                         index_dtype=jnp.int16)
    p32 = blockell.pack(M, tm=16)
    assert pack.streamed_bytes() < p32.streamed_bytes()
    y = np.asarray(blockell_spmv(pack, jnp.asarray(x), interpret=True))
    ref_y = A @ x
    scale = max(1.0, np.abs(ref_y).max())
    np.testing.assert_allclose(y / scale, ref_y / scale, atol=3e-2)
