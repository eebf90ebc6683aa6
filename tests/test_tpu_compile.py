"""Compile the Pallas kernels of the main path for a TPU v5e, no chip needed.

The TPU compiler is installed with jax, so a described (not attached)
``v5e:2x2`` topology lets every one-hot kernel be lowered and compiled
ahead of time at the widths ``chip_smoke.py`` runs: fem_band(2**20, 16)
(n = 1 048 576 rows, bandwidth 16, ~10 k-step slots per row) for the SpMV
and SpMM kernels, and grid_tet(48) for the assembly grid; the assembly
executors the tuner measures are compiled at fem_tet113's 8.66 M tets.
Interpret-mode tests cannot see what this catches: block shapes that
break the 8×128 tiling rule, VMEM over-subscription, ops Mosaic cannot
lower, programs over a chip's memory.  The
row-padded ``ell`` product, plain XLA, is compiled at the hpcg27 cell's
104³ rows, the main path of both benchmark cells, and so is its halo
shard function on a one-wide mesh at the hpcg27x4 cell's slab.

The topology is described inside a module-scoped fixture (never at
import), and everything is compiled from ShapeDtypeStructs in this
process: only one process may hold the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core.blockell import BlockEll
from repro.core.distributed import halo_shard_fn
from repro.kernels import assembly_scatter as akern
from repro.kernels.csrc_spmv import (ONEHOT_MAX_WINDOW, blockell_spmm,
                                     blockell_spmv)
from repro.kernels.csrc_spmv_ell import (EllHalo, EllPack, ell_local_fn,
                                        ell_spmm, ell_spmv)
from repro.kernels.csrc_spmv_flat import FlatBlockEll, flat_spmm, flat_spmv
from repro.kernels.csrc_spmv_nnzsplit import (NnzSplitPack, nnzsplit_spmm,
                                              nnzsplit_spmv)

N = 2 ** 20                 # chip_smoke phase A/B rows
SERVE_NRHS = 8              # SpmvServingEngine.serve_nrhs default
TET_SIZE = 1_707_697        # grid_tet(48): n + 2k of the unified vector
TET_CONTRIBS = 10_616_832   # grid_tet(48): ne * edof^2
FEM113_NE = 6 * 113 ** 3    # fem_tet113: 8,657,382 tets
FEM113_SIZE = 21_912_622    # its n + 2k
HPCG_ROWS = 104 ** 3        # the hpcg27 cell's 27-point stencil
HPCG_WIDTH = 13             # its most lower slots of a row
HPCG_HALO = 10_928          # hpcg27x4's band 10,921, 8-aligned


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return make


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _window(tm, band=16):
    return -(-(tm + band) // max(128, tm)) * max(128, tm)


@pytest.mark.parametrize("tm,vdt,idt,nrhs", [
    (128, jnp.float32, jnp.int32, 1),
    (128, jnp.float32, jnp.int32, SERVE_NRHS),
    (32, jnp.float32, jnp.int32, 1),
    (128, jnp.bfloat16, jnp.int16, SERVE_NRHS),
])
def test_rect_kernel_compiles(shape, tm, vdt, idt, nrhs):
    nt, w, s = N // tm, _window(tm), 2048

    def run(vl, vu, col, row, ad, x):
        pk = BlockEll(n=N, tm=tm, nt=nt, w_pad=w, s=s, vals_l=vl, vals_u=vu,
                      col_local=col, row_in_win=row, ad=ad,
                      num_symmetric=False, pad_ratio=1.0)
        if x.ndim == 2:
            return blockell_spmm(pk, x, interpret=False)
        return blockell_spmv(pk, x, interpret=False)

    x = shape((N, nrhs) if nrhs > 1 else (N,), jnp.float32)
    txt = _compiled_text(run, shape((nt, s), vdt), shape((nt, s), vdt),
                         shape((nt, s), idt), shape((nt, s), idt),
                         shape((nt, tm), vdt), x)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("nrhs", [1, SERVE_NRHS])
def test_flat_kernel_compiles(shape, nrhs):
    tm, ks = 128, 8
    nt, w = N // tm, _window(tm)
    steps = 2 * nt

    def run(vl, vu, col, row, ad, tile, first, x):
        pk = FlatBlockEll(n=N, tm=tm, nt=nt, w_pad=w, total_steps=steps,
                          ks=ks, vals_l=vl, vals_u=vu, col_local=col,
                          row_in_win=row, ad=ad, tile_of_step=tile,
                          first_of_tile=first, num_symmetric=False,
                          pad_ratio=1.0)
        if x.ndim == 2:
            return flat_spmm(pk, x, interpret=False)
        return flat_spmv(pk, x, interpret=False)

    stream = shape((steps, ks, 128), jnp.float32)
    index = shape((steps, ks, 128), jnp.int32)
    txt = _compiled_text(run, stream, stream, index, index,
                         shape((nt, tm), jnp.float32),
                         shape((steps,), jnp.int32),
                         shape((steps,), jnp.int32),
                         shape((N, nrhs) if nrhs > 1 else (N,),
                               jnp.float32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("ks,r_pad,nrhs,idt", [
    (8, 128, 1, jnp.int32), (8, 128, SERVE_NRHS, jnp.int32),
    (2, 128, 1, jnp.int16), (8, ONEHOT_MAX_WINDOW, SERVE_NRHS, jnp.int32),
])
def test_nnzsplit_kernel_compiles(shape, ks, r_pad, nrhs, idt):
    k = 10 * N                                  # lower slots of the class
    nc = -(-2 * k // (ks * 128))

    def run(vals, lrow, src, row0, fixup, ad, x):
        pk = NnzSplitPack(n=N, num_chunks=nc, ks=ks, r_pad=r_pad, vals=vals,
                          lrow=lrow, src=src, chunk_row0=row0,
                          fixup_idx=fixup, ad=ad, num_symmetric=False,
                          pad_ratio=1.0)
        if x.ndim == 2:
            return nnzsplit_spmm(pk, x, interpret=False)
        return nnzsplit_spmv(pk, x, interpret=False)

    txt = _compiled_text(
        run, shape((nc, ks, 128), jnp.float32),
        shape((nc, ks, 128), idt), shape((nc * ks * 128,), idt),
        shape((nc,), jnp.int32), shape((nc * r_pad,), jnp.int32),
        shape((N,), jnp.float32),
        shape((N, nrhs) if nrhs > 1 else (N,), jnp.float32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("nrhs", [1, SERVE_NRHS])
def test_ell_product_compiles(shape, nrhs):
    """The row-padded product is plain XLA: one gather with a reduction
    over the planes and one scatter-add."""
    n, w = HPCG_ROWS, HPCG_WIDTH

    def run(ja, al, ad, x):
        pk = EllPack(n=n, width=w, ja=ja, al=al, au=None,
                     plane_of_slot=None)
        if x.ndim == 2:
            return ell_spmm(pk, ad, x)
        return ell_spmv(pk, ad, x)

    txt = _compiled_text(run, shape((w, n), jnp.int32),
                         shape((w, n), jnp.float32), shape((n,), jnp.float32),
                         shape((n, nrhs) if nrhs > 1 else (n,),
                               jnp.float32))
    assert "scatter" in txt and "gather" in txt


@pytest.mark.parametrize("nrhs", [1, SERVE_NRHS])
def test_ell_halo_shard_fn_compiles(topo, nrhs):
    """The halo strategy's shard function around the row-padded shard
    product, on a one-wide mesh of one v5e chip: its two permutes, the
    gather with its reduction and the one scatter-add."""
    ns, h, w = HPCG_ROWS, HPCG_HALO, HPCG_WIDTH
    mesh = Mesh(np.asarray(topo.devices[:1]), ("rows",),
                axis_types=(AxisType.Auto,))
    lay = EllHalo(p=1, ns=ns, h=h, n_local=ns + h, width=w, ja=None,
                  al=None, au=None, ad=None, plane_of_slot=None)
    local = halo_shard_fn(ell_local_fn(lay, lay.n_local), "rows", 1, h)
    fn = shard_map(local, mesh=mesh, in_specs=(P("rows"),) * 4,
                   out_specs=P("rows"), check_vma=False)

    def arg(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, P("rows")))

    txt = _compiled_text(fn, arg((1, w, ns), jnp.int32),
                         arg((1, w, ns), jnp.float32),
                         arg((1, ns), jnp.float32),
                         arg((ns, nrhs) if nrhs > 1 else (ns,),
                             jnp.float32))
    assert "scatter" in txt and "gather" in txt


def test_assembly_onehot_grid_compiles(shape):
    colors = 27
    lmax = -(-TET_CONTRIBS // colors // 128) * 128

    def run(slots, tgts, kflat):
        return akern.colored_scatter(slots, tgts, kflat, TET_SIZE,
                                     variant="onehot", interpret=False)

    txt = _compiled_text(run, shape((colors, lmax), jnp.int32),
                         shape((colors, lmax), jnp.int32),
                         shape((TET_CONTRIBS // 16, 4, 4), jnp.float32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("strategy", ["colored", "sorted", "private"])
def test_assembly_executors_fit_one_chip_at_113(shape, strategy):
    """Each executor the assembly tuner measures at StocF-1465's size
    compiles for one v5e and leaves room beside the step runner's ring of
    4 element-matrix sets, which set-up holds twice: the contributions are
    read component-major, never through an (ne, 4, 4) copy whose minor
    axis pads to 128 lanes (17.7 GB)."""
    ne, size = FEM113_NE, FEM113_SIZE
    g = ne * 16
    colors = 28
    lmax = -(-(-(-ne // colors)) * 16 // 128) * 128
    ke = shape((ne, 4, 4), jnp.float32)
    i32 = jnp.int32
    fn, args = {
        "colored": (lambda sl, t, k: akern.colored_scatter_fused(
            sl, t, k, size), (shape((colors, lmax), i32),
                              shape((colors, lmax), i32), ke)),
        "sorted": (lambda p, t, k: akern.sorted_scatter(p, t, k, size),
                   (shape((g,), i32), shape((g,), i32), ke)),
        "private": (lambda t, k: akern.private_scatter(t, k, 8, size),
                    (shape((g,), i32), ke)),
    }[strategy]
    m = jax.jit(fn).lower(*args).compile().memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    ring_held_twice = 2 * 4 * g * 4
    assert used + ring_held_twice < 16e9, used
