"""End-to-end behaviour tests for the paper's system: the full CSRC stack
(build → pack → kernel → accumulate → solver) and the dry-run cell driver
on a small mesh."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import csrc, solvers
from repro.kernels import ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_end_to_end_fem_solve():
    """The paper's target workload: assemble a FEM-like system, solve with
    PCG where every matrix-vector product runs the CSRC Pallas kernel."""
    M = csrc.poisson2d(24)                      # 576-dof Laplacian
    op = ops.SpmvOperator(M, path="kernel", tm=16)
    rng = np.random.default_rng(0)
    x_true = rng.standard_normal(M.n).astype(np.float32)
    b = op(jnp.asarray(x_true))                 # rhs via the same operator
    res = solvers.cg(op, b, tol=1e-6, maxiter=3000, diag=M.ad)
    assert bool(res.converged)
    assert np.abs(np.asarray(res.x) - x_true).max() < 1e-3
    # working-set bookkeeping matches the paper's accounting
    assert op.flops_per_call == 2 * M.nnz - M.n
    assert op.bytes_per_call > 0


def test_paper_bandwidth_claim():
    """Paper §4.1: CSRC loads ≈ (5/2)nnz - n/2 vs CSR 3nnz → ratio < 1.
    Check our streamed-bytes accounting reproduces the direction."""
    M = csrc.fem_band(2048, 64, seed=0)
    csr_loads = 3 * M.nnz
    csrc_loads = 5 * M.nnz // 2 - M.n // 2
    assert csrc_loads < csr_loads
    # numerically symmetric halves the value stream further
    Ms = csrc.fem_band(2048, 64, seed=0, numeric_symmetric=True)
    from repro.core import blockell
    p_ns = blockell.pack(M, tm=64)
    p_s = blockell.pack(Ms, tm=64)
    assert p_s.streamed_bytes() < p_ns.streamed_bytes()


@pytest.mark.slow
def test_dryrun_cell_on_test_mesh():
    """The launch driver lowers+compiles a real cell on a small placeholder
    mesh (subprocess: 8 fake devices) — the same path the 512-chip run
    uses."""
    code = """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.launch.dryrun import lower_cell
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rec = lower_cell("qwen1.5-0.5b", "train_4k", mesh, "4x2",
                         verbose=False)
        assert rec["status"] == "ok", rec
        r = rec["roofline"]
        assert r["hlo_flops"] > 0 and r["collective_bytes"] > 0
        print("OK", r["bottleneck"])
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]


def test_all_cells_have_records_or_skips():
    """After the full dry-run sweep, every (arch × shape × mesh) cell must
    have a record: ok or a documented skip.  Runs only when results exist
    (the sweep is executed by `python -m repro.launch.dryrun`)."""
    outdir = os.path.join(ROOT, "results", "dryrun")
    if not os.path.isdir(outdir) or len(os.listdir(outdir)) < 80:
        pytest.skip("full dry-run sweep not yet executed")
    import json
    from repro.configs.base import registry
    from repro.configs.shapes import SHAPES
    bad = []
    for arch in registry():
        for shape in SHAPES:
            for mesh in ("16x16", "2x16x16"):
                p = os.path.join(outdir, f"{arch}__{shape}__{mesh}.json")
                if not os.path.exists(p):
                    bad.append((arch, shape, mesh, "missing"))
                    continue
                rec = json.load(open(p))
                if rec["status"] not in ("ok", "skipped"):
                    bad.append((arch, shape, mesh, rec["status"]))
    assert not bad, bad
