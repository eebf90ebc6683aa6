"""Backend-derived settings: the interpret flag, the compile-cache
directory, the one-hot kernels' VMEM window gate, and the Auto-axis mesh
the distributed builders run on."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro import runtime
from repro.core import csrc, distributed as D, paths, tuner
from repro.core.plan import ExecutionPlan, feasible
from repro.kernels import ops
from repro.kernels.csrc_spmv import ONEHOT_MAX_WINDOW


def test_interpret_mode_follows_backend():
    on_tpu = jax.default_backend() == "tpu"
    assert runtime.interpret_mode() is (not on_tpu)
    assert runtime.interpret_mode(True) is True
    assert runtime.interpret_mode(False) is False


def test_compile_cache_uses_the_env_dir_and_sets_nothing(monkeypatch,
                                                         tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(runtime.CACHE_ENV, str(tmp_path))
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_onehot_plans_stop_at_the_vmem_window():
    band = ONEHOT_MAX_WINDOW            # window of tm=128 exceeds the gate
    onehot = ExecutionPlan(path="kernel", tm=128, variant="onehot")
    stream = ExecutionPlan(path="kernel", tm=128, variant="stream")
    assert not feasible(onehot, n=9000, m=9000, bandwidth=band)
    assert feasible(stream, n=9000, m=9000, bandwidth=band)
    assert feasible(onehot, n=9000, m=9000, bandwidth=16)
    M = csrc.fem_band(2400, band, seed=1, fill=0.02)
    plans = tuner.enumerate_plans(tuner.stats_of(M), tms=(128,))
    windowed = [p for p in plans if p.path in ("kernel", "flat")]
    assert windowed and all(p.variant == "stream" for p in windowed)


def test_nnzsplit_onehot_refuses_a_wide_chunk_window():
    n = 16384
    i = np.arange(8, n, 8)
    rows = np.concatenate([np.arange(n), i, i - 1])
    cols = np.concatenate([np.arange(n), i - 1, i])
    vals = np.concatenate([np.full(n, 4.0), -np.ones(2 * i.size)])
    M = csrc.from_coo(rows, cols, vals, n=n, pad_pattern=False)
    base = ExecutionPlan(path="nnzsplit", k_step_sublanes=8, w_cap=8192)
    op = ops.SpmvOperator.from_plan(M, ExecutionPlan(
        **{**base.to_dict(), "variant": "stream"}))
    assert op.pack.r_pad > ONEHOT_MAX_WINDOW
    x = np.ones(n, np.float32)
    np.testing.assert_allclose(np.asarray(op(jnp.asarray(x))),
                               csrc.to_dense(M) @ x)
    with pytest.raises(ValueError, match="one-hot"):
        ops.SpmvOperator.from_plan(M, base)


def test_runs_pallas_names_the_kernel_plans():
    assert paths.runs_pallas(ExecutionPlan(path="kernel", variant="onehot"))
    assert paths.runs_pallas(ExecutionPlan(path="nnzsplit"))
    assert not paths.runs_pallas(ExecutionPlan(path="flat",
                                               variant="stream"))
    assert not paths.runs_pallas(ExecutionPlan(path="segment"))


def test_mesh_axis_is_auto():
    mesh = D.make_mesh(1)
    assert mesh.axis_names == ("rows",)
    assert tuple(mesh.axis_types) == (AxisType.Auto,)


@pytest.mark.parametrize("variant", ["stream", "onehot"])
def test_executor_programs_take_the_pack_as_arguments(variant):
    """The compiled program depends on shapes only: a 4x larger matrix
    lowers to text of the same size (its arrays are arguments, not
    baked-in constants — those would make the executable, and every
    compile, scale with the matrix)."""
    plan = ExecutionPlan(path="kernel", tm=32, variant=variant)
    texts = []
    for n in (512, 2048):
        M = csrc.fem_band(n, 8, seed=3)
        op = ops.SpmvOperator.from_plan(M, plan)
        x = jnp.ones(n, jnp.float32)
        texts.append(op.lower(x).as_text())
        np.testing.assert_allclose(np.asarray(op(x)),
                                   csrc.to_dense(M) @ np.ones(n),
                                   rtol=1e-5, atol=1e-4)
    assert abs(len(texts[1]) - len(texts[0])) < 0.05 * len(texts[0])
