"""Roofline machinery: trip-count-aware HLO cost rollup + collective parse
(validated against hand-computable modules)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.hlo_cost import analyze_hlo, parse_hlo

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_scan_flops_scale_with_trip_count():
    def make(L):
        def f(w, x):
            def body(x, wi):
                return jnp.tanh(x @ wi), None
            x, _ = jax.lax.scan(body, x, w)
            return x.sum()
        return f

    for L in (2, 8, 24):
        w = jax.ShapeDtypeStruct((L, 128, 128), jnp.float32)
        x = jax.ShapeDtypeStruct((4, 128), jnp.float32)
        c = analyze_hlo(jax.jit(make(L)).lower(w, x).compile().as_text())
        expect = L * 2 * 4 * 128 * 128
        assert abs(c.flops / expect - 1.0) < 0.05, (L, c.flops)


def test_nested_scan_multiplies():
    def f(w, x):
        def outer(x, _):
            def inner(x, wi):
                return x @ wi, None
            x, _ = jax.lax.scan(inner, x, w)
            return x, None
        x, _ = jax.lax.scan(outer, x, None, length=3)
        return x.sum()

    w = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((2, 64), jnp.float32)
    c = analyze_hlo(jax.jit(f).lower(w, x).compile().as_text())
    expect = 3 * 5 * 2 * 2 * 64 * 64
    assert abs(c.flops / expect - 1.0) < 0.05


def test_plain_matmul_flops():
    a = jax.ShapeDtypeStruct((32, 48), jnp.float32)
    b = jax.ShapeDtypeStruct((48, 16), jnp.float32)
    c = analyze_hlo(jax.jit(jnp.dot).lower(a, b).compile().as_text())
    assert abs(c.flops - 2 * 32 * 48 * 16) / (2 * 32 * 48 * 16) < 0.01


@pytest.mark.slow
def test_collectives_counted_in_sharded_module():
    """psum inside a scan over a sharded mesh: collective bytes must be
    multiplied by the trip count (subprocess: needs 8 fake devices)."""
    code = """
        import jax, jax.numpy as jnp, functools
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.core.distributed import make_mesh
        from repro.roofline.hlo_cost import analyze_hlo
        mesh = make_mesh(8, 'd')
        def inner(x):
            def body(c, _):
                return jax.lax.psum(c, 'd'), None
            c, _ = jax.lax.scan(body, x, None, length=10)
            return c
        fn = shard_map(inner, mesh=mesh, in_specs=P(), out_specs=P())
        x = jax.ShapeDtypeStruct((1024,), jnp.float32)
        with mesh:
            txt = jax.jit(fn).lower(x).compile().as_text()
        c = analyze_hlo(txt)
        # 10 iterations x >= 4KB each (any all-reduce impl moves >= payload)
        assert c.collective_bytes >= 10 * 1024 * 4, c.collective_bytes
        assert c.collectives['all-reduce']['count'] >= 10
        print('OK', c.collective_bytes)
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]


def test_model_flops_formula():
    from repro.roofline.analysis import model_flops
    from repro.configs.base import get_config
    from repro.configs.shapes import SHAPES
    cfg = get_config("qwen3-8b")
    n = cfg.param_count()
    assert abs(model_flops(cfg, SHAPES["train_4k"])
               - 6 * n * 4096 * 256) / (6 * n * 4096 * 256) < 1e-6
    moe = get_config("qwen3-moe-235b-a22b")
    assert moe.active_param_count() < 0.15 * moe.param_count()
    # ~235B total / ~22B active (within modelling tolerance)
    assert 1.8e11 < moe.param_count() < 2.6e11
    assert 1.6e10 < moe.active_param_count() < 2.8e10
