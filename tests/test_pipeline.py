"""Pipeline parallelism: GPipe schedule correctness on placeholder devices."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bubble_fraction():
    from repro.train.pipeline import bubble_fraction
    assert bubble_fraction(1, 8) == 0.0
    assert abs(bubble_fraction(4, 8) - 3 / 11) < 1e-9
    assert bubble_fraction(4, 32) < bubble_fraction(4, 8)


@pytest.mark.slow
def test_pipeline_matches_sequential():
    code = """
        import numpy as np, jax, jax.numpy as jnp
        from repro.train.pipeline import pipeline_apply
        mesh = jax.make_mesh((4,), ('stage',),
                             axis_types=(jax.sharding.AxisType.Auto,))
        L, D, M, B = 8, 16, 6, 3
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (L, D, D)) * (D ** -0.5)
        params = {'w': w}
        def layer_fn(p, x):
            return jnp.tanh(x @ p['w'])
        xs = jax.random.normal(jax.random.PRNGKey(1), (M, B, D))
        out = pipeline_apply(layer_fn, params, xs, mesh, 'stage')
        # sequential reference
        ref = xs
        for i in range(L):
            ref = jnp.tanh(ref @ w[i])
        err = float(jnp.abs(out - ref).max())
        assert err < 1e-5, err
        print('OK', err)
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]


@pytest.mark.slow
def test_pipeline_collectives_are_permutes():
    """The handoff must lower to collective-permute (point-to-point), not
    all-gather — that is the PP communication advantage."""
    code = """
        import jax, jax.numpy as jnp
        from repro.train.pipeline import pipeline_apply
        from repro.roofline.hlo_cost import analyze_hlo
        mesh = jax.make_mesh((4,), ('stage',),
                             axis_types=(jax.sharding.AxisType.Auto,))
        L, D, M, B = 8, 16, 6, 3
        w = jax.random.normal(jax.random.PRNGKey(0), (L, D, D))
        def layer_fn(p, x): return jnp.tanh(x @ p['w'])
        xs = jax.ShapeDtypeStruct((M, B, D), jnp.float32)
        ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
        with mesh:
            txt = jax.jit(lambda w_, x_: pipeline_apply(
                layer_fn, {'w': w_}, x_, mesh, 'stage')).lower(
                ws, xs).compile().as_text()
        c = analyze_hlo(txt)
        assert c.collectives['collective-permute']['count'] > 0
        print('OK', {k: v['count'] for k, v in c.collectives.items()
                     if isinstance(v, dict)})
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
