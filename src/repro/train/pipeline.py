"""GPipe-style pipeline parallelism over a mesh axis.

Layers are split into S contiguous stages along a `stage` mesh axis; M
microbatches stream through with ppermute activation handoff.  Each tick
every stage runs its layer block on its current microbatch — the schedule
fills in S-1 ticks, runs M+S-1 ticks total (bubble fraction
(S-1)/(M+S-1)), exactly GPipe.

SPMD formulation: all stages execute one program under shard_map; stage
identity comes from jax.lax.axis_index.  Stage 0 injects microbatch t at
tick t; the last stage emits microbatch t at tick t+S-1; a psum over the
stage axis (outputs are zero-masked elsewhere) collects results.

This composes with the data/model axes (pipeline over `pod`, FSDP/TP
inside a stage) — at 512+ chips PP over pods avoids cross-DCI all-reduce
of weights.  Correctness is subprocess-tested on 8 placeholder devices
(tests/test_pipeline.py); the same code lowers on the production mesh.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(layer_fn: Callable, stacked_params, x_microbatches,
                   mesh: Mesh, axis: str = "stage"):
    """Run a stack of layers as a pipeline over ``axis``.

    layer_fn(params_i, x) -> x       one layer
    stacked_params: pytree with leading axis L (total layers); L must be
      divisible by the stage count S = mesh.shape[axis].
    x_microbatches: (M, ...) microbatch-major activations.
    Returns (M, ...) outputs, identical to applying all L layers serially
    to each microbatch.
    """
    s = dict(mesh.shape)[axis]
    m = x_microbatches.shape[0]
    leaves = jax.tree.leaves(stacked_params)
    l_total = leaves[0].shape[0]
    assert l_total % s == 0, f"{l_total} layers not divisible by {s} stages"

    # reshape params to (S, L/S, ...) and shard the stage axis
    staged = jax.tree.map(
        lambda a: a.reshape((s, l_total // s) + a.shape[1:]),
        stacked_params)

    def stage_program(params_local, xs):
        # params_local: (1, L/S, ...) this stage's block; xs: (M, ...) full
        stage_id = jax.lax.axis_index(axis)
        params_local = jax.tree.map(lambda a: a[0], params_local)

        def run_block(x):
            def body(x, p):
                return layer_fn(p, x), None
            x, _ = jax.lax.scan(body, x, params_local)
            return x

        ticks = m + s - 1
        zero = jnp.zeros_like(xs[0])

        def tick(carry, t):
            held, outputs = carry
            # stage 0 injects microbatch t (if in range); others use held
            inject = jnp.where(t < m, t, m - 1)
            x_in = jnp.where(stage_id == 0, xs[inject], held)
            active = (t - stage_id >= 0) & (t - stage_id < m)
            y = run_block(x_in)
            y = jnp.where(active, y, zero)
            # pass to the right neighbor (stage i -> i+1)
            passed = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % s) for i in range(s)])
            # last stage emits microbatch t-(s-1) at tick t; masked add
            # (each microbatch is emitted exactly once) keeps the body
            # branch-free for shard_map's varying-axis typing
            emit_idx = t - (s - 1)
            do_emit = (stage_id == s - 1) & (emit_idx >= 0) & (emit_idx < m)
            outputs = outputs.at[jnp.clip(emit_idx, 0, m - 1)].add(
                jnp.where(do_emit, y, 0.0))
            return (passed, outputs), None

        outputs0 = jnp.zeros((m,) + xs.shape[1:], xs.dtype)
        # carries become stage-varying inside the body; mark the initials
        init = jax.lax.pcast((zero, outputs0), (axis,), to="varying")
        (_, outputs), _ = jax.lax.scan(tick, init, jnp.arange(ticks))
        # collect: outputs live on the last stage only
        return jax.lax.psum(jnp.where(stage_id == s - 1, outputs, 0.0),
                            axis)

    in_specs = (jax.tree.map(lambda _: P(axis), staged), P())
    fn = shard_map(stage_program, mesh=mesh, in_specs=in_specs,
                   out_specs=P())
    return fn(staged, x_microbatches)


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    """GPipe bubble overhead: (S-1)/(M+S-1)."""
    return (num_stages - 1) / (num_microbatches + num_stages - 1)
