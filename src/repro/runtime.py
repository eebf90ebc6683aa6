"""How programs are compiled: facts derived from the backend instead of
taken as options, and the compile helpers every executor shares.

  interpret_mode   whether Pallas kernels run in the interpreter: only
                   when the default backend is not a TPU (the CPU test
                   and development host).  Entry points take
                   ``interpret=None`` and resolve it here, so on a TPU no
                   normal path reaches the interpreter.
  enable_compile_cache
                   the persistent XLA compilation cache: the directory
                   ``JAX_COMPILATION_CACHE_DIR`` names when it is set
                   (JAX reads it itself, nothing is set in code), else a
                   fixed ``<checkout>/.jax_cache`` — a stable path, since
                   the path is part of what a later run must find again.
  jit_hoisted      ``jax.jit`` for executors that close over large
                   arrays: the arrays become program arguments, not
                   constants baked into the executable.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax
from jax.extend.core import ClosedJaxpr, jaxpr_as_fun

CHECKOUT = Path(__file__).resolve().parents[2]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """The Pallas ``interpret`` flag: an explicit bool wins (tests force
    the interpreter); ``None`` means interpret everywhere but on a TPU."""
    return (not on_tpu()) if interpret is None else bool(interpret)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def jit_hoisted(fn):
    """``jax.jit(fn)`` with the arrays ``fn`` closes over passed to the
    program as arguments.

    Executors close over their packs — hundreds of MB at 10**6 rows.
    Baked in as constants they make every compile slow and every
    executable that large, too large for the persistent compilation
    cache; as arguments the program depends only on shapes.  ``fn`` takes
    one array; it is traced once per input shape and dtype, and the
    closed-over arrays are put on the device once.  The returned callable
    has ``lower(x)`` for inspecting the program it runs.
    """
    programs = {}

    def program(x):
        key = (tuple(x.shape), str(x.dtype))
        if key not in programs:
            closed = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct(x.shape,
                                                             x.dtype))
            run = jax.jit(lambda consts, v: jaxpr_as_fun(
                ClosedJaxpr(closed.jaxpr, consts))(v)[0])
            programs[key] = (run, jax.device_put(list(closed.consts)))
        return programs[key]

    def call(x):
        run, consts = program(x)
        return run(consts, x)

    def lower(x):
        run, consts = program(x)
        return run.lower(consts, x)

    call.lower = lower
    return call
