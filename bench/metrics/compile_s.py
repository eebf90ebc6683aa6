"""Seconds JAX spent compiling or loading compiled programs from the
persistent cache during set-up (``/jax/core/compile/backend_compile_duration``)."""


def read(ctx):
    return ctx.setup_compile["compile_s"]
