"""Seconds from process start to the end of set-up: imports, data
generation, plan and schedule loading or building, compilation or
compile-cache loading, and warm-up."""


def read(ctx):
    return ctx.setup_s
