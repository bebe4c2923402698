"""Seconds JAX spent in backend compiles (persistent-cache loads included)
from process start to the end of the run; none may fall in the window."""


def read(ctx):
    return ctx.get("setup_compile_s")
