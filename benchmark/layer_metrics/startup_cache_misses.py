"""Programs of the program's own, compiled before the window, that JAX's
persistent compilation cache did not hold (``compile.backend`` entries with
``cache: miss`` whose parent is a program phase). 0 on a warm cache."""
from benchmark.lib import startup_record


def read(ctx):
    return startup_record.read(ctx, "cache_misses")
