"""Seconds of backend initialisation (the record's ``startup.backend``): the
program's own bracket where it made the process's first device query, else
from ``enable_jax_cache``'s stamp to the first program entry or compile that
found the backend up (the benchmark's ``require_chips`` brings it up, so here
the bound, which also holds what the caller did between the two marks)."""
from benchmark.lib import startup_record


def read(ctx):
    return startup_record.read(ctx, "backend_s")
