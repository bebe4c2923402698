"""Seconds from the OS's start of the process to the window that no entry of
the start-up record names: the caller's own time (the benchmark's plan, its
weights and, on the fit cells, its reference) plus whatever the record lacks.
The split is logged beside it, with the longest stretches no entry covers."""
from benchmark.lib import startup_record


def read(ctx):
    return startup_record.read(ctx, "unattributed_s")
