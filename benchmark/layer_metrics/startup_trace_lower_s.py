"""Seconds the program's own programs spent being traced and lowered before
the window: the union of the record's ``compile.trace`` and ``compile.lower``
entries whose parent is a program phase. The reference's and the weight
maker's programs run under no program phase and are left out."""
from benchmark.lib import startup_record


def read(ctx):
    return startup_record.read(ctx, "trace_lower_s")
