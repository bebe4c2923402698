"""Seconds of backend compile (the persistent cache's read included) of the
program's own programs before the window: the record's ``compile.backend``
entries whose parent is a program phase. What ``compile_s`` set out to be:
that one also counts the reference's compiles."""
from benchmark.lib import startup_record


def read(ctx):
    return startup_record.read(ctx, "compile_s")
