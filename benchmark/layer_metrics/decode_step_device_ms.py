"""Device milliseconds of one decode program (``jit_paged_decode_step`` on
the trace's ``XLA Modules`` line)."""
from benchmark.lib import program_spans


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    return program_spans.module_ms(ctx, "paged_decode_step")
