"""Device milliseconds of one decode step with a prefill chunk aboard
(``jit_paged_mixed_step`` on the trace's ``XLA Modules`` line): the tick's
last chunk and the decode step as one program. None where the traced
window holds no such call (the parent of the PR that added the program
launches none)."""
from benchmark.lib import program_spans


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    return program_spans.module_ms(ctx, "paged_mixed_step")
