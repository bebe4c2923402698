"""Device milliseconds of one prefill-chunk program
(``jit_paged_prefill_chunk`` on the trace's ``XLA Modules`` line)."""
from benchmark.lib import program_spans


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    return program_spans.module_ms(ctx, "paged_prefill_chunk")
