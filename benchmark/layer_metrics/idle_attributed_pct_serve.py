"""Health of the tracing, serving cells: the share of the first chip's idle
time in the traced window that lies inside a leaf boundary span of the
program (``program_spans.LEAVES``). The generator's own work between two
``router.step`` calls is idle time no span of the program can own."""
from benchmark.lib import program_spans


def read(ctx):
    return program_spans.idle_attributed_pct(ctx, "serve")
