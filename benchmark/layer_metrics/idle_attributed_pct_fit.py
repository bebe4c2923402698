"""Health of the tracing, training cells: the share of the first chip's idle
time in the traced window that lies inside a leaf boundary span of the
program (``program_spans.LEAVES``), so that a gap has an owner. Idle time in
a parent span's own work, or outside every span, is what is missing."""
from benchmark.lib import program_spans


def read(ctx):
    return program_spans.idle_attributed_pct(ctx, "fit")
