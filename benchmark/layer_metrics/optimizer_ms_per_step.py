"""Device milliseconds a training step spends under the scope ``optimizer``
(the update rule traced into the step). An update the compiler fused into a
weight-gradient matmul is billed to whichever scope the fusion kept."""
from benchmark.lib import program_spans


def read(ctx):
    return program_spans.scope_ms_per_step(ctx, ("optimizer",))
