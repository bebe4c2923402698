"""Device milliseconds a training step spends under the scopes ``lm_head``
and ``loss``, forward and backward: the logits path."""
from benchmark.lib import program_spans


def read(ctx):
    return program_spans.scope_ms_per_step(ctx, ("lm_head", "loss"))
