"""Milliseconds a training step leaves the first chip idle while the host
waits for a batch (``fit.next_batch``), does the step's bookkeeping
(``fit.post_step``) or reads the epoch's loss (``fit.epoch_sync``)."""
from benchmark.lib import program_spans


def read(ctx):
    return program_spans.idle_ms_per_step(
        ctx, ("fit.next_batch", "fit.post_step", "fit.epoch_sync"))
