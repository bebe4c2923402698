"""Milliseconds a training step leaves the first chip idle while the host is
inside ``fit.dispatch`` (the call of the compiled step): idle time of the
traced window inside those spans over the steps dispatched in it."""
from benchmark.lib import program_spans


def read(ctx):
    return program_spans.idle_ms_per_step(ctx, ("fit.dispatch",))
