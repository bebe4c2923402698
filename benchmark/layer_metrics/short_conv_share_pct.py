"""Share of the training step's device time spent under the scope
``short_conv`` (LFM2's gated short convolution: both projections, the gates
and the taps), forward and backward."""
from benchmark.lib import train_scopes


def read(ctx):
    return train_scopes.share_pct(ctx, ("short_conv",))
