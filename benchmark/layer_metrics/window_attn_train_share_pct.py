"""Share of the training step's device time spent in the WINDOW attention
layers, forward and backward: operations under the scope ``attn.window``
(projections, rotary embedding, the K/V repeat, the flash kernels)."""
from benchmark.lib import train_scopes


def read(ctx):
    return train_scopes.share_pct(ctx, ("attn.window",))
