"""Share of the training step's device time spent in the routed expert
layers, forward and backward: operations under the scope ``moe`` (router,
sort and gather, the experts' activation, un-sort) and the grouped-matmul
kernels, which carry no scope (``lib/train_scopes.py``)."""
from benchmark.lib import train_scopes


def read(ctx):
    return train_scopes.share_pct(ctx, ("moe",), (train_scopes.GROUPED,))
