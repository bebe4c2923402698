"""Share of the decode program's device time spent under the scope
``mamba`` (the Mamba-2 blocks: projections, convolution, state update,
gated norm)."""
from benchmark.layer_metrics.moe_share_pct import scope_share_pct


def read(ctx):
    return scope_share_pct(ctx, "mamba")
