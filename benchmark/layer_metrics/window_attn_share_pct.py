"""Share of the decode program's device time spent under the scope
``attn.window`` (the sliding-window layers: projections, per-head norms,
rotary embedding, attention over the rows a lane holds, the output
projection)."""
from benchmark.layer_metrics.moe_share_pct import scope_share_pct


def read(ctx):
    return scope_share_pct(ctx, "attn.window")
