"""Share of the decode program's device time spent under the scope
``attn.full`` (a full-attention layer: projections, per-head norms, the
paged decode-attention kernel over every cached token, the output
projection)."""
from benchmark.layer_metrics.moe_share_pct import scope_share_pct


def read(ctx):
    return scope_share_pct(ctx, "attn.full")
