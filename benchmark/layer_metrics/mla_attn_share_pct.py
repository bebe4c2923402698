"""Share of the decode program's device time spent under the scope
``attn.mla`` (a latent-attention layer: the query and the compressed-row
projections, the norm and the rotary embedding, the absorb and un-absorb
products, the paged decode-attention kernel over every cached row, the
output projection)."""
from benchmark.layer_metrics.moe_share_pct import scope_share_pct


def read(ctx):
    return scope_share_pct(ctx, "attn.mla")
