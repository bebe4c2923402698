"""Share of the decode program's device time spent under the scope
``attn.linear`` (a gated delta-rule linear-attention layer: projections,
convolution, the rule's state update, the output norm and gate)."""
from benchmark.layer_metrics.moe_share_pct import scope_share_pct


def read(ctx):
    return scope_share_pct(ctx, "attn.linear")
