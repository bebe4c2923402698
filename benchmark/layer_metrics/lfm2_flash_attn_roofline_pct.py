"""The flash-attention kernels' share of their roofline in an ``lfm2_moe``
training cell, from the device trace: ``flash_attn_roofline_pct``'s reading
(the same call signatures, the same operations and bytes of
benchmark/lib/flops.py against the published peaks, over the summed device
time of the calls) with the heads and the head width taken from the published
keys of this architecture: ``num_attention_heads`` heads of ``hidden_size /
num_attention_heads``.

The model repeats each K/V head to the query heads it serves before the call,
so a call is billed as the kernel gets it: ``batch x num_attention_heads``
sequences, K and V read at that many heads. The operations are those of the
published head width (64), whatever width the kernel pads its tiles to."""
from benchmark.layer_metrics.flash_attn_roofline_pct import kernel_kind
from benchmark.lib import flops, peaks, train_scopes


def read(ctx):
    trace = ctx.get("trace")
    if (ctx["kind"] != "fit" or ctx["config"].get("arch") != "lfm2_moe"
            or not trace or not trace.get("ops")):
        return None
    cfg = ctx["config"]
    heads = cfg["num_attention_heads"]
    bh, hd = ctx["batch"] // ctx["chips"] * heads, cfg["hidden_size"] // heads
    pk = peaks.peaks_for(ctx["device_kind"])
    ideal = spent = 0.0
    seen = set()
    for name, (calls, secs) in trace["ops"].items():
        kind = kernel_kind(name)
        if kind is None or name.startswith("%" + train_scopes.GROUPED):
            continue
        seen.add(kind)
        ideal += calls * flops.roofline_seconds(
            flops.flash_call_flops(kind, bh, ctx["seq_len"], hd),
            flops.flash_call_bytes(kind, bh, ctx["seq_len"], hd), pk)
        spent += secs
    if seen != {"fwd", "dq", "dkv"}:
        return None
    return 100.0 * ideal / spent
