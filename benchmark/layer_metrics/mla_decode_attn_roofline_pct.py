"""The paged decode-attention kernel's share of its roofline over LATENT
pages (``arch`` ``deepseek_v3``), from the device trace. Over EVERY traced
tick that launched a decode step, whether it also ran prefill chunks or not
(a saturated long-document window holds no decode-only tick; the kernel's
calls belong to the decode step whichever programs ran beside it): the least
time the chip could take for every cached row once a layer at 1152 B a
token, an absorbed query row in and a latent output row out a head a lane
(``lib/flops_deepseek_v3.py``), or for the absorbed operations, whichever
is larger, over the device time of the kernel's calls (``paged_decode_attn``,
one a layer a decode step) inside the same ``bench.step`` spans. None where
the trace holds no such kernel."""
from benchmark.layer_metrics.mla_decode_roofline_pct import (calls_inside,
                                                             traced_ticks)
from benchmark.layer_metrics.paged_decode_attn_roofline_pct import is_kernel
from benchmark.lib import flops, flops_deepseek_v3, peaks


def read(ctx):
    ticks = traced_ticks(ctx)
    if ticks is None:
        return None
    trace = ctx["trace"]
    calls = sorted((s, e) for n, s, e in
                   trace["devices"][sorted(trace["devices"])[0]]["ops"]
                   if is_kernel(n))
    starts = [s for s, _e in calls]
    pk = peaks.peaks_for(ctx["device_kind"])
    cfg = ctx["config"]
    layers = flops_deepseek_v3.counts(cfg)["layers"]
    ideal = spent = 0.0
    for lo, hi, tick in ticks:
        decodes, cached = tick[3], tick[6]
        inside = calls_inside(calls, starts, lo, hi)
        # one call a layer: a span that holds another count straddles two
        # decode steps' kernels and is left out
        if not decodes or len(inside) != layers:
            continue
        ideal += flops.roofline_seconds(
            flops_deepseek_v3.latent_attn_decode_flops(cfg, cached),
            flops_deepseek_v3.latent_attn_decode_bytes(cfg, decodes, cached),
            pk)
        spent += sum(e - s for s, e in inside)
    return 100.0 * ideal / spent if spent else None
