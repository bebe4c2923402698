"""The paged decode-attention kernel's share of its roofline in a decoder
whose full-attention layers have as many K/V heads as query heads (``arch``
``olmo_hybrid``: 30 and 30, a group of one), from the device trace:
``paged_decode_attn_roofline_pct``'s reading with this architecture's
counts. Over the traced decode-only ticks: the least time the chip could
take for K and V of every cached token once a full layer (15 360 B a token
a layer at the published 30 heads; the pages hold 32, and what the program
pads to is not billed), a query row in and an output row out a lane
(``lib/flops_olmo_hybrid.py``), or for the operations, whichever is larger,
over the device time of the kernel's calls (``paged_decode_attn``, one a
full layer a decode step) inside the same ``bench.step`` spans. None where
the trace holds no such kernel."""
import bisect

from benchmark.drivers.serve import ran_prefill
from benchmark.layer_metrics.paged_decode_attn_roofline_pct import is_kernel
from benchmark.lib import flops, flops_olmo_hybrid, peaks


def read(ctx):
    trace = ctx.get("trace")
    if (ctx["kind"] != "serve" or not trace or not trace.get("devices")
            or ctx["config"].get("arch") != "olmo_hybrid"):
        return None
    tick0 = ctx["window"]["trace_tick0"]
    if tick0 is None:
        return None
    ticks = ctx["window"]["ticks"][tick0:]
    spans = [s for s in trace["host_spans"] if s[0] == "bench.step"]
    calls = sorted((s, e) for n, s, e in
                   trace["devices"][sorted(trace["devices"])[0]]["ops"]
                   if is_kernel(n))
    starts = [s for s, _e in calls]
    pk = peaks.peaks_for(ctx["device_kind"])
    cfg = ctx["config"]
    ideal = spent = 0.0
    for (_name, lo, hi), tick in zip(spans, ticks):
        decodes, cached = tick[3], tick[6]
        if ran_prefill(tick) or not decodes:
            continue
        inside = calls[bisect.bisect_left(starts, lo):
                       bisect.bisect_left(starts, hi)]
        if not inside:
            continue
        ideal += flops.roofline_seconds(
            flops_olmo_hybrid.full_attn_decode_flops(cfg, cached),
            flops_olmo_hybrid.full_attn_decode_bytes(cfg, decodes, cached),
            pk)
        spent += sum(e - s for s, e in inside)
    return 100.0 * ideal / spent if spent else None
