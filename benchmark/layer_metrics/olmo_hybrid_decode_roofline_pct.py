"""The Olmo-Hybrid decoder's decode program as a share of its roofline, from
the device trace: over the traced ticks that decoded, the least time the chip
could take for the step's bytes or operations (``lib/flops_olmo_hybrid.py``:
every weight once, the delta-rule state and the convolution's window read
and written once a decoding lane a linear layer, K/V of the cached tokens in
the full layers, the rows written, the head) over the device time of that
tick's ``jit_paged_decode_step`` call on the ``XLA Modules`` line. A call
belongs to the tick whose ``bench.step`` span it starts in."""
import bisect

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
    first = trace["devices"][sorted(trace["devices"])[0]]
    calls = sorted((s, e) for n, s, e in first["modules"]
                   if n.startswith("jit_paged_decode_step("))
    starts = [s for s, _e in calls]
    pk = peaks.peaks_for(ctx["device_kind"])
    cfg = ctx["config"]
    ideal = spent = 0.0
    for (_name, lo, hi), tick in zip(spans, ticks):
        decodes, cached = tick[3], tick[6]
        inside = calls[bisect.bisect_left(starts, lo):
                       bisect.bisect_left(starts, hi)]
        if not decodes or len(inside) != 1:
            continue
        ideal += flops.roofline_seconds(
            flops_olmo_hybrid.decode_step_flops(cfg, decodes, cached),
            flops_olmo_hybrid.decode_step_bytes(cfg, decodes, cached), pk)
        spent += inside[0][1] - inside[0][0]
    return 100.0 * ideal / spent if spent else None
