"""The decode program's share of its roofline, from the device trace.

A decode step is bound by HBM traffic: every layer's weights and the head
once, and the keys and values of every cached token of every active slot once
(benchmark/lib/flops.py). Over the traced decode-only ticks: the least time
the chip could take for those bytes (or operations, whichever is larger)
over the device time the trace shows inside the same ``bench.step`` spans.
Ticks map to spans by order: the k-th span is the k-th tick after the trace
began."""
import bisect

from benchmark.drivers.serve import ran_prefill
from benchmark.lib import flops, peaks, trace_reduce


def read(ctx):
    trace = ctx.get("trace")
    if ctx["kind"] != "serve" or not trace or not trace.get("devices"):
        return None
    tick0 = ctx["window"]["trace_tick0"]
    ticks = ctx["window"]["ticks"][tick0:]
    spans = [s for s in trace["host_spans"] if s[0] == "bench.step"]
    ops = sorted((s, e) for _n, s, e in
                 trace["devices"][sorted(trace["devices"])[0]]["ops"])
    starts = [s for s, _e in ops]
    pk = peaks.peaks_for(ctx["device_kind"])
    ideal = spent = 0.0
    for (_name, lo, hi), tick in zip(spans, ticks):
        decodes, cached = tick[3], tick[6]
        if ran_prefill(tick) or not decodes:
            continue
        first = bisect.bisect_left(starts, lo)
        last = bisect.bisect_right(starts, hi)
        busy = trace_reduce.union_seconds(
            [(s, min(e, hi)) for s, e in ops[first:last]])
        if busy <= 0:
            continue
        ideal += flops.roofline_seconds(
            flops.decode_step_flops(ctx["config"], decodes, cached),
            flops.decode_step_bytes(ctx["config"], cached), pk)
        spent += busy
    return 100.0 * ideal / spent if spent else None
