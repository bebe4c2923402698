"""The DeepSeek-V3 decoder's decode program as a share of its roofline, from
the device trace: over the traced ticks that decoded, the least time the
chip could take for the step's bytes or operations
(``lib/flops_deepseek_v3.py``: weights once, experts billed as touched, 1152
B a cached token a layer whatever the program pads a row to, the written
rows, the head's slice; attention over cached rows in the absorbed form)
over the device time of that tick's ``jit_paged_decode_step`` call on the
``XLA Modules`` line. A call belongs to the tick whose ``bench.step`` span
it starts in."""
import bisect

from benchmark.lib import flops, flops_deepseek_v3, peaks


def traced_ticks(ctx):
    """``(span lo, span hi, tick record)`` of the traced ticks, or None
    where the run has no trace of a ``deepseek_v3`` serving program."""
    trace = ctx.get("trace")
    if (ctx["kind"] != "serve" or not trace or not trace.get("devices")
            or ctx["config"].get("arch") != "deepseek_v3"):
        return None
    tick0 = ctx["window"]["trace_tick0"]
    if tick0 is None:
        return None
    spans = [s for s in trace["host_spans"] if s[0] == "bench.step"]
    return [(lo, hi, tick) for (_name, lo, hi), tick
            in zip(spans, ctx["window"]["ticks"][tick0:])]


def calls_inside(calls, starts, lo, hi):
    return calls[bisect.bisect_left(starts, lo):bisect.bisect_left(starts, hi)]


def read(ctx):
    ticks = traced_ticks(ctx)
    if ticks is None:
        return None
    trace = ctx["trace"]
    first = trace["devices"][sorted(trace["devices"])[0]]
    calls = sorted((s, e) for n, s, e in first["modules"]
                   if n.startswith("jit_paged_decode_step("))
    starts = [s for s, _e in calls]
    pk = peaks.peaks_for(ctx["device_kind"])
    cfg = ctx["config"]
    ideal = spent = 0.0
    for lo, hi, tick in ticks:
        decodes, cached = tick[3], tick[6]
        inside = calls_inside(calls, starts, lo, hi)
        if not decodes or len(inside) != 1:
            continue
        ideal += flops.roofline_seconds(
            flops_deepseek_v3.decode_step_flops(cfg, decodes, cached),
            flops_deepseek_v3.decode_step_bytes(cfg, decodes, cached), pk)
        spent += inside[0][1] - inside[0][0]
    return 100.0 * ideal / spent if spent else None
