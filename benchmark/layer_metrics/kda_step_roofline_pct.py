"""The Kimi-delta step kernel's share of its roofline, from the device trace
(``arch`` ``solar_open2``). Over EVERY traced tick that launched a decode
step, whether a prefill chunk rode it or not (a saturated window's steps
nearly all carry a chunk; the kernel's calls belong to the decode step
whichever rows ran beside it): the least time the chip could take to move
each decoding lane's matrix state in and out ONCE a KDA layer, with its ``q``
/ ``k`` / ``v`` / decay rows in and its ``o`` row out
(``lib/flops_solar_open2.py`` ``rule_decode_bytes``), or for the rule's
operations, whichever is larger, over the device time of the kernel's calls
(``delta_rule_step``, by instruction name, one a KDA layer a step) inside the
same ``bench.step`` spans. The kernel visits every slot's tile, idle or not;
only the lanes that decoded are billed. None where the trace holds no such
kernel."""
from benchmark.layer_metrics.solar_tick_roofline_pct import (calls_inside,
                                                             traced_ticks)
from benchmark.lib import flops, flops_solar_open2 as fl, peaks, program_spans

KERNEL = "delta_rule_step"


def read(ctx):
    ticks = traced_ticks(ctx)
    if ticks is None:
        return None
    trace = ctx["trace"]
    calls = sorted((s, e) for n, s, e in
                   trace["devices"][sorted(trace["devices"])[0]]["ops"]
                   if program_spans.kernel_name(n.split(" ", 1)[0]) == KERNEL)
    starts = [s for s, _e in calls]
    pk = peaks.peaks_for(ctx["device_kind"])
    cfg = ctx["config"]
    layers = fl.counts(cfg)["kda"]
    ideal = spent = 0.0
    for lo, hi, tick in ticks:
        decodes = tick[3]
        inside = calls_inside(calls, starts, lo, hi)
        # one call a layer: a span that holds another count straddles two
        # steps' kernels and is left out
        if not decodes or len(inside) != layers:
            continue
        ideal += layers * flops.roofline_seconds(
            fl.rule_decode_flops(cfg, decodes),
            fl.rule_decode_bytes(cfg, decodes), pk)
        spent += sum(e - s for s, e in inside)
    return 100.0 * ideal / spent if spent else None
