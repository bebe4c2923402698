"""The delta rule's decode step as a share of its roofline, from the device
trace (``arch`` ``olmo_hybrid``).

Over the traced decode-only ticks (one ``jit_paged_decode_step`` call, no
chunk): the least time the chip could take to move each decoding lane's
matrix state in and out ONCE a linear layer, with its ``q`` / ``k`` / ``v``
rows in and its ``o`` row out (``lib/flops_olmo_hybrid.py``
``rule_decode_bytes``), or for the rule's operations, whichever is larger,
over the device time of the operations under scope ``attn.linear.rule``
inside that call: the kernel's (``delta_rule_step``), or whatever fusions
XLA makes of the plain form, and the q / k normalisation and layout work
around it. A tick's lanes are its record's ``decodes``. Ticks map to
``bench.step`` spans by order. None where the program names no such scope
(the parent of the PR that added it)."""
import bisect

from benchmark.drivers.serve import ran_prefill
from benchmark.lib import flops, flops_olmo_hybrid, peaks, program_spans

SCOPE = "attn.linear.rule"


def read(ctx):
    trace = ctx.get("trace")
    if (ctx["kind"] != "serve" or not trace or not trace.get("devices")
            or ctx["config"].get("arch") != "olmo_hybrid"):
        return None
    tick0 = ctx["window"]["trace_tick0"]
    rec = program_spans.recording(ctx)
    if tick0 is None or rec is None or not rec["scopes"]:
        return None
    ticks = ctx["window"]["ticks"][tick0:]
    spans = [s for s in trace["host_spans"] if s[0] == "bench.step"]
    calls = sorted((s, e) for n, s, e in rec["modules"]
                   if n.startswith("jit_paged_decode_step("))
    starts = [s for s, _e in calls]
    under = program_spans._under(SCOPE)
    ruled = sorted(
        (s, e) for name, s, e in rec["ops"]
        if under.search(rec["scopes"].get(name, "").rsplit(":", 1)[0]))
    rule_starts = [s for s, _e in ruled]
    pk = peaks.peaks_for(ctx["device_kind"])
    cfg = ctx["config"]
    layers = flops_olmo_hybrid.counts(cfg)[flops_olmo_hybrid.LINEAR]
    ideal = spent = 0.0
    for (_name, lo, hi), tick in zip(spans, ticks):
        decodes = tick[3]
        inside = calls[bisect.bisect_left(starts, lo):
                       bisect.bisect_left(starts, hi)]
        if ran_prefill(tick) or not decodes or len(inside) != 1:
            continue
        c_lo, c_hi = inside[0]
        mine = ruled[bisect.bisect_left(rule_starts, c_lo):
                     bisect.bisect_left(rule_starts, c_hi)]
        if not mine:
            continue
        ideal += layers * flops.roofline_seconds(
            flops_olmo_hybrid.rule_decode_flops(cfg, decodes),
            flops_olmo_hybrid.rule_decode_bytes(cfg, decodes), pk)
        spent += sum(e - s for s, e in mine)
    return 100.0 * ideal / spent if spent else None
