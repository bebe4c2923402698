"""Milliseconds a decode-only tick leaves the first chip idle inside
``router.step``: over the traced ticks that ran one decode program and no
prefill chunk, the idle time inside the tick's ``router.step`` span. The
split over the tick's leaf spans is logged beside it. The generator's work
between two ticks is outside every ``router.step`` and not counted."""
from benchmark.lib import harness, program_spans


def read(ctx):
    rec = program_spans.recording(ctx) if ctx["kind"] == "serve" else None
    if rec is None:
        return None
    spans = rec["spans"]
    ticks = [t for t in spans["router.step"]
             if len(program_spans.contained(spans["serving.decode"], t)) == 1
             and not program_spans.contained(spans["serving.prefill"], t)]
    gaps = program_spans.idle_gaps(ctx)
    lo, hi = ctx["trace"]["t_lo"], ctx["trace"]["t_hi"]
    ticks = [t for t in ticks if lo <= t[0] and t[1] <= hi]
    if not ticks:
        return None
    total = program_spans.overlap_seconds(gaps, ticks)
    idle = {}
    for name in program_spans.SPANS:
        if name.startswith(("fit.", "io.", "serving.prefill")):
            continue
        inside = [iv for t in ticks
                  for iv in program_spans.contained(spans[name], t)]
        idle[name] = 1e3 * program_spans.overlap_seconds(
            gaps, program_spans.merged(inside)) / len(ticks)
    # a parent's own work: its idle time less its children's
    split = {name: round(ms - sum(idle[c] for c in idle
                                  if program_spans.SPANS[c] == name), 4)
             for name, ms in idle.items()}
    harness.log(f"tick_host_exposed_ms over {len(ticks)} decode-only ticks, "
                f"idle ms a tick by span (a parent's: its own work): {split}")
    return 1e3 * total / len(ticks)
