"""Share of the prefill-chunk program's device time spent under the scope
``paged_attention`` (cache write, page gather, scores, softmax, values)."""
from benchmark.lib import program_spans


def read(ctx):
    rec = program_spans.recording(ctx) if ctx["kind"] == "serve" else None
    got = rec and program_spans.scope_seconds(
        rec, ("paged_attention",), "paged_prefill_chunk")
    if not got:
        return None
    under, _calls, program_s = got
    return 100.0 * under / program_s
