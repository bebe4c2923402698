"""Share of the serving programs' device time spent under the scope
``attn.linear`` (a Kimi delta attention layer: projections, the three
convolutions, the rule, the output norm and gate), over ALL three programs
(``jit_paged_prefill_chunk``, ``jit_paged_mixed_step``,
``jit_paged_decode_step``) in the traced window: this architecture's cell
fills every tick with chunks, so a reader of the decode program alone
(``linear_attn_share_pct``) reads the one tick in 32 whose step goes out
apart."""
from benchmark.lib import program_spans

PROGRAMS = ("paged_prefill_chunk", "paged_mixed_step", "paged_decode_step")


def read(ctx):
    if (ctx["kind"] != "serve"
            or ctx["config"].get("arch") != "solar_open2"):
        return None
    rec = program_spans.recording(ctx)
    if rec is None:
        return None
    under = total = 0.0
    for program in PROGRAMS:
        got = program_spans.scope_seconds(rec, ("attn.linear",), program)
        if got:
            under += got[0]
            total += got[2]
    return 100.0 * under / total if under else None
