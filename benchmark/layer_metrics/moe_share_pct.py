"""Share of the decode program's device time spent under the scope
``moe`` (the latent expert blocks: router, routed experts, shared expert)."""
from benchmark.lib import program_spans


def scope_share_pct(ctx, scope: str):
    """Percent of ``jit_paged_decode_step``'s device time under ``scope``,
    or None where the trace holds no such scope."""
    rec = program_spans.recording(ctx) if ctx["kind"] == "serve" else None
    got = rec and program_spans.scope_seconds(rec, (scope,),
                                              "paged_decode_step")
    if not got:
        return None
    under, _calls, program_s = got
    return 100.0 * under / program_s if under else None


def read(ctx):
    return scope_share_pct(ctx, "moe")
