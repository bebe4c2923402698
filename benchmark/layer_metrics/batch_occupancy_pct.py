"""Mean share of the engine's slots that held a request at the start of a
tick (``PagedEngine.num_active`` over ``max_batch``)."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    ticks = ctx["window"]["ticks"]
    if not ticks or ctx["window"]["trace_tick0"] is None:
        return None             # slots are only read in an observed run
    return 100.0 * sum(t[5] for t in ticks) / (len(ticks) * ctx["max_batch"])
