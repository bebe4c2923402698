"""Expert load imbalance over the window's training steps, from the step's
own device counter (``observability.trace.STEP_COUNTERS`` ``moe.expert_load``,
summed inside the compiled step and read by ``Engine.fit`` with each epoch's
loss): per routed layer the busiest held expert's tokens over the mean, and
of the layers the worst. 1.0 is perfectly even."""


def read(ctx):
    load = ctx.get("expert_load")
    if not load:
        return None
    worst = None
    for row in load:
        tokens = row[:-2]
        mean = sum(tokens) / len(tokens)
        if mean > 0:
            worst = max(worst or 0.0, max(tokens) / mean)
    return worst
