"""Expert load imbalance over the window, from the engine's own counters
(``PagedEngine.expert_load`` read before and after the window by the hybrid
serving driver): per expert layer the busiest held expert's tokens over the
mean, and of the layers the worst. 1.0 is perfectly even."""


def read(ctx):
    load = (ctx.get("window") or {}).get("expert_load")
    if not load:
        return None
    worst = None
    for tokens in load:
        mean = sum(tokens) / len(tokens)
        if mean > 0:
            worst = max(worst or 0.0, max(tokens) / mean)
    return worst
