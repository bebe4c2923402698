"""Share of the time spent inside ``router.step()`` that went to ticks
which ran a prefill chunk."""
from benchmark.drivers.serve import ran_prefill


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    ticks = ctx["window"]["ticks"]
    total = sum(t[1] - t[0] for t in ticks)
    if not total:
        return None
    return 100.0 * sum(t[1] - t[0] for t in ticks if ran_prefill(t)) / total
