"""Median time from when a request was due to the start of the first
``router.step()`` in which it holds a slot (generator's clock)."""
from benchmark.lib import stats


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    waits = [r.slot_t - r.due for r in ctx["window"]["records"]
             if r.slot_t is not None]
    return 1e3 * stats.median(waits) if waits else None
