"""Median wall time of ``router.step()`` on decode-only ticks (no prefill
chunk ran, at least one token decoded), generator's clock."""
from benchmark.drivers.serve import ran_prefill
from benchmark.lib import stats


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    spans = [t[1] - t[0] for t in ctx["window"]["ticks"]
             if not ran_prefill(t) and t[3]]
    return 1e3 * stats.median(spans) if spans else None
