"""Device time of the collective operations per training step, from the
device trace, averaged over the chips: an asynchronous collective counts
from its start to its done (the ``Async XLA Ops`` line), a synchronous one
for its own duration. How much of it no compute hides is another metric."""
from benchmark.lib import trace_reduce

NEEDLES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
           "collective-permute")


def read(ctx):
    trace = ctx.get("trace")
    if ctx["kind"] != "fit" or ctx["chips"] < 2 or not trace:
        return None
    sync = {n: v for n, v in trace["ops"].items()
            if not n.endswith(("-start", "-done"))}
    calls_a, secs_a = trace_reduce.seconds_matching(trace["async_ops"],
                                                    NEEDLES)
    calls_s, secs_s = trace_reduce.seconds_matching(sync, NEEDLES)
    if not calls_a + calls_s:
        return None
    return 1e3 * (secs_a + secs_s) / ctx["traced_steps"]
