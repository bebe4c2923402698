"""What one ``Engine.fit`` call costs beyond its steps: the call's wall time
minus steps x the median step time (median over the epochs' own spans)."""
import statistics


def read(ctx):
    if ctx["kind"] != "fit":
        return None
    edges = ctx["epoch_starts"] + [ctx["t_end"]]
    per_step = [(b - a) / ctx["steps_per_epoch"]
                for a, b in zip(edges, edges[1:])]
    steps = ctx["epochs"] * ctx["steps_per_epoch"]
    return ctx["fit_call_s"] - steps * statistics.median(per_step)
