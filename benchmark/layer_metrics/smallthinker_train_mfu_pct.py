"""Model FLOP/s utilisation of a ``smallthinker`` training cell: operations
the forward and backward passes need per token (``lib/flops_smallthinker``:
each layer's attention over the pairs inside ITS mask, recomputation not
billed, the routed experts billed for the (token, expert) pairs that LANDED
on the experts held here) x tokens/s over chips x the chip's published bf16
peak. The landed pairs are the step's own counter's over the window, as the
tokens and the seconds are (a traced run carries it); without the counter
nothing is read."""
from benchmark.lib import flops_smallthinker, peaks


def read(ctx):
    if ctx["kind"] != "fit" or ctx["config"].get("arch") != "smallthinker":
        return None
    cfg, load = ctx["config"], ctx.get("expert_load")
    if not load:
        return None
    even = (flops_smallthinker.pairs_landed_per_token(cfg) * ctx["tokens"]
            * len(load))
    landed = sum(row[-2] for row in load)
    needed = (flops_smallthinker.train_flops_per_token(cfg, ctx["seq_len"])
              * ctx["tokens"]
              + 3.0 * (landed - even) * flops_smallthinker.expert_flops(cfg))
    peak = peaks.peaks_for(ctx["device_kind"])["bf16_flops"] * ctx["chips"]
    return 100.0 * needed / ctx["window_s"] / peak
