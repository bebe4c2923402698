"""Model FLOP/s utilisation of an ``lfm2_moe`` training cell: operations the
forward and backward passes need per token (benchmark/lib/flops_lfm2_moe.py:
causal attention, recomputation not billed, the routed experts billed for the
(token, expert) pairs that LANDED on the experts held here) x tokens/s over
chips x the chip's published bf16 peak. The landed pairs are the step's own
counter's over the window, as the tokens and the seconds are (a traced run
carries it). Without the counter nothing is read: the router of a one-chip
share drifts towards the held experts within a window, and the even split
that fresh weights route to would bill fewer pairs than the step
multiplied."""
from benchmark.lib import flops_lfm2_moe, peaks


def read(ctx):
    if ctx["kind"] != "fit" or ctx["config"].get("arch") != "lfm2_moe":
        return None
    cfg, load = ctx["config"], ctx.get("expert_load")
    if not load:
        return None
    even = (flops_lfm2_moe.pairs_landed_per_token(cfg) * ctx["tokens"]
            * len(load))
    landed = sum(row[-2] for row in load)
    needed = (flops_lfm2_moe.train_flops_per_token(cfg, ctx["seq_len"])
              * ctx["tokens"]
              + 3.0 * (landed - even) * flops_lfm2_moe.expert_flops(cfg))
    peak = peaks.peaks_for(ctx["device_kind"])["bf16_flops"] * ctx["chips"]
    return 100.0 * needed / ctx["window_s"] / peak
