"""Model FLOP/s utilisation: operations the forward and backward passes
need per token (benchmark/lib/flops.py; recomputation not billed) x tokens/s
over chips x the chip's published bf16 peak."""
from benchmark.lib import flops, peaks


def read(ctx):
    if ctx["kind"] != "fit":
        return None
    per_token = flops.gpt2_train_flops_per_token(ctx["config"], ctx["seq_len"])
    peak = peaks.peaks_for(ctx["device_kind"])["bf16_flops"] * ctx["chips"]
    return 100.0 * per_token * ctx["tokens"] / ctx["window_s"] / peak
