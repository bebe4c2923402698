"""The paged decode-attention kernel's share of its roofline, from the
device trace.

The kernel (``paged_decode_attn``, ``paddle_tpu/ops/pallas/
paged_attention.py``) runs once an attention layer a decode step and is bound
by HBM traffic: the keys and values of every cached token of every decoding
lane once, the queries in and the outputs out. Over the traced decode-only
ticks: the least time the chip could take for that (or for its operations,
whichever is larger) over the device time of the kernel's calls inside the
same ``bench.step`` spans. ``cached`` is the tick record's count of the
tokens the decoding lanes hold, as ``decode_step_roofline_pct`` reads it, so
only live tokens are billed. Ticks map to spans by order. None where the
trace holds no such kernel (a program that attends through the composite).
"""
import bisect

from benchmark.drivers.serve import ran_prefill
from benchmark.lib import flops, peaks

KERNEL = "paged_decode_attn"


def is_kernel(short_name: str) -> bool:
    """``%paged_decode_attn.3 tpu_custom_call/5 (...)``: the compiler names
    the instruction after the kernel's ``name=``."""
    return (short_name.split(" ", 1)[0].lstrip("%").rsplit(".", 1)[0]
            == KERNEL)


def _query_width(cfg: dict) -> int:
    """Heads x head size: the elements of one lane's query (and output)."""
    hd = cfg.get("head_dim") or (cfg["hidden_size"]
                                 // cfg["num_attention_heads"])
    return cfg["num_attention_heads"] * hd


def step_bytes(cfg: dict, lanes: int, cached_tokens: int,
               itemsize: int = 2) -> float:
    """Least HBM traffic of a decode step's kernel calls, one a layer: K
    and V of every cached token once, a query row in and an output row out
    for every head of every decoding lane."""
    rows = 2 * lanes * _query_width(cfg) * itemsize
    return (cached_tokens * flops.kv_bytes_per_token(cfg, itemsize)
            + cfg["num_hidden_layers"] * rows)


def step_flops(cfg: dict, cached_tokens: int) -> float:
    """QK^T and PV over the cached tokens, every layer."""
    return (2.0 * 2 * _query_width(cfg) * cfg["num_hidden_layers"]
            * cached_tokens)


def read(ctx):
    trace = ctx.get("trace")
    if (ctx["kind"] != "serve" or not trace or not trace.get("devices")
            or ctx["config"].get("arch") != "llama_like"):
        return None
    tick0 = ctx["window"]["trace_tick0"]
    if tick0 is None:
        return None
    ticks = ctx["window"]["ticks"][tick0:]
    spans = [s for s in trace["host_spans"] if s[0] == "bench.step"]
    calls = sorted((s, e) for n, s, e in
                   trace["devices"][sorted(trace["devices"])[0]]["ops"]
                   if is_kernel(n))
    starts = [s for s, _e in calls]
    pk = peaks.peaks_for(ctx["device_kind"])
    cfg = ctx["config"]
    ideal = spent = 0.0
    for (_name, lo, hi), tick in zip(spans, ticks):
        decodes, cached = tick[3], tick[6]
        if ran_prefill(tick) or not decodes:
            continue
        inside = calls[bisect.bisect_left(starts, lo):
                       bisect.bisect_left(starts, hi)]
        if not inside:
            continue
        ideal += flops.roofline_seconds(
            step_flops(cfg, cached), step_bytes(cfg, decodes, cached), pk)
        spent += sum(e - s for s, e in inside)
    return 100.0 * ideal / spent if spent else None
