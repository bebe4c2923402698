"""The flash-attention kernels' share of their roofline, from the device
trace: the least time the chip could take for the calls the trace shows
(operations and bytes from benchmark/lib/flops.py against the published
peaks) over the summed device time of those calls.

The three Pallas kernels of ``ops/pallas/flash_attention.py`` carry no name
of their own in the trace (the instruction is named after the scope it was
traced under: ``jvp``, ``checkpoint``, ``rematted_computation``), so a call
is told by its signature as ``trace_reduce.short_name`` keeps it: forward
takes q k v and gives (out bf16, lse f32); dQ takes six operands and gives
one bf16 array; dK/dV takes six and gives two."""
import re

from benchmark.lib import flops, peaks

_CALL = re.compile(r" tpu_custom_call/(\d+) (.*)$")


def kernel_kind(short_name: str):
    m = _CALL.search(short_name)
    if not m:
        return None
    n_in, shape = int(m.group(1)), m.group(2)
    results = shape.strip("()").split("],")
    if n_in == 3 and len(results) == 2 and results[1].startswith("f32"):
        return "fwd"
    if n_in == 6 and len(results) == 1:
        return "dq"
    if n_in == 6 and len(results) == 2:
        return "dkv"
    return None


def read(ctx):
    trace = ctx.get("trace")
    if ctx["kind"] != "fit" or not trace or not trace["ops"]:
        return None
    cfg = ctx["config"]
    bh = ctx["batch"] // ctx["chips"] * cfg["n_head"]
    hd = cfg["n_embd"] // cfg["n_head"]
    pk = peaks.peaks_for(ctx["device_kind"])
    ideal = spent = 0.0
    seen = set()
    for name, (calls, secs) in trace["ops"].items():
        kind = kernel_kind(name)
        if kind is None:
            continue
        seen.add(kind)
        ideal += calls * flops.roofline_seconds(
            flops.flash_call_flops(kind, bh, ctx["seq_len"], hd),
            flops.flash_call_bytes(kind, bh, ctx["seq_len"], hd), pk)
        spent += secs
    if seen != {"fwd", "dq", "dkv"}:
        return None
    return 100.0 * ideal / spent
