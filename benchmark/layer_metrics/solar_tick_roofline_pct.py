"""The Solar Open 2 decoder's programs as a share of their roofline, from the
device trace: over the traced ticks, every call of the three serving programs
(``jit_paged_prefill_chunk``, ``jit_paged_mixed_step``,
``jit_paged_decode_step`` on the ``XLA Modules`` line), the least time the
chip could take for its bytes or its operations, whichever is larger
(``lib/flops_solar_open2.py``: weights once a program, experts as touched by
the program's rows, the KDA state and windows in and out once a decoding
lane and once for a chunk's slot, K/V of the cached tokens in the one GQA
layer, the rows written, the head's slice, the chunk's products), over the
calls' device time. A call belongs to the tick whose ``bench.step`` span it
starts in; that tick's record gives the step's lanes (``decodes``) and their
cached tokens. What the records do not hold is billed at its least: a chunk
reads no K/V before its own rows (4 KiB a token beside 6.2 GB of weights),
and a step whose tick read no decoded token has no lanes."""
import bisect

from benchmark.lib import flops, flops_solar_open2 as fl, peaks

PROGRAMS = ("jit_paged_prefill_chunk(", "jit_paged_mixed_step(",
            "jit_paged_decode_step(")
#: rows of the engine's widest prefill chunk
CHUNK = 256


def traced_ticks(ctx):
    """``(span lo, span hi, tick record)`` of the traced ticks, or None
    where the run has no trace of a ``solar_open2`` serving program."""
    trace = ctx.get("trace")
    if (ctx["kind"] != "serve" or not trace or not trace.get("devices")
            or ctx["config"].get("arch") != "solar_open2"):
        return None
    tick0 = ctx["window"]["trace_tick0"]
    if tick0 is None:
        return None
    spans = [s for s in trace["host_spans"] if s[0] == "bench.step"]
    return [(lo, hi, tick) for (_name, lo, hi), tick
            in zip(spans, ctx["window"]["ticks"][tick0:])]


def calls_inside(calls, starts, lo, hi):
    return calls[bisect.bisect_left(starts, lo):bisect.bisect_left(starts, hi)]


def chunk_width(cfg: dict) -> int:
    return min(CHUNK, cfg["engine"].get("prefill_token_budget") or CHUNK)


def call_cost(cfg: dict, program: str, lanes: int, cached: int):
    """``(operations, bytes)`` of one call of ``program``."""
    width = chunk_width(cfg)
    if program == PROGRAMS[0]:
        return (fl.prefill_chunk_flops(cfg, width, width),
                fl.prefill_chunk_bytes(cfg, width, width))
    if program == PROGRAMS[1]:
        return (fl.mixed_step_flops(cfg, width, width, lanes, cached),
                fl.mixed_step_bytes(cfg, width, width, lanes, cached))
    return (fl.decode_step_flops(cfg, lanes, cached),
            fl.decode_step_bytes(cfg, lanes, cached))


def read(ctx):
    ticks = traced_ticks(ctx)
    if ticks is None:
        return None
    trace = ctx["trace"]
    first = trace["devices"][sorted(trace["devices"])[0]]
    calls = sorted((s, e, n) for n, s, e in first["modules"]
                   if n.startswith(PROGRAMS))
    starts = [s for s, _e, _n in calls]
    pk = peaks.peaks_for(ctx["device_kind"])
    cfg = ctx["config"]
    ideal = spent = 0.0
    for lo, hi, tick in ticks:
        decodes, cached = tick[3], tick[6]
        for s, e, name in calls_inside(calls, starts, lo, hi):
            program = next(p for p in PROGRAMS if name.startswith(p))
            ideal += flops.roofline_seconds(
                *call_cost(cfg, program, decodes, cached), pk)
            spent += e - s
    return 100.0 * ideal / spent if spent else None
