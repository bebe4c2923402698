"""The flash-attention kernel calls of a traced training step BY SCOPE: a
model whose layers attend under different masks (``attn.window`` beside
``attn.full``) runs the same three kernels under each, told apart by the
``jax.named_scope`` in the call's ``op_name`` (``lib/train_scopes.step_ops``
gives the path) and, forward from dQ from dK/dV, by the call's signature
(``layer_metrics/flash_attn_roofline_pct.kernel_kind``)."""
from __future__ import annotations

from benchmark.layer_metrics.flash_attn_roofline_pct import kernel_kind
from benchmark.lib import (flops, flops_smallthinker, peaks, program_spans,
                           trace_reduce, train_scopes)

KINDS = {"fwd", "dq", "dkv"}


def calls_under(ctx, scope: str):
    """``{kind: [seconds of each call]}`` of the flash kernels the traced
    steps ran under ``scope``, or None without a trace of a training step."""
    rec = program_spans.recording(ctx) if ctx["kind"] == "fit" else None
    if rec is None or not rec["scopes"]:
        return None
    ops, (steps, _seconds) = train_scopes.step_ops(rec)
    if not steps:
        return None
    under, out = program_spans._under(scope), {}
    for name, secs, path in ops:
        kind = kernel_kind(trace_reduce.short_name(name))
        if kind is not None and under.search(path):
            out.setdefault(kind, []).append(secs)
    return out


def smallthinker_roofline_pct(ctx, scope: str, window):
    """Ideal time of a ``smallthinker`` cell's flash calls under ``scope``
    (operations over the pairs inside the mask, ``lib/flops_smallthinker``)
    over their device time, in percent; None unless all three kernels ran
    there."""
    if ctx["config"].get("arch") != "smallthinker":
        return None
    calls = calls_under(ctx, scope)
    if not calls or set(calls) != KINDS:
        return None
    cfg = ctx["config"]
    bh = ctx["batch"] // ctx["chips"] * cfg["num_attention_heads"]
    seq, hd = ctx["seq_len"], cfg["head_dim"]
    pk = peaks.peaks_for(ctx["device_kind"])
    ideal = sum(
        len(secs) * flops.roofline_seconds(
            flops_smallthinker.flash_call_flops(kind, bh, seq, hd, window),
            flops_smallthinker.flash_call_bytes(kind, bh, seq, hd), pk)
        for kind, secs in calls.items())
    return 100.0 * ideal / sum(sum(secs) for secs in calls.values())
