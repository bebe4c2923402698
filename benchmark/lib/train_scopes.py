"""Device time of a traced training step by scope AND by kernel name.

``program_spans.scope_seconds`` bills an operation to the ``jax.named_scope``
its ``op_name`` carries. A grouped matmul (``jax.lax.ragged_dot``) carries
none: XLA replaces it by a Mosaic kernel of its own whose instruction and
``op_name`` are ``ragged-dot-<mode>`` (and a small ``ragged-dot-metadata``
before it) whatever scope it was traced under. So the readers of an expert
layer's time name both: the scopes, and the instruction-name prefixes that
belong to the layer."""
from __future__ import annotations

import bisect
import re

from benchmark.lib import program_spans

PROGRAM = "engine_train_step"
#: instruction-name prefix of XLA's grouped-matmul kernels, metadata included
GROUPED = "ragged-dot"
_GROUPED_CALL = re.compile(
    r"^%ragged-dot-(?!metadata)[\w-]*?(?:\.\d+)? = (\w+)\[([\d,]+)\]")


def step_ops(rec):
    """``[(instruction text, seconds, op_name path)]`` of the operations the
    first chip ran inside calls of the train step, and ``(calls, seconds)``
    of the program itself."""
    calls = program_spans.merged(
        [(s, e) for n, s, e in rec["modules"]
         if n.startswith(f"jit_{PROGRAM}(")])
    starts = [c[0] for c in calls]
    out = []
    for name, s, e in rec["ops"]:
        k = bisect.bisect_right(starts, s) - 1
        if k < 0 or s >= calls[k][1]:
            continue
        path = rec["scopes"].get(name, "").rsplit(":", 1)[0]
        out.append((name, e - s, path))
    return out, (len(calls), sum(e - s for s, e in calls))


def seconds(ctx, scopes=(), kernels=()):
    """``(seconds under a scope or in a named kernel, calls of the step, the
    step's seconds)`` over the traced window, or None without a trace of a
    training step."""
    rec = program_spans.recording(ctx) if ctx["kind"] == "fit" else None
    if rec is None or not rec["scopes"]:
        return None
    ops, (calls, program_s) = step_ops(rec)
    if not calls:
        return None
    patterns = [program_spans._under(s) for s in scopes]
    under = sum(
        secs for name, secs, path in ops
        if any(p.search(path) for p in patterns)
        or any(name.startswith("%" + k) for k in kernels))
    return under, calls, program_s


def share_pct(ctx, scopes=(), kernels=()):
    got = seconds(ctx, scopes, kernels)
    if not got or not got[0]:
        return None
    return 100.0 * got[0] / got[2]


def grouped_calls(ctx):
    """``[(result shape, seconds)]`` of every grouped-matmul kernel call in
    the traced steps (the metadata kernels left out), or None."""
    rec = program_spans.recording(ctx) if ctx["kind"] == "fit" else None
    if rec is None:
        return None
    ops, _program = step_ops(rec)
    out = []
    for name, secs, _path in ops:
        m = _GROUPED_CALL.match(name)
        if m:
            out.append((tuple(int(d) for d in m.group(2).split(",")), secs))
    return out or None
