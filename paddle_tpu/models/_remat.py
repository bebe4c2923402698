"""Shared activation-checkpoint helper for the model zoo.

TPU-native recompute: under a jax trace (a jitted training step,
``jax.value_and_grad`` over the model — the steady-state path) each
transformer block is wrapped in ``jax.checkpoint`` so only the
block-boundary activation is a backward residual; the interior
(attention scores, MLP intermediate) is rematerialized during the
backward pass. That trades ~1/3 extra FLOPs for the activation HBM that
otherwise caps model size on a 16 GB chip. In eager mode the tape-level
``fleet.recompute`` PyLayer provides the same contract (reference:
python/paddle/distributed/fleet/recompute/recompute.py).
"""
from __future__ import annotations

import jax

from ..core import dispatch
from ..core.tensor import Tensor


def remat_block(blk, *args):
    """Run ``blk(*args)`` (Tensor -> Tensor) with activation checkpointing.

    ``blk`` is typically a Layer; extra Tensor args (e.g. an attention
    mask) ride along and are saved as residuals, not rematerialized. Under
    a trace the block may return a tuple of Tensors (its output and what it
    counted).
    """
    datas = [a._data for a in args]
    if any(isinstance(d, jax.core.Tracer) for d in datas):
        def f(*arrs):
            out = blk(*[Tensor(a) for a in arrs])
            return tuple(o._data for o in out) if isinstance(out, tuple) \
                else out._data
        out = jax.checkpoint(f)(*datas)
        if isinstance(out, tuple):
            return tuple(Tensor(o, stop_gradient=False) for o in out)
        return Tensor(out, stop_gradient=False)
    if not dispatch.grad_enabled():
        return blk(*args)
    from ..distributed.fleet.recompute import recompute
    return recompute(blk, *args)
