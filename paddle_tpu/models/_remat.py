"""Shared activation-checkpoint helper for the model zoo.

TPU-native recompute: under a jax trace (a jitted training step,
``jax.value_and_grad`` over the model — the steady-state path) each
transformer block is wrapped in ``jax.checkpoint`` so the backward's
residuals are the block-boundary activation and, for each flash-attention
call inside the block, the two arrays its backward kernels read and its
forward kernel alone can make (``flash_attention.KEPT_RESIDUALS``: the
output, as large as the block's input, and the lane-dense logsumexp, a
thirty-second of it at d 64). The interior (norms, projections, the q/k/v
transposes and pads, MLP intermediate) is rematerialized during the
backward pass, the forward kernel is not run again. That trades ~1/3 extra
FLOPs for the activation HBM that otherwise caps model size on a 16 GB
chip. What was kept is on the ``compile.trace`` entry of the program being
traced (``remat_kept``, the start-up record). In eager mode the tape-level
``fleet.recompute`` PyLayer provides the block-boundary contract
(reference: python/paddle/distributed/fleet/recompute/recompute.py).
"""
from __future__ import annotations

import logging
import math

import jax

from ..core import dispatch
from ..core.tensor import Tensor


_logged = set()


def _note_kept(names, kept):
    """``remat_kept`` on the ``compile.trace`` entry of the program being
    traced: the names and, over the program's blocks so far, how many arrays
    and bytes the policy keeps under each (as the forward rules saw them: a
    device's share inside a ``shard_map``); logged once a block signature at
    ``FLAGS_log_level`` 1."""
    from ..core import flags
    from ..observability import trace
    so_far = trace.compile_noted("remat_kept") or {"arrays": {}, "bytes": {}}
    arrays, nbytes = dict(so_far["arrays"]), dict(so_far["bytes"])
    for name, shape, dtype in kept:
        arrays[name] = arrays.get(name, 0) + 1
        nbytes[name] = (nbytes.get(name, 0)
                        + math.prod(shape) * dtype.itemsize)
    trace.compile_note("remat_kept", {"names": list(names), "arrays": arrays,
                                      "bytes": nbytes})
    signature = tuple((n, s, str(d)) for n, s, d in kept)
    if flags.get_flag("log_level") >= 1 and signature not in _logged:
        _logged.add(signature)
        logging.getLogger("paddle_tpu.remat").info(
            "a rematerialised block keeps %s", signature)


def remat_block(blk, *args):
    """Run ``blk(*args)`` (Tensor -> Tensor) with activation checkpointing.

    ``blk`` is typically a Layer; extra Tensor args (e.g. an attention
    mask) ride along and are saved as residuals, not rematerialized. Under
    a trace the block may return a tuple of Tensors (its output and what it
    counted).
    """
    datas = [a._data for a in args]
    if any(isinstance(d, jax.core.Tracer) for d in datas):
        from ..ops.pallas import flash_attention as fa

        def f(*arrs):
            out = blk(*[Tensor(a) for a in arrs])
            return tuple(o._data for o in out) if isinstance(out, tuple) \
                else out._data
        policy = jax.checkpoint_policies.save_only_these_names(
            *fa.KEPT_RESIDUALS)
        with fa.kept_residuals() as kept:
            out = jax.checkpoint(f, policy=policy)(*datas)
        if kept:
            _note_kept(fa.KEPT_RESIDUALS, kept)
        if isinstance(out, tuple):
            return tuple(Tensor(o, stop_gradient=False) for o in out)
        return Tensor(out, stop_gradient=False)
    if not dispatch.grad_enabled():
        return blk(*args)
    from ..distributed.fleet.recompute import recompute
    return recompute(blk, *args)
