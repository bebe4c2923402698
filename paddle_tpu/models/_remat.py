"""Shared activation-checkpoint helper for the model zoo.

TPU-native recompute: under a jax trace (a jitted training step,
``jax.value_and_grad`` over the model — the steady-state path) each
transformer block is wrapped in ``jax.checkpoint``. The backward's residuals
are the block-boundary activation and, by name (``core/residuals.py``):

* for each flash-attention call inside the block, the two arrays its
  backward kernels read and its forward kernel alone can make
  (``KEPT_RESIDUALS``: the output, as large as the block's input, and the
  lane-dense logsumexp, a thirty-second of it at d 64): always;
* the outputs of the block's ``F.linear`` products (``LINEAR_OUT``), while
  the program's byte budget lasts (``KEPT_SHARE``): the backward then reads
  qkv's, the out-projection's and the MLP's first (gate and up) outputs
  where it would run the product a second time. A block's last projection
  is named too and read by nothing in the backward, so nothing saves it. In
  bf16 a block names / keeps 151 / 134 MB at GPT-2 medium's 8 x 1024 rows
  (qkv 50.3, out 16.8, fc1 67.1, fc2 16.8), 235 MB at SmallThinker's 16 384
  (q, k, v, o: 7168 columns), and 268 (a conv operator's in- and
  out-projection), 168 (an attention layer's four) and 537 / 470 MB (a dense
  SwiGLU layer's three) at LFM2's 2 x 8192.

Everything else of the block is rematerialised in the backward: norms,
activations (GELU, the gates), q/k/v splits, rotary, the K/V repeats of
grouped queries, the router's float32 product, the routed layers' loop of
grouped matmuls and ``gated_conv_arrays``; the forward kernel and the kept
products are not run again. What a program's blocks held is on the
``compile.trace`` entry of the program being traced (``remat_kept``, the
start-up record). In eager mode the tape-level ``fleet.recompute`` PyLayer
provides the block-boundary contract
(reference: python/paddle/distributed/fleet/recompute/recompute.py).
"""
from __future__ import annotations

import logging
import math

import jax

from ..core import dispatch, residuals
from ..core.tensor import Tensor


_logged = set()

#: the share of a device's memory (``memory_stats()["bytes_limit"]``) that the
#: projection outputs named in one program's keeping blocks may take together.
#: Chosen from XLA's memory analysis of the four fit steps compiled for a v5e
#: (PR 50; arguments + outputs - aliased + temporaries, the flash pair alone
#: -> every block keeping; shares are of a limit of 16.9 GB, 15.75 GiB):
#: GPT-2 medium at 8 x 1024 rows a chip 6.66 -> 9.82 GB (names 3.62 GB in 24
#: blocks, 21.4 % of the limit: the largest need, one chip and dp4 alike),
#: SmallThinker at 16 384 rows 9.65 -> 9.82 GB (names 0.94), LFM2 at 2 x 8192
#: 12.86 -> 13.83 GB (names 2.58), the tightest at 81.8 %: every block of
#: every cell keeps and every step stays under 90 % of the limit. A quarter
#: is the round share above the largest need; no cell reaches it, so none
#: says what a larger one would cost a program whose blocks name more (it
#: would keep its first blocks' and no more, and grow by at most the share).
KEPT_SHARE = 0.25


def _device_memory():
    """What the first local device states of its memory (None on a CPU)."""
    return jax.local_devices()[0].memory_stats()


def _kept_budget():
    """Bytes of ``LINEAR_OUT`` arrays a program's blocks may keep on one
    device: a share of the capacity the device states, never of what is in
    use while the step is traced (the program, and its compile-cache key,
    are the same in every process on the chip); None where the backend
    states no capacity (a CPU): no limit."""
    limit = (_device_memory() or {}).get("bytes_limit")
    return int(KEPT_SHARE * limit) if limit else None


def _sized(kept, rows):
    """``(name, shape, dtype, bytes on one device)`` of what a block whose
    input has ``rows`` leading rows named: a projection is traced at the
    global batch, which the mesh's data axes cut; the flash forward rules
    see a device's share already."""
    from ..distributed import mesh as mesh_mod
    mesh, axes = mesh_mod.data_axes_dividing(rows)
    cut = math.prod(mesh.shape[a] for a in axes) if axes else 1
    return [(name, shape, dtype, math.prod(shape) * dtype.itemsize
             // (cut if name == residuals.LINEAR_OUT else 1))
            for name, shape, dtype in kept]


def _choose(sized):
    """The names a block's policy holds: the flash pair always, the
    projections' outputs too if this block's fit the program's budget beside
    those of the blocks before it (trace order)."""
    from ..observability import trace
    mine = sum(n for name, *_sd, n in sized if name == residuals.LINEAR_OUT)
    so_far = (trace.compile_noted("remat_kept") or {"bytes": {}})[
        "bytes"].get(residuals.LINEAR_OUT, 0)
    budget = _kept_budget()
    if mine and (budget is None or so_far + mine <= budget):
        return residuals.KEPT_RESIDUALS + (residuals.LINEAR_OUT,)
    return residuals.KEPT_RESIDUALS


def _note_kept(names, kept):
    """``remat_kept`` on the ``compile.trace`` entry of the program being
    traced: the names some block's policy held and, over the program's
    differentiated blocks so far, how many arrays and bytes the policies may
    keep under each (one device's; ``LINEAR_OUT``'s are an upper bound: a
    named output that no backward reads is not saved), ``blocks`` and how
    many of them kept the projections' outputs (``blocks_keeping``); logged
    once a block signature at ``FLAGS_log_level`` 1."""
    from ..core import flags
    from ..observability import trace
    so_far = trace.compile_noted("remat_kept") or {
        "arrays": {}, "bytes": {}, "blocks": 0, "blocks_keeping": 0}
    arrays, nbytes = dict(so_far["arrays"]), dict(so_far["bytes"])
    for name, _shape, _dtype, n in kept:
        arrays[name] = arrays.get(name, 0) + 1
        nbytes[name] = nbytes.get(name, 0) + n
    keeping = so_far["blocks_keeping"] + (residuals.LINEAR_OUT in names)
    trace.compile_note("remat_kept", {
        "names": list(residuals.KEPT_RESIDUALS
                      + (residuals.LINEAR_OUT,) * bool(keeping)),
        "arrays": arrays, "bytes": nbytes, "blocks": so_far["blocks"] + 1,
        "blocks_keeping": keeping})
    signature = tuple((n, s, str(d)) for n, s, d, _n in kept)
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
        def f(*arrs):
            out = blk(*[Tensor(a) for a in arrs])
            return tuple(o._data for o in out) if isinstance(out, tuple) \
                else out._data

        # the policy outlives the trace inside the program's jaxpr: it holds
        # the row count and the log's shapes, no tracer
        rows = datas[0].shape[0]
        names = saving = None

        def policy(*prim_and_avals, **params):
            # JAX asks only when the block is differentiated, and then after
            # it has traced ``f``: the projections it named are known by now
            nonlocal names, saving
            if names is None:
                names = _choose(_sized(kept, rows))
                saving = jax.checkpoint_policies.save_only_these_names(
                    *names)
            return saving(*prim_and_avals, **params)

        with residuals.kept_residuals() as kept:
            out = jax.checkpoint(f, policy=policy)(*datas)
        if names is not None:
            # after the call: the flash forward rules have named theirs too
            _note_kept(names, [k for k in _sized(kept, rows)
                               if k[0] in names])
        if isinstance(out, tuple):
            return tuple(Tensor(o, stop_gradient=False) for o in out)
        return Tensor(out, stop_gradient=False)
    if not dispatch.grad_enabled():
        return blk(*args)
    from ..distributed.fleet.recompute import recompute
    return recompute(blk, *args)
