"""Pipeline schedules beyond the stage-major FThenB/1F1B scan: interleaved
virtual-pipeline (VPP), zero-bubble ZBH1, and heterogeneous-stage rings.

Reference contracts:
* interleaved VPP — reference
  python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py:1010
  (``PipelineParallelWithInterleave``) and pp_layers.py:207
  (``PipelineLayerChunk``): each rank owns K *non-contiguous* chunks
  (block-major round-robin), shrinking the pipeline bubble from
  ``(S-1)/(m+S-1)`` of the run to ``~(S-1)/(mK+S-1)`` — a K-fold
  reduction in idle ticks.
* ZBH1 — reference
  distributed/passes/pipeline_scheduler_pass/pipeline_zero_bubble.py:
  split each block's backward into dX (activation grad, on the ring's
  critical path) and dW (weight grad, bubble filler). TPU-native form: a
  ``jax.custom_vjp`` whose backward ring computes ONLY the dX chain
  (ppermute critical path carries no weight-grad FLOPs) and then runs all
  dW work as one bulk collective-free phase XLA can schedule into the
  drain.
* heterogeneous stages — reference pipeline_parallel.py segments arbitrary
  layer stacks per stage. TPU-native form: per-stage parameter packs are
  flattened into one padded buffer sharded over ``pp``; activations ride a
  flat ring buffer sized for the largest inter-stage tensor; each rank
  dispatches its own stage's program with ``lax.switch`` on its ring
  index, so unequal stages still pipeline inside ONE compiled SPMD
  program.

All three schedules keep the exact-numerics contract: outputs and
gradients match the sequential model up to float reassociation.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...shard_map_compat import shard_map as _shard_map


def schedule_block_ticks(schedule: str, m: int, S: int, K: int) -> int:
    """Total per-rank block-unit ticks the compiled schedule executes.

    One block-unit tick = one pipeline-block application. FThenB/1F1B run
    ``(m + S - 1)`` stage ticks of ``K`` blocks each; interleaved VPP runs
    ``ceil(m/S) * S * K + S - 1`` single-block ticks. For ``K > 1`` (and
    ``m >= S``) VPP is strictly fewer — the bubble shrinks by ``~K``.
    """
    sched = schedule.upper()
    if sched in ("VPP", "INTERLEAVE", "INTERLEAVED"):
        groups = math.ceil(m / S)
        return groups * S * K + S - 1
    return (m + S - 1) * K


# --------------------------------------------------------------------------
# Interleaved VPP
# --------------------------------------------------------------------------

def spmd_pipeline_interleaved(block_fn: Callable, stacked: Sequence, xs, *,
                              mesh, num_stages: int, remat: bool = True,
                              return_stats: bool = False):
    """Interleaved virtual-pipeline schedule over the ``pp`` mesh axis.

    Layout is block-major: rank ``r`` owns blocks ``r, S+r, …, (K-1)S+r``
    (K chunks). An in-flight activation circles the ring K times, carrying
    its chunk index; rank 0 injects micro-batches in groups of S whenever
    its ring slot frees (every ``S*K`` ticks), giving
    ``ceil(m/S)*S*K + S - 1`` total single-block ticks versus the
    stage-major schedule's ``(m + S - 1) * K``.

    ``stacked`` — arrays ``[S*K, …]`` in block order; ``xs`` — ``[m, …]``
    micro-batches. Returns ``[m, …]`` outputs replicated over pp; with
    ``return_stats`` also a dict whose ``active_block_ticks`` /
    ``total_block_slots`` the compiled program itself counts — the
    measured bubble fraction is ``1 - active/total``.
    """
    S = num_stages
    m = xs.shape[0]
    L = stacked[0].shape[0]
    K = L // S
    assert K * S == L, (L, S)
    if remat:
        block_fn = jax.checkpoint(block_fn)

    # [L, ...] -> [K, S, ...] -> [S, K, ...]: chunked[r][c] = block c*S + r
    chunked = [a.reshape((K, S) + a.shape[1:]).swapaxes(0, 1)
               for a in stacked]
    perm = [(i, (i + 1) % S) for i in range(S)]
    T = schedule_block_ticks("VPP", m, S, K)
    # scalar ride-along needs chunk (< K+1) and mb (< m, plus -1) exact
    # in the activation dtype's integer range
    xdt = jnp.dtype(xs.dtype)
    exact = {jnp.dtype(jnp.float32): 1 << 24,
             jnp.dtype(jnp.bfloat16): 1 << 8,
             jnp.dtype(jnp.float16): 1 << 11}.get(xdt, 0)
    pack_scalars = max(m, K + 1) < exact

    def body(chunked_local, xs):
        local = [a[0] for a in chunked_local]  # [K, ...] per param
        idx = jax.lax.axis_index("pp")

        state = jnp.zeros(xs.shape[1:], xs.dtype)
        chunk = jnp.int32(0)
        mb = jnp.int32(-1)          # micro-batch in this slot; -1 = idle
        out = jnp.zeros_like(xs)
        n_active = jnp.int32(0)

        def tick(carry, t):
            state, chunk, mb, out, n_active = carry
            # rank-0 injection: groups of S micro-batches every S*K ticks
            tm = t % (S * K)
            mb_new = (t // (S * K)) * S + tm
            do_inject = jnp.logical_and(tm < S, mb_new < m)
            inject_now = jnp.logical_and(idx == 0, do_inject)
            x_inj = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(mb_new, 0, m - 1), 0, keepdims=False)
            x_in = jnp.where(inject_now, x_inj, state)
            chunk = jnp.where(inject_now, jnp.int32(0), chunk)
            mb = jnp.where(inject_now, mb_new.astype(jnp.int32), mb)
            active = mb >= 0
            n_active = n_active + active.astype(jnp.int32)

            # chunk selection via lax.switch over STATIC slices — a dynamic
            # gather here would fuse into the block matmul as a strided
            # read and wreck MXU/GEMM efficiency.
            y = jax.lax.switch(
                jnp.clip(chunk, 0, K - 1),
                [partial(lambda c, x: block_fn([a[c] for a in local], x), c)
                 for c in range(K)],
                x_in)
            y = jnp.where(active, y, x_in)

            # completed micro-batch leaves at rank S-1, last chunk
            done = jnp.logical_and(
                idx == S - 1, jnp.logical_and(active, chunk == K - 1))
            wpos = jnp.clip(mb, 0, m - 1)
            old = jax.lax.dynamic_index_in_dim(out, wpos, 0, keepdims=False)
            out = jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(done, y, old), wpos, 0)

            nxt_chunk = jnp.where(idx == S - 1, chunk + 1, chunk)
            nxt_mb = jnp.where(done, jnp.int32(-1), mb)
            if pack_scalars:
                # ONE collective per tick: the two int scalars ride in
                # two extra elements of the activation buffer (exactness
                # guarded at schedule build; measured ~20% per-tick
                # saving on the CPU mesh, where each collective is a
                # full cross-device rendezvous)
                ring = jnp.concatenate([
                    y.reshape(-1),
                    jnp.stack([nxt_chunk, nxt_mb]).astype(y.dtype)])
                ring = jax.lax.ppermute(ring, "pp", perm)
                state = ring[:-2].reshape(y.shape)
                chunk = ring[-2].astype(jnp.int32)
                mb = ring[-1].astype(jnp.int32)
            else:
                state, chunk, mb = jax.lax.ppermute(
                    (y, nxt_chunk, nxt_mb), "pp", perm)
            return (state, chunk, mb, out, n_active), None

        (_, _, _, out, n_active), _ = jax.lax.scan(
            tick, (state, chunk, mb, out, n_active), jnp.arange(T))
        out = jax.lax.psum(
            jnp.where(idx == S - 1, out, jnp.zeros_like(out)), "pp")
        return out, jax.lax.psum(n_active, "pp")

    out, n_active = _shard_map(
        body, mesh=mesh,
        in_specs=([P("pp")] * len(chunked), P()),
        out_specs=(P(), P()),
        axis_names=frozenset({"pp"}), check=False)(chunked, xs)
    if return_stats:
        return out, {"active_block_ticks": n_active,
                     "total_block_slots": T * S}
    return out


# --------------------------------------------------------------------------
# ZBH1: zero-bubble dX/dW split
# --------------------------------------------------------------------------

def spmd_pipeline_zb(block_fn: Callable, stacked: Sequence, xs, *,
                     mesh, num_stages: int):
    """Stage-major ring with a zero-bubble (ZBH1-style) custom backward.

    Forward is the FThenB/1F1B tick scan. The custom VJP's backward runs a
    *reverse* ring that per tick computes only ``dX`` (the activation
    cotangent the inverse ppermute must carry on), recording
    ``(x_in, dy)`` pairs; all ``dW`` contributions are then computed in a
    single collective-free accumulation phase. The dX ring is the critical
    path; the dW phase has no ppermutes, so XLA schedules it as bubble
    filler — the program-level analogue of ZBH1's B/W split.
    """
    S = num_stages
    m = xs.shape[0]
    L = stacked[0].shape[0]
    K = L // S
    assert K * S == L, (L, S)

    staged = [a.reshape((S, K) + a.shape[1:]) for a in stacked]
    perm = [(i, (i + 1) % S) for i in range(S)]
    inv_perm = [(j, i) for i, j in perm]
    T = m + S - 1

    def stage_fn(local, x):
        def blk(h, per_block):
            return block_fn(per_block, h), None
        h, _ = jax.lax.scan(blk, x, local)
        return h

    def fwd_scan(local, xs):
        idx = jax.lax.axis_index("pp")
        state = jnp.zeros(xs.shape[1:], xs.dtype)
        out = jnp.zeros_like(xs)

        def tick(carry, t):
            state, out = carry
            inject = jax.lax.dynamic_index_in_dim(
                xs, jnp.minimum(t, m - 1), 0, keepdims=False)
            x_in = jnp.where(idx == 0, inject, state)
            y = stage_fn(local, x_in)
            wpos = jnp.clip(t - (S - 1), 0, m - 1)
            old = jax.lax.dynamic_index_in_dim(out, wpos, 0, keepdims=False)
            newval = jnp.where(
                jnp.logical_and(idx == S - 1, t >= S - 1), y, old)
            out = jax.lax.dynamic_update_index_in_dim(out, newval, wpos, 0)
            state = jax.lax.ppermute(y, "pp", perm)
            return (state, out), x_in

        (_, out), x_buf = jax.lax.scan(
            tick, (state, out), jnp.arange(T))
        return out, x_buf

    def body(staged_local, xs):
        local_outer = [a[0] for a in staged_local]

        # The custom_vjp is purely per-shard (its only collectives are the
        # ring ppermutes, whose transposes we write ourselves); the final
        # cross-rank psum stays OUTSIDE so shard_map's own transpose
        # handles the replicated-output cotangent convention.
        @jax.custom_vjp
        def pipe(local, xs):
            out, _ = fwd_scan(local, xs)
            idx = jax.lax.axis_index("pp")
            return jnp.where(idx == S - 1, out, jnp.zeros_like(out))

        def pipe_fwd(local, xs):
            out, x_buf = fwd_scan(local, xs)
            idx = jax.lax.axis_index("pp")
            return (jnp.where(idx == S - 1, out, jnp.zeros_like(out)),
                    (local, xs, x_buf))

        def pipe_bwd(res, g):
            local, xs, x_buf = res
            idx = jax.lax.axis_index("pp")
            d_xs = jnp.zeros_like(xs)

            # ---- dX ring: reverse ticks, activation cotangents only.
            def btick(carry, t):
                d_state, d_xs = carry
                wpos = jnp.clip(t - (S - 1), 0, m - 1)
                write_cond = jnp.logical_and(idx == S - 1, t >= S - 1)
                g_t = jax.lax.dynamic_index_in_dim(
                    g, wpos, 0, keepdims=False)
                dy = jax.lax.ppermute(d_state, "pp", inv_perm)
                dy = dy + jnp.where(write_cond, g_t, jnp.zeros_like(g_t))
                x_t = jax.lax.dynamic_index_in_dim(
                    x_buf, t, 0, keepdims=False)
                # dX only: weights are closed over, so the transpose here
                # computes no weight cotangent — the ZBH1 critical path.
                _, vjp_x = jax.vjp(lambda x: stage_fn(local, x), x_t)
                (dx,) = vjp_x(dy)
                d_state = jnp.where(idx == 0, jnp.zeros_like(dx), dx)
                inj = jnp.minimum(t, m - 1)
                old = jax.lax.dynamic_index_in_dim(
                    d_xs, inj, 0, keepdims=False)
                d_xs = jax.lax.dynamic_update_index_in_dim(
                    d_xs, old + jnp.where(idx == 0, dx, jnp.zeros_like(dx)),
                    inj, 0)
                return (d_state, d_xs), dy

            (_, d_xs), dy_buf = jax.lax.scan(
                btick, (jnp.zeros(xs.shape[1:], xs.dtype), d_xs),
                jnp.arange(T), reverse=True)

            # ---- dW filler: one collective-free accumulation pass.
            def wtick(acc, xd):
                x_t, dy_t = xd
                _, vjp_w = jax.vjp(lambda w: stage_fn(w, x_t), local)
                (dw,) = vjp_w(dy_t)
                return jax.tree.map(jnp.add, acc, dw), None

            d_local, _ = jax.lax.scan(
                wtick, jax.tree.map(jnp.zeros_like, local),
                (x_buf, dy_buf))
            # d_xs stays per-shard (only rank 0 accumulated): shard_map's
            # transpose of the replicated xs input psums shard cotangents
            return d_local, d_xs

        pipe.defvjp(pipe_fwd, pipe_bwd)
        out_local = pipe(local_outer, xs)
        return jax.lax.psum(out_local, "pp")

    out = _shard_map(
        lambda st, xs: body(st, xs), mesh=mesh,
        in_specs=([P("pp")] * len(staged), P()),
        out_specs=P(),
        axis_names=frozenset({"pp"}), check=False)(staged, xs)
    return out


# --------------------------------------------------------------------------
# Heterogeneous stages: flat ring buffer + per-rank lax.switch
# --------------------------------------------------------------------------

def _buffer_dtype(dtypes):
    """Narrowest float buffer that round-trips every entry EXACTLY:
    all-bf16 (or all-f16) stages ride a same-width ring — half the
    ppermute bytes and per-rank buffer HBM of an fp32 ring; any f32 (or
    integer) entry widens to f32 (bf16<->f32 casts are exact, integers
    are exact up to 2**24)."""
    floats = {np.dtype(d) for d in dtypes
              if np.issubdtype(np.dtype(d), np.floating)
              or np.dtype(d) == np.dtype("bfloat16")}
    non_floats = {np.dtype(d) for d in dtypes} - floats
    if not non_floats and len(floats) == 1:
        return jnp.dtype(next(iter(floats)))
    return jnp.float32


def _pad_tail(vec, size):
    """Right-pad a 1-D vector with zeros to ``size`` via concatenate —
    NOT jnp.pad: on the current jax/XLA lineage a pad op (even
    zero-width) feeding a manual shard_map region on a multi-axis mesh
    makes the SPMD partitioner mis-assign the region's inputs, silently
    corrupting the pipeline (reproduced in tests/test_pipeline_schedules
    on the dp×pp virtual mesh; concatenate partitions correctly)."""
    if size <= vec.shape[0]:
        return vec
    return jnp.concatenate(
        [vec, jnp.zeros((size - vec.shape[0],), vec.dtype)])


def _flatten_pack(arrays, size, buf_dtype=jnp.float32):
    flat = (jnp.concatenate([jnp.ravel(a).astype(buf_dtype)
                             for a in arrays])
            if arrays else jnp.zeros((0,), buf_dtype))
    return _pad_tail(flat, size)

def _unpack(flat, shapes, dtypes):
    outs, off = [], 0
    for shp, dt in zip(shapes, dtypes):
        n = int(np.prod(shp)) if shp else 1
        outs.append(flat[off:off + n].reshape(shp).astype(dt))
        off += n
    return outs


def spmd_pipeline_hetero(stage_fns: List[Callable],
                         stage_params: List[Sequence], xs, *,
                         mesh, num_stages: int, out_aval,
                         stage_in_avals, remat: bool = True):
    """Pipeline ``S`` *unequal* stages inside one SPMD program.

    ``stage_fns[s](params_s, x_s) -> y_s`` with arbitrary per-stage
    parameter pytrees and inter-stage activation shapes. Parameters are
    packed into one padded buffer sharded over ``pp``; activations ride a
    flat ring buffer sized for the largest inter-stage tensor; rank ``r``
    runs branch ``r`` of a ``lax.switch``. Buffers take the NARROWEST
    float dtype that round-trips every entry exactly (``_buffer_dtype``):
    an all-bf16 model pays bf16 bytes per element — not a 4-byte fp32
    slot — in both per-rank param HBM and ppermute ring bandwidth; any
    f32 entry widens the buffer to f32 (bf16<->f32 is exact either way,
    so per-stage dtypes always round-trip bit-exactly). One SPMD program
    means one rectangular array per input, so each rank's buffer is
    padded to the LARGEST stage's byte need — per-rank memory is bounded
    by max-stage, not sum-of-stages (replication) nor exactly own-stage
    (which would need per-rank shapes, i.e. MPMD).
    ``stage_in_avals[s]`` is the activation aval entering stage ``s``
    (``stage_in_avals[0]`` = micro-batch aval); ``out_aval`` is the final
    stage's output aval.
    """
    S = num_stages
    m = xs.shape[0]
    assert len(stage_fns) == S == len(stage_params)

    p_shapes = [[tuple(p.shape) for p in ps] for ps in stage_params]
    p_dtypes = [[p.dtype for p in ps] for ps in stage_params]
    p_sizes = [sum(int(np.prod(s)) if s else 1 for s in shp)
               for shp in p_shapes]
    Pmax = max(p_sizes + [1])
    param_dtype = _buffer_dtype(
        [d for ds in p_dtypes for d in ds] or [jnp.float32])
    packed = jnp.stack([_flatten_pack(ps, Pmax, param_dtype)
                        for ps in stage_params])

    act_avals = list(stage_in_avals) + [out_aval]
    act_sizes = [int(np.prod(a.shape)) for a in act_avals]
    Amax = max(act_sizes)
    out_size = act_sizes[-1]
    act_dtype = _buffer_dtype([a.dtype for a in act_avals])
    if remat:
        stage_fns = [jax.checkpoint(f) for f in stage_fns]

    def _branch(s):
        fn = stage_fns[s]
        in_aval = act_avals[s]

        def run(flat_params, flat_x):
            params = _unpack(flat_params, p_shapes[s], p_dtypes[s])
            n_in = act_sizes[s]
            x = flat_x[:n_in].reshape(in_aval.shape).astype(in_aval.dtype)
            y = fn(params, x)
            yf = jnp.ravel(y).astype(act_dtype)
            return _pad_tail(yf, Amax)
        return run

    branches = [_branch(s) for s in range(S)]
    perm = [(i, (i + 1) % S) for i in range(S)]
    T = m + S - 1
    in_size = act_sizes[0]

    def body(packed_local, xs):
        local = packed_local[0]
        idx = jax.lax.axis_index("pp")
        xs2 = xs.reshape(m, -1).astype(act_dtype)
        if Amax > in_size:  # _pad_tail, 2-D: jnp.pad corrupts shard_map
            xs2 = jnp.concatenate(
                [xs2, jnp.zeros((m, Amax - in_size), act_dtype)], axis=1)
        xs_flat = xs2
        state = jnp.zeros((Amax,), act_dtype)
        out = jnp.zeros((m, Amax), act_dtype)

        def tick(carry, t):
            state, out = carry
            inject = jax.lax.dynamic_index_in_dim(
                xs_flat, jnp.minimum(t, m - 1), 0, keepdims=False)
            x_in = jnp.where(idx == 0, inject, state)
            y = jax.lax.switch(idx, branches, local, x_in)
            wpos = jnp.clip(t - (S - 1), 0, m - 1)
            old = jax.lax.dynamic_index_in_dim(out, wpos, 0, keepdims=False)
            newval = jnp.where(
                jnp.logical_and(idx == S - 1, t >= S - 1), y, old)
            out = jax.lax.dynamic_update_index_in_dim(out, newval, wpos, 0)
            state = jax.lax.ppermute(y, "pp", perm)
            return (state, out), None

        (_, out), _ = jax.lax.scan(tick, (state, out), jnp.arange(T))
        return jax.lax.psum(
            jnp.where(idx == S - 1, out, jnp.zeros_like(out)), "pp")

    out_flat = _shard_map(
        body, mesh=mesh,
        in_specs=(P("pp"), P()),
        out_specs=P(),
        axis_names=frozenset({"pp"}), check=False)(packed, xs)
    out = out_flat[:, :out_size].reshape((m,) + tuple(out_aval.shape))
    return out.astype(out_aval.dtype)
