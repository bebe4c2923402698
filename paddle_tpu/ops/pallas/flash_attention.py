"""Flash attention (forward + backward) as Pallas TPU kernels.

Replaces the reference's FlashAttention-2 CUDA library integration
(reference: third_party/flashattn; op `flash_attn` at
paddle/phi/ops/yaml/ops.yaml:1635). Design:

* forward — online-softmax over KV tiles: grid (batch*heads, q_tiles,
  kv_tiles) with the kv axis innermost so the fp32 accumulators in VMEM
  scratch persist across kv steps; the MXU consumes (Bq, d) x (d, Bk)
  tiles; causal tiles above the diagonal are skipped with @pl.when so no
  FLOPs are spent on masked blocks. Also emits the per-row logsumexp
  (the FA2 "L" residual) for backward.
* backward — the FA2 recompute strategy, O(S·d) memory: residuals are only
  (q, k, v, out, lse); each backward tile recomputes p = exp(qk·scale−lse)
  on the fly. Two kernels: dQ iterates kv innermost accumulating
  dq += ds·K; dK/dV iterates q innermost accumulating dv += pᵀ·dO and
  dk += dsᵀ·Q, where ds = p·(dp − Δ)·scale, dp = dO·Vᵀ and
  Δ = rowsum(dO∘O) is precomputed by one fused XLA reduction. The full
  (S, S) probability matrix is never materialized in either pass.

``block_q`` / ``block_k`` are exposed for tuning (reference
flash_attn's num_splits analog); ``INTERPRET=True`` runs the same kernels
through the Pallas interpreter so CPU tests cover the real kernel code.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import import_pallas

pl, pltpu = import_pallas()

NEG_INF = -1e30

# Tuning knobs (VMEM-footprint vs pipeline depth); override per call via
# flash_attention_fwd(..., block_q=..., block_k=...).
DEFAULT_BLOCK_Q = 1024      # tuned on v5e @ S=8k: 23 TF/s vs 19 at 512
DEFAULT_BLOCK_K = 1024
DEFAULT_BWD_BLOCK_Q = 512
DEFAULT_BWD_BLOCK_K = 512


def _bwd_block_for(seq):
    """Backward tile size for ONE side (q or k), from that side's length:
    1024 wins at short/medium seq (measured on v5e: 82.0ms vs 84.1ms GPT-2
    step @ S=1024) but only when it divides the seq (otherwise padding
    wastes up to 33% of the grid); longer seqs keep the 512 tiles that hold
    the dKdV accumulators in VMEM (the original 8k tuning)."""
    if seq <= 2048 and seq % 1024 == 0:
        return 1024
    return DEFAULT_BWD_BLOCK_Q

#: run kernels in the Pallas interpreter (CPU testing of kernel code)
INTERPRET = False

# Candidate tile grids for the measured autotuner (ops/pallas/autotune.py).
# Small on purpose: each candidate costs one Pallas compile at first sight
# of a new (shape-class, chip) key; winners persist to disk.
FWD_TILE_CANDIDATES = [(1024, 1024), (512, 512), (512, 1024), (1024, 512),
                       (2048, 512)]
BWD_TILE_CANDIDATES = [(512, 512), (1024, 1024), (256, 512), (512, 1024),
                       (1024, 512)]


def _tuned_blocks(kind, bh, s_q, s_k, d, dtype, causal, scale):
    """Measured (block_q, block_k) for this shape class on this chip.

    Falls back to the hand-tuned v5e constants when autotuning is off or
    the backend is not a real TPU (reference
    phi/kernels/autotune/switch_autotune.cc gate). Benchmarks run on
    noise at the BUCKETED sequence lengths (tile ranking is data- and
    batch-mostly-independent; batch*heads is capped at 8 to keep the
    probe cheap).
    """
    from . import autotune as at

    if INTERPRET or not at.should_autotune():
        if kind == "fwd":
            return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
        return _bwd_block_for(s_q), _bwd_block_for(s_k)

    sq_b, sk_b = at.seq_bucket(s_q), at.seq_bucket(s_k)
    key = at.make_key(f"flash_{kind}", sq=sq_b, sk=sk_b, d=d,
                      dt=str(jnp.dtype(dtype)), causal=bool(causal))
    cached = at.get_cache().get(key)
    if cached is not None:
        return tuple(cached)

    bh_b = min(bh, 8)
    # probe on noise, not zeros (constant-folding could skip real work)
    nvar = 3
    qs, ks, vs = [], [], []
    for i in range(nvar):
        kp = jax.random.key(i)
        qs.append(jax.random.normal(kp, (bh_b, sq_b, d)).astype(dtype))
        ks.append(jax.random.normal(
            jax.random.fold_in(kp, 1), (bh_b, sk_b, d)).astype(dtype))
        vs.append(jax.random.normal(
            jax.random.fold_in(kp, 2), (bh_b, sk_b, d)).astype(dtype))
    # amortize per-call dispatch under the kernel: chain K applications
    # data-dependently inside ONE program (the kernel's q-shaped output
    # feeds the next iteration), sized so device time dominates
    kernel_flops = 4.0 * bh_b * sq_b * sk_b * d * (0.5 if causal else 1.0)
    reps = at.probe_reps(kernel_flops)
    jitted = {}
    if kind == "fwd":
        candidates, default = FWD_TILE_CANDIDATES, (DEFAULT_BLOCK_Q,
                                                    DEFAULT_BLOCK_K)

        def run(c, i):
            fn = jitted.get(c)
            if fn is None:
                kern = functools.partial(
                    _flash_fwd_bhsd, causal=causal, scale=scale,
                    block_q=c[0], block_k=c[1])

                def chained(q0, k0, v0):
                    return jax.lax.fori_loop(
                        0, reps, lambda _, q: kern(q, k0, v0)[0], q0)

                fn = jitted[c] = jax.jit(chained)
            j = i % nvar
            return fn(qs[j], ks[j], vs[j])
    else:
        candidates = BWD_TILE_CANDIDATES
        default = (_bwd_block_for(s_q), _bwd_block_for(s_k))
        fwd = jax.jit(functools.partial(
            _flash_fwd_bhsd, causal=causal, scale=scale,
            block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K))
        outs, lses = zip(*(fwd(qs[j], ks[j], vs[j])
                           for j in range(nvar)))

        def run(c, i):
            fn = jitted.get(c)
            if fn is None:
                kern = functools.partial(
                    _flash_bwd_bhsd, causal=causal, scale=scale,
                    block_q=c[0], block_k=c[1])

                def chained(q0, k0, v0, o0, l0, g0):
                    return jax.lax.fori_loop(
                        0, reps,
                        lambda _, q: kern(q, k0, v0, o0, l0, g0)[0], q0)

                fn = jitted[c] = jax.jit(chained)
            j = i % nvar
            return fn(qs[j], ks[j], vs[j], outs[j], lses[j], outs[j])

    return tuple(at.autotune(key, candidates, run, default,
                             warmup=2, iters=5))


def _causal_run(q_idx, kv_idx, block_q, block_k, offset):
    """Tile intersects the bottom-right-aligned causal region."""
    return kv_idx * block_k <= q_idx * block_q + (block_q - 1) + offset


def _tile_mask(q_idx, kv_idx, block_q, block_k, seq_k, causal, offset):
    q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos < seq_k
    if causal:
        mask = mask & (q_pos + offset >= k_pos)
    return mask


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k, seq_q, seq_k):
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)
    num_kv = pl.num_programs(2)
    # Bottom-right-aligned causal diagonal (matches tril(..., k=t-s) in the
    # XLA reference path): query i attends keys <= i + (seq_k - seq_q).
    causal_offset = seq_k - seq_q

    @pl.when(kv_idx == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        run = _causal_run(q_idx, kv_idx, block_q, block_k, causal_offset)

    @pl.when(run)
    def _step():
        q = q_ref[0]          # (block_q, d)
        k = k_ref[0]          # (block_k, d)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = _tile_mask(q_idx, kv_idx, block_q, block_k, seq_k, causal,
                          causal_offset)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:]                      # (block_q, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                 # (block_q, block_k)
        # fully-masked rows (causal, seq_q > seq_k): m_new == NEG_INF and
        # exp(s - m_new) == 1; zero them so l stays 0 and out stays 0
        p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(kv_idx == num_kv - 1)
    def _finish():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale, causal, block_q, block_k, seq_q, seq_k):
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)
    num_kv = pl.num_programs(2)
    causal_offset = seq_k - seq_q

    @pl.when(kv_idx == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True
    if causal:
        run = _causal_run(q_idx, kv_idx, block_q, block_k, causal_offset)

    @pl.when(run)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                       # (block_q, 1)
        delta = delta_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = _tile_mask(q_idx, kv_idx, block_q, block_k, seq_k, causal,
                          causal_offset)
        s = jnp.where(mask, s, NEG_INF)
        # mask-guard (not just exp underflow): for fully-masked rows lse is
        # garbage (~NEG_INF) and exp(NEG_INF - lse) would be 1, not 0
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale          # (block_q, block_k) fp32
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kv_idx == num_kv - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, scale, causal, block_q, block_k,
                seq_q, seq_k):
    q_idx = pl.program_id(2)       # q innermost in this kernel
    kv_idx = pl.program_id(1)
    num_q = pl.num_programs(2)
    causal_offset = seq_k - seq_q

    @pl.when(q_idx == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        run = _causal_run(q_idx, kv_idx, block_q, block_k, causal_offset)

    @pl.when(run)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = _tile_mask(q_idx, kv_idx, block_q, block_k, seq_k, causal,
                          causal_offset)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        # dv += P^T dO
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        # dk += dS^T Q
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(q_idx == num_q - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _pad_bhsd(x, block_s, pad_d):
    pad_s = (-x.shape[1]) % block_s
    if pad_s or pad_d:
        x = jnp.pad(x, ((0, 0), (0, pad_s), (0, pad_d)))
    return x


def _flash_fwd_bhsd(q, k, v, *, causal, scale, block_q, block_k):
    """q/k/v: (BH, S, d) -> (out (BH, S, d), lse fp32 (BH, Sq_padded))."""
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    block_q = min(block_q, max(s_q, 8))
    block_k = min(block_k, max(s_k, 8))
    pad_d = (-d) % 128
    q = _pad_bhsd(q, block_q, pad_d)
    k = _pad_bhsd(k, block_k, pad_d)
    v = _pad_bhsd(v, block_k, pad_d)
    sp_q, sp_k, dp = q.shape[1], k.shape[1], d + pad_d

    grid = (bh, sp_q // block_q, sp_k // block_k)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_q=s_q, seq_k=s_k)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((bh, sp_q, dp), q.dtype),
                   jax.ShapeDtypeStruct((bh, sp_q, 1), jnp.float32)],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dp), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dp), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dp), jnp.float32),
        ],
        interpret=INTERPRET,
        name="flash_fwd",
    )(q, k, v)
    return out[:, :s_q, :d], lse


def _flash_bwd_bhsd(q, k, v, out, lse, do, *, causal, scale, block_q,
                    block_k):
    """FA2 backward. All of q/k/v/out/do: (BH, S, d); lse: (BH, Sq_pad_fwd).
    Returns (dq, dk, dv) unpadded."""
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    block_q = min(block_q, max(s_q, 8))
    block_k = min(block_k, max(s_k, 8))
    pad_d = (-d) % 128

    # Δ = rowsum(dO ∘ O): one fused XLA reduction, fp32.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                # (BH, s_q, 1)

    q = _pad_bhsd(q, block_q, pad_d)
    do = _pad_bhsd(do, block_q, pad_d)
    k = _pad_bhsd(k, block_k, pad_d)
    v = _pad_bhsd(v, block_k, pad_d)
    sp_q, sp_k, dp = q.shape[1], k.shape[1], d + pad_d
    if lse.shape[1] < sp_q:     # fwd may have tiled with a different block
        lse = jnp.pad(lse, ((0, 0), (0, sp_q - lse.shape[1]), (0, 0)))
    elif lse.shape[1] > sp_q:
        lse = lse[:, :sp_q]
    delta = jnp.pad(delta, ((0, 0), (0, sp_q - s_q), (0, 0)))

    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
              seq_q=s_q, seq_k=s_k)
    q_spec = pl.BlockSpec((1, block_q, dp), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        out_shape=jax.ShapeDtypeStruct((bh, sp_q, dp), q.dtype),
        grid=(bh, sp_q // block_q, sp_k // block_k),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, block_k, dp), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dp), lambda b, i, j: (b, j, 0)),
            q_spec, row_spec, row_spec,
        ],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((block_q, dp), jnp.float32)],
        interpret=INTERPRET,
        name="flash_dq",
    )(q, k, v, do, lse, delta)

    # dk/dv: kv outer, q inner
    qi_spec = pl.BlockSpec((1, block_q, dp), lambda b, i, j: (b, j, 0))
    rowi_spec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, j, 0))
    kv_spec = pl.BlockSpec((1, block_k, dp), lambda b, i, j: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        out_shape=[jax.ShapeDtypeStruct((bh, sp_k, dp), k.dtype),
                   jax.ShapeDtypeStruct((bh, sp_k, dp), v.dtype)],
        grid=(bh, sp_k // block_k, sp_q // block_q),
        in_specs=[qi_spec, kv_spec, kv_spec, qi_spec, rowi_spec, rowi_spec],
        out_specs=[kv_spec, kv_spec],
        scratch_shapes=[pltpu.VMEM((block_k, dp), jnp.float32),
                        pltpu.VMEM((block_k, dp), jnp.float32)],
        interpret=INTERPRET,
        name="flash_dkv",
    )(q, k, v, do, lse, delta)
    return (dq[:, :s_q, :d], dk[:, :s_k, :d], dv[:, :s_k, :d])


def _bshd_to_bhsd(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _bhsd_to_bshd(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, scale, block_q, block_k):
    out, _ = _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k)
    return out


def _resolve_blocks(kind, block_q, block_k, q, k, causal, scale):
    """Caller-pinned tiles win; unset ones come from the autotuner. The
    vjp rules run while the model's step is being TRACED, so the probe
    is built and run in an eval context: it must execute on the chip,
    not be staged into the caller's program (where its timings would be
    trace time)."""
    if block_q is not None and block_k is not None:
        return block_q, block_k
    b, s, h, d = q.shape
    with jax.core.eval_context():
        tq, tk = _tuned_blocks(kind, b * h, s, k.shape[1], d, q.dtype,
                               causal, scale)
    return (tq if block_q is None else block_q,
            tk if block_k is None else block_k)


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k):
    b, s, h, d = q.shape
    block_q, block_k = _resolve_blocks("fwd", block_q, block_k, q, k,
                                       causal, scale)
    out, lse = _flash_fwd_bhsd(
        _bshd_to_bhsd(q), _bshd_to_bhsd(k), _bshd_to_bhsd(v),
        causal=causal, scale=scale, block_q=block_q, block_k=block_k)
    out_bshd = _bhsd_to_bshd(out, b, h)
    return out_bshd, (q, k, v, out_bshd, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, res, g):
    q, k, v, out, lse = res
    b, s, h, d = q.shape
    block_q, block_k = _resolve_blocks("bwd", block_q, block_k, q, k,
                                       causal, scale)
    dq, dk, dv = _flash_bwd_bhsd(
        _bshd_to_bhsd(q), _bshd_to_bhsd(k), _bshd_to_bhsd(v),
        _bshd_to_bhsd(out), lse, _bshd_to_bhsd(g),
        causal=causal, scale=scale, block_q=block_q, block_k=block_k)
    return (_bhsd_to_bshd(dq, b, h), _bhsd_to_bshd(dk, b, h),
            _bhsd_to_bshd(dv, b, h))


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention_fwd(q, k, v, causal=False, scale=None, block_q=None,
                        block_k=None):
    """Public entry: q/k/v (batch, seq, heads, head_dim). ``block_q`` /
    ``block_k`` tune the tile sizes (defaults: DEFAULT_BLOCK_Q/K forward,
    DEFAULT_BWD_BLOCK_Q/K backward)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _flash_attention(q, k, v, causal, scale, block_q, block_k)
