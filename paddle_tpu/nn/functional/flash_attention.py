"""Attention functionals.

Reference: python/paddle/nn/functional/flash_attention.py (flash_attention
:147, flash_attn_unpadded :455, scaled_dot_product_attention :722) backed by
the third_party/flashattn CUDA library. TPU-native: a Pallas flash-attention
kernel (paddle_tpu/ops/pallas/flash_attention.py) on TPU backends, with an
XLA-fused reference path everywhere else (CPU tests, capture tracing).

Layout follows the reference: q/k/v are (batch, seq, num_heads, head_dim).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...core import dispatch
from ...core.tensor import Tensor, as_tensor


def _t(x):
    return x if isinstance(x, Tensor) else as_tensor(x)


def _use_pallas(seq_len=None, head_dim=None, dtype=None, causal=True):
    from ...core import flags
    if not flags.get_flag("use_pallas_kernels"):
        return False
    if jax.default_backend() != "tpu":
        return False
    if seq_len is None:
        return True
    # algorithm selection (the reference autotune cache's other job,
    # phi/kernels/autotune/cache.h AlgorithmType): when enabled and the
    # user has not pinned flash_min_seq_len, MEASURE XLA-dense vs
    # Pallas-flash for this shape class once per chip and cache the
    # winner. OPT-IN (FLAGS_autotune_attn_impl): unlike tile tuning,
    # a wrong winner here changes the ALGORITHM — one noisy probe can
    # flip a model to the slower path wholesale.
    f = flags._registry.get("flash_min_seq_len")
    if (flags.get_flag("autotune_attn_impl")
            and f is not None and f.value == f.default
            and head_dim is not None):
        from ...ops.pallas import autotune as at
        if at.should_autotune():
            # may run under the caller's trace: probe in an eval
            # context so it executes on the chip (see ops.pallas.autotune)
            with jax.core.eval_context():
                return _tuned_attn_impl(seq_len, head_dim, dtype,
                                        causal) == "pallas"
    if seq_len < flags.get_flag("flash_min_seq_len"):
        # measured crossover (see flag docstring): short sequences run
        # faster through XLA's fused dense attention than the blocked
        # Pallas kernel
        return False
    return True


def _flash_pallas(q, k, v, causal, window=None):
    """The Pallas kernel over (B, S, H, D) arrays, partitioned by hand
    where it has to be. GSPMD cannot partition a Mosaic kernel ("wrap
    the call in a shard_map"), so under a multi-device mesh the call
    runs in a full-manual ``shard_map`` over the dims attention is
    independent along: batch over the data axes, heads over ``mp`` —
    each only where it divides evenly; a dim that does not is left
    replicated (every device along that axis computes it: correct,
    redundant). One device, or a caller already inside a manual region,
    calls the kernel directly."""
    from ...distributed import mesh as mesh_mod
    from ...distributed.shard_map_compat import shard_map
    from ...ops.pallas.flash_attention import flash_attention_fwd

    kernel = functools.partial(flash_attention_fwd, causal=causal,
                               window=window)
    mesh = mesh_mod.get_mesh()
    if mesh.size == 1 or jax.sharding.get_abstract_mesh().manual_axes:
        return kernel(q, k, v)

    spec = P(mesh_mod.axes_dividing(mesh, q.shape[0], ("dp", "sharding")),
             None, mesh_mod.axes_dividing(mesh, q.shape[2], ("mp",)), None)
    return shard_map(kernel, mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


def _tuned_attn_impl(seq_len, head_dim, dtype, causal):
    """'pallas' or 'xla' for this (seq-bucket, head_dim, causal, dtype)
    class, measured once per chip: one fwd+bwd attention step per
    candidate, chained data-dependently so dispatch divides out. XLA
    dense at long seq OOMs its (B,H,S,S) logits — the probe's failure
    skips it, which picks pallas exactly where dense is infeasible."""
    from ...ops.pallas import autotune as at

    dt = jnp.dtype(dtype) if dtype is not None else jnp.float32
    sb = at.seq_bucket(seq_len)
    key = at.make_key("attn_impl", s=sb, d=int(head_dim),
                      dt=str(dt), causal=bool(causal))
    cached = at.get_cache().get(key)
    if cached is not None:
        return cached

    B, H = 2, 8
    qs, ks, vs = [], [], []
    for i in range(3):
        kp = jax.random.key(50 + i)
        qs.append(jax.random.normal(
            kp, (B, sb, H, head_dim)).astype(dt))
        ks.append(jax.random.normal(
            jax.random.fold_in(kp, 1), (B, sb, H, head_dim)).astype(dt))
        vs.append(jax.random.normal(
            jax.random.fold_in(kp, 2), (B, sb, H, head_dim)).astype(dt))
    flops = 3 * 4.0 * B * H * sb * sb * head_dim * (0.5 if causal else 1)
    reps = at.probe_reps(flops)
    jitted = {}

    def run(impl, i):
        fn = jitted.get(impl)
        if fn is None:
            def one(q, k, v):
                if impl == "pallas":
                    from ...ops.pallas.flash_attention import \
                        flash_attention_fwd
                    out = flash_attention_fwd(q, k, v, causal=causal)
                else:
                    out = _sdpa_xla(q, k, v, causal=causal)
                return jnp.mean(out.astype(jnp.float32))

            def step(q, k, v):
                def body(_, c):
                    l, g = jax.value_and_grad(one)(c, k, v)
                    # tiny NONZERO factor: a zero coefficient would let
                    # XLA dead-code-eliminate the whole backward pass
                    return c - g * jnp.asarray(1e-30, c.dtype)
                return jax.lax.fori_loop(0, reps, body, q)

            fn = jitted[impl] = jax.jit(step)
        j = i % 3
        return fn(qs[j], ks[j], vs[j])

    default = "pallas" if seq_len >= 1024 else "xla"
    return at.autotune(key, ["pallas", "xla"], run, default,
                       warmup=2, iters=5)


def _sdpa_xla(q, k, v, bias=None, causal=False, dropout_p=0.0, key=None,
              scale=None, window=None):
    """Reference-path attention in BSHD layout; fp32 softmax accumulator.
    ``window`` (with ``causal``): a query sees its own key and the ``window
    - 1`` before it."""
    sc = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    qt = jnp.einsum("bshd,bthd->bhst", q, k) * sc
    logits = qt.astype(jnp.float32)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s, t), bool), k=t - s)
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((s, t), bool), k=t - s - window)
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, 1 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1 - dropout_p), 0.0)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None, window=None):
    """``softmax(q k^T / sqrt(d)) v`` over (batch, seq, heads, head_dim)
    arrays (reference ``flash_attention``; returns ``(out, None)``).
    ``window`` (with ``causal``): sliding-window attention, query ``i`` sees
    key ``j`` iff ``0 <= i - j < window``: its own key and the ``window - 1``
    before it. The flash kernels neither run nor fetch the tiles wholly
    outside that band; a window no shorter than the sequence is the causal
    call."""
    q, k, v = _t(query), _t(key), _t(value)
    if window is not None and (not causal or window < 1):
        raise ValueError("a window is a causal call's, and at least 1")
    drop_key = None
    if dropout > 0.0 and training:
        from ...core.generator import next_key
        drop_key = next_key()

    if _use_pallas(q.shape[1], q.shape[-1], q.dtype,
                   causal) and dropout == 0.0:
        out = dispatch.call(
            "flash_attention",
            functools.partial(_flash_pallas, causal=causal, window=window),
            [q, k, v])
    else:
        def f(qa, ka, va):
            return _sdpa_xla(qa, ka, va, causal=causal,
                             dropout_p=dropout if training else 0.0,
                             key=drop_key, window=window)
        out = dispatch.call("flash_attention", f, [q, k, v])
    return out, None


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """Fused attention over [B, S, H, D] q/k/v (reference
    nn.functional.scaled_dot_product_attention): softmax(q·kᵀ/√d)·v
    with optional additive/boolean mask, causal masking and dropout.
    Dispatches the Pallas flash kernel when the shape class qualifies,
    else the XLA composite."""
    q, k, v = _t(query), _t(key), _t(value)
    inputs = [q, k, v]
    has_mask = attn_mask is not None
    if has_mask:
        inputs.append(_t(attn_mask))
    drop_key = None
    if dropout_p > 0.0 and training:
        from ...core.generator import next_key
        drop_key = next_key()

    if _use_pallas(q.shape[1], q.shape[-1], q.dtype, is_causal) \
            and not has_mask and dropout_p == 0.0:
        return dispatch.call(
            "scaled_dot_product_attention",
            functools.partial(_flash_pallas, causal=is_causal), [q, k, v])

    def f(qa, ka, va, *mask):
        bias = mask[0] if mask else None
        if bias is not None and jnp.issubdtype(bias.dtype, jnp.bool_):
            bias = jnp.where(bias, 0.0, -1e30)
        return _sdpa_xla(qa, ka, va, bias=bias, causal=is_causal,
                         dropout_p=dropout_p if training else 0.0,
                         key=drop_key)
    return dispatch.call("scaled_dot_product_attention", f, inputs,
                         differentiable_mask=[True, True, True]
                         + [False] * has_mask)


# registry entry for the dispatched name: the op already carried a
# named spmd rule + cost model, but no OpDef — the program verifier's
# contract pass (TPU700) surfaced the gap
from ...ops.registry import register as _register  # noqa: E402

_register("scaled_dot_product_attention",
          category="attention")(scaled_dot_product_attention)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Varlen attention over packed (total_tokens, heads, dim) tensors.
    Implemented by segment-masked attention: positions attend only within
    their own sequence (reference flash_attn_unpadded :455)."""
    q, k, v = _t(query), _t(key), _t(value)
    cq, ck = _t(cu_seqlens_q), _t(cu_seqlens_k)

    def f(qa, ka, va, cqa, cka):
        tq = qa.shape[0]
        tk = ka.shape[0]
        # segment id per token from cumulative seqlens
        pos_q = jnp.arange(tq)
        pos_k = jnp.arange(tk)
        seg_q = jnp.searchsorted(cqa[1:], pos_q, side="right")
        seg_k = jnp.searchsorted(cka[1:], pos_k, side="right")
        logits = jnp.einsum("qhd,khd->hqk", qa, ka) * scale
        logits = logits.astype(jnp.float32)
        same = seg_q[:, None] == seg_k[None, :]
        if causal:
            off_q = pos_q - jnp.take(cqa, seg_q)
            off_k = pos_k - jnp.take(cka, seg_k)
            same = same & (off_q[:, None] >= off_k[None, :])
        logits = jnp.where(same[None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(qa.dtype)
        return jnp.einsum("hqk,khd->qhd", probs, va)
    out = dispatch.call("flash_attn_unpadded", f, [q, k, v, cq, ck],
                        differentiable_mask=[True, True, True, False, False])
    return out, None


def flash_attention_with_sparse_mask(query, key, value,
                                     attn_mask_start_row_indices,
                                     attn_mask_start_row=0, dropout_p=0.0,
                                     is_causal=True, training=True, name=None):
    """Sparse-mask attention (reference :844): rows below a per-column start
    index are masked out in addition to the causal structure."""
    q, k, v = _t(query), _t(key), _t(value)
    idx = _t(attn_mask_start_row_indices)

    def f(qa, ka, va, ia):
        sc = 1.0 / math.sqrt(qa.shape[-1])
        logits = jnp.einsum("bshd,bthd->bhst", qa, ka) * sc
        logits = logits.astype(jnp.float32)
        s, t = logits.shape[-2], logits.shape[-1]
        rows = jnp.arange(s)[:, None]
        cols = jnp.arange(t)[None, :]
        mask = rows >= cols if is_causal else jnp.ones((s, t), bool)
        # ia: (batch, num_heads, seq) start row per column
        start = ia[:, :, None, :]
        mask = mask[None, None] & (rows[None, None] < start)
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(qa.dtype)
        return jnp.einsum("bhst,bthd->bshd", probs, va)
    return dispatch.call("flash_attention_with_sparse_mask", f, [q, k, v, idx],
                         differentiable_mask=[True, True, True, False])


def sdp_kernel(*args, **kwargs):
    class _Null:
        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False
    return _Null()


__all__ = ["flash_attention", "scaled_dot_product_attention",
           "flash_attn_unpadded", "flash_attention_with_sparse_mask",
           "sdp_kernel"]
