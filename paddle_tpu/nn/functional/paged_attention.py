"""Paged (block-table) KV-cache attention for serving.

Reference: block_multi_head_attention
(phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu, exposed via
python/paddle/incubate/nn/functional/block_multihead_attention.py) — the
vLLM-style paged KV cache: the KV history of each sequence lives in
fixed-size physical blocks referenced through a per-sequence block table,
so sequences grow without reallocating or compacting.

TPU-native design: the cache is one (num_blocks, block_size, KVH, D) array
per K/V; a step is (1) scatter the step's new KV into physical slots
computed from the block table (one `.at[].set` with batched indices), then
(2) per sequence gather its blocks back into a contiguous (S_max, KVH, D)
view and run masked attention — gathers + one MXU einsum, all static
shapes, fully jittable into a serving step. GQA/MQA supported (H a
multiple of KVH).

int8 page pool (the serving tier's ``kv_dtype="int8"`` knob): pass int8
caches plus sidecar per-(position, head) scale arrays ``k_scale`` /
``v_scale`` of shape (num_blocks, block_size, KVH). New KV is quantized
symmetric-abs-max over the head dim on write (``ops.pallas.serving``),
and the gather dequantizes into the attention math's fp32 accumulation —
the same payload-int8 / sidecar-scales / dequant-at-consumer pattern as
``nn.quant.weight_only_linear``, applied to KV pages. Resident KV shrinks
~2x vs bf16 pages, which is resident-batch headroom on a serving chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import dispatch
from ...core.tensor import Tensor, as_tensor

__all__ = ["block_multihead_attention"]


def _t(x):
    return x if isinstance(x, Tensor) else as_tensor(x)


def block_multihead_attention(q, key_cache, value_cache, block_tables,
                              seq_lens, new_k=None, new_v=None, causal=True,
                              scale=None, k_scale=None, v_scale=None,
                              name=None):
    """Attend over paged KV history (+ optionally append this step's KV).

    Args:
      q: (B, T, H, D) queries for the T newest positions of each sequence
         (T=1 decode; T>1 chunked prefill / speculative verify).
      key_cache / value_cache: (num_blocks, block_size, KVH, D). Float
         pages, or int8 pages when ``k_scale``/``v_scale`` are given.
      block_tables: (B, max_blocks_per_seq) int32 physical block ids;
         entries beyond a sequence's allocation may be any valid id (they
         are masked by seq_lens).
      seq_lens: (B,) int32 sequence lengths INCLUDING the new T tokens.
      new_k / new_v: (B, T, KVH, D) — written into the caches at positions
         [len-T, len) before attending. Omit for read-only attention.
      causal: within the T new positions, query t sees history up to and
         including its own slot.
      k_scale / v_scale: (num_blocks, block_size, KVH) float32 sidecar
         scales for int8 caches. New KV is quantized on write; the
         per-sequence gather dequantizes.

    Returns (out (B, T, H, D), key_cache, value_cache) — plus the updated
    (k_scale, v_scale) appended when int8 caches are used. Caches update
    functionally (donate them in a jitted serving step for in-place
    reuse).
    """
    q, kc, vc = _t(q), _t(key_cache), _t(value_cache)
    bt, sl = _t(block_tables), _t(seq_lens)
    tensors = [q, kc, vc, bt, sl]
    has_new = new_k is not None
    if has_new:
        new_k, new_v = _t(new_k), _t(new_v)
        tensors += [new_k, new_v]
    quantized = k_scale is not None
    if quantized:
        if v_scale is None:
            raise ValueError("int8 KV cache needs both k_scale and v_scale")
        ks_t, vs_t = _t(k_scale), _t(v_scale)
        tensors += [ks_t, vs_t]

    def f(*arrays):
        # the composite's scope in a trace: cache write, per-sequence page
        # gather, scores, softmax, values
        with jax.named_scope("paged_attention"):
            return composite(*arrays)

    def composite(qa, kca, vca, bta, sla, *rest):
        from ...ops.pallas.serving import (kv_dequantize_int8,
                                           kv_quantize_int8)

        B, T, H, D = qa.shape
        nb, bs, KVH, _ = kca.shape
        max_blocks = bta.shape[1]
        s_max = max_blocks * bs
        if H % KVH:
            raise ValueError(f"H={H} not a multiple of KVH={KVH}")
        group = H // KVH
        sla_i = sla.astype(jnp.int32)
        bta_i = bta.astype(jnp.int32)
        ksa = vsa = None
        if quantized:
            ksa, vsa = rest[-2:]
            rest = rest[:-2]

        if has_new:
            nk, nv = rest
            # flat slot of new token t of seq b: pos = len - T + t. Rows
            # with seq_len < T (padded batch rows) would yield negative
            # positions that WRAP into live blocks — drop those writes.
            pos = sla_i[:, None] - T + jnp.arange(T)[None, :]     # (B, T)
            ok = pos >= 0
            blk = jnp.take_along_axis(bta_i, jnp.maximum(pos, 0) // bs,
                                      axis=1)                     # (B, T)
            blk = jnp.where(ok, blk, nb)  # out-of-range -> mode="drop"
            off = jnp.maximum(pos, 0) % bs
            if quantized:
                qk, sk = kv_quantize_int8(nk)
                qv, sv = kv_quantize_int8(nv)
                kca = kca.at[blk, off].set(qk, mode="drop")
                vca = vca.at[blk, off].set(qv, mode="drop")
                ksa = ksa.at[blk, off].set(sk, mode="drop")
                vsa = vsa.at[blk, off].set(sv, mode="drop")
            else:
                kca = kca.at[blk, off].set(nk, mode="drop")
                vca = vca.at[blk, off].set(nv, mode="drop")

        sc = scale if scale is not None else 1.0 / (D ** 0.5)

        def per_seq(blocks, length, qb):
            # gather this sequence's pages -> (s_max, KVH, D)
            if quantized:
                k = kv_dequantize_int8(kca[blocks], ksa[blocks])
                v = kv_dequantize_int8(vca[blocks], vsa[blocks])
                k = k.reshape(s_max, KVH, D)
                v = v.reshape(s_max, KVH, D)
            else:
                k = kca[blocks].reshape(s_max, KVH, D)
                v = vca[blocks].reshape(s_max, KVH, D)
            qg = qb.reshape(T, KVH, group, D)
            s = jnp.einsum("tkgd,skd->tkgs", qg.astype(jnp.float32),
                           k.astype(jnp.float32)) * sc
            jpos = jnp.arange(s_max)[None, None, None, :]
            qpos = (length - T + jnp.arange(T)).reshape(T, 1, 1, 1)
            mask = jpos < length
            if causal:
                mask = jpos <= qpos
            # -1e30 (not -inf) + explicit zeroing of fully-masked rows:
            # a padded row (length <= 0) must yield 0, not NaN
            s = jnp.where(mask, s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("tkgs,skd->tkgd", p, v.astype(jnp.float32))
            any_valid = mask.any(axis=-1, keepdims=True)
            o = jnp.where(any_valid, o, 0.0)
            return o.reshape(T, H, D).astype(qb.dtype)

        out = jax.vmap(per_seq)(bta_i, sla_i, qa)
        if quantized:
            return out, kca, vca, ksa, vsa
        return out, kca, vca

    # int8 caches/scales are not differentiable surfaces (round/clip);
    # the float path keeps its original cache lineage for trainers that
    # backprop through read-only paged attention.
    mask = ([True] + [not quantized] * 2 + [False, False]
            + [True, True] * has_new + [False, False] * quantized)
    return dispatch.call("block_multihead_attention", f, tensors,
                         differentiable_mask=mask)
