"""Multi-head latent attention (MLA), the DeepSeek-V2/V3 attention layer.

A token's keys and values are not projected from the hidden state but
expanded from ONE compressed row: ``[c' | k_r'] = W_kva u`` (hidden ->
``kv_lora_rank + qk_rope_head_dim``), ``c = RMSNorm(c')``, ``k_r =
RoPE(k_r')`` (one rotary row shared by every head), and per head ``[k_nope_h
| v_h] = W_kvb,h c``. A query head is ``[q_nope_h | q_rope_h]`` (``q_rope``
rotated), its score against key s ``(q_nope_h . k_nope_h,s + q_rope_h .
k_r,s) * qk_head_dim ** -0.5``, causal softmax, ``o_h = sum_s p_s v_h,s``,
then ``W_o``. Rotary pairs are the interleaved ``(2i, 2i + 1)`` of the
published checkpoints (``rope_interleave``): they are brought to the
half-split layout and rotated as rotate-half there, as the HF
``deepseek_v3`` code does; q and k stay in that layout, which their dot
product does not see.

The layer has the function in both of its forms:

* **materialised** (``forward``): K and V of every head are expanded from
  ``c`` and attended as ordinary multi-head attention. What the published
  description says, what a whole-sequence forward (tests, trainers) runs.
* **absorbed** (``absorbed_queries`` / ``unabsorb`` around an attention over
  the rows ``[c | k_r]`` themselves): split ``W_kvb,h`` into ``W_uk,h`` and
  ``W_uv,h``; ``q_lat,h = W_uk,h^T q_nope_h``, score ``(q_lat,h . c_s +
  q_rope_h . k_r,s) * qk_head_dim ** -0.5``, ``o_lat,h = sum_s p_s c_s``,
  ``o_h = W_uv,h o_lat,h``. The same function; multi-query attention with
  one K/V head whose keys are the cached rows and whose values are their
  first ``kv_lora_rank`` columns. What serving runs, since only ``[c | k_r]``
  is cached: ``kv_lora_rank + qk_rope_head_dim`` numbers a token a layer
  where per-head K and V would be ``heads * (qk_head_dim + v_head_dim)``.

Where the absorbed form rounds in a bfloat16 model: ``q_lat`` (a 512-wide
product of ``q_nope``, rounded once to bfloat16 before the scores) and
``o_lat`` (rounded once before ``W_uv``), where the materialised form
rounds ``k_nope`` and ``v``; both keep float32 scores and accumulators.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import ops
from ...core.tensor import Tensor
from .. import functional as F
from ..initializer import Normal
from ..parameter import ParamAttr
from .common import Linear
from .layers import Layer
from .norm import RMSNorm

__all__ = ["MultiHeadLatentAttention"]


def _linear(in_f, out_f, std):
    return Linear(in_f, out_f, bias_attr=False,
                  weight_attr=ParamAttr(initializer=Normal(0.0, std)))


def _rotate(x, theta, start, interleaved):
    """Rotary embedding of ``x`` (B, T, heads, D) from position ``start``
    (an int, (B,) offsets, or the rows' (B, T) positions)."""
    from ...models.llama import rotary_embedding
    if interleaved:
        b, t, h, d = x.shape
        x = ops.reshape(ops.transpose(
            ops.reshape(x, [b, t, h, d // 2, 2]), [0, 1, 2, 4, 3]),
            [b, t, h, d])
    return rotary_embedding(x, theta, pos_offset=start)


class MultiHeadLatentAttention(Layer):
    """``forward(u)``: causal attention over a whole sequence ``u`` (B, T,
    hidden) in the materialised form. The published parameter names
    (``q_lora_rank`` None: queries are projected straight from the hidden
    state, no bias)."""

    def __init__(self, hidden_size: int, num_heads: int, kv_lora_rank: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int,
                 v_head_dim: int, rope_theta: float = 10000.0,
                 rope_interleave: bool = True, rms_norm_eps: float = 1e-6,
                 init_std: float = 0.02):
        super().__init__()
        self.num_heads, self.kv_lora_rank = num_heads, kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.qk_head_dim = qk_nope_head_dim + qk_rope_head_dim
        self.rope_theta, self.rope_interleave = rope_theta, rope_interleave
        #: the softmax scale: of the un-absorbed head, in both forms
        self.scale = self.qk_head_dim ** -0.5
        self.q_proj = _linear(hidden_size, num_heads * self.qk_head_dim,
                              init_std)
        self.kv_a_proj_with_mqa = _linear(
            hidden_size, kv_lora_rank + qk_rope_head_dim, init_std)
        self.kv_a_layernorm = RMSNorm(kv_lora_rank, epsilon=rms_norm_eps)
        self.kv_b_proj = _linear(
            kv_lora_rank, num_heads * (qk_nope_head_dim + v_head_dim),
            init_std)
        self.o_proj = _linear(num_heads * v_head_dim, hidden_size, init_std)

    @property
    def row_width(self) -> int:
        """What a token caches in this layer: ``[c | k_r]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def latent(self, u, start=0):
        """``q_nope`` (B, T, H, nope), ``q_rope`` (B, T, H, rope) rotated,
        ``c`` (B, T, rank) normed and ``k_r`` (B, T, rope) rotated, from
        position ``start``."""
        b, t = u.shape[0], u.shape[1]
        h, nope, rope = (self.num_heads, self.qk_nope_head_dim,
                         self.qk_rope_head_dim)
        q = ops.reshape(self.q_proj(u), [b, t, h, self.qk_head_dim])
        q_nope, q_rope = q[:, :, :, :nope], q[:, :, :, nope:]
        ckr = self.kv_a_proj_with_mqa(u)
        c = self.kv_a_layernorm(ckr[:, :, :self.kv_lora_rank])
        k_r = ops.reshape(ckr[:, :, self.kv_lora_rank:], [b, t, 1, rope])
        q_rope = _rotate(q_rope, self.rope_theta, start,
                         self.rope_interleave)
        k_r = _rotate(k_r, self.rope_theta, start, self.rope_interleave)
        return q_nope, q_rope, c, ops.reshape(k_r, [b, t, rope])

    def forward(self, u):
        b, t = u.shape[0], u.shape[1]
        h, nope, rope, vd = (self.num_heads, self.qk_nope_head_dim,
                             self.qk_rope_head_dim, self.v_head_dim)
        q_nope, q_rope, c, k_r = self.latent(u)
        kv = ops.reshape(self.kv_b_proj(c), [b, t, h, nope + vd])
        k = ops.concat([kv[:, :, :, :nope], ops.tile(
            ops.reshape(k_r, [b, t, 1, rope]), [1, 1, h, 1])], axis=-1)
        q = ops.concat([q_nope, q_rope], axis=-1)
        # values ride at the keys' width (zeros past v_head_dim), so that
        # one fused attention serves heads whose K and V differ in size
        v = ops.concat([kv[:, :, :, nope:], ops.zeros(
            [b, t, h, self.qk_head_dim - vd], dtype=kv.dtype)], axis=-1)
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return self.o_proj(ops.reshape(out[:, :, :, :vd], [b, t, h * vd]))

    # ------------------------------------------------------ absorbed form
    def _w_kvb(self):
        """``W_kvb`` as (rank, H, nope + v): ``[..., :nope]`` is ``W_uk``,
        the rest ``W_uv``."""
        return self.kv_b_proj.weight._data.reshape(
            self.kv_lora_rank, self.num_heads,
            self.qk_nope_head_dim + self.v_head_dim)

    def absorbed_queries(self, q_nope, q_rope) -> Tensor:
        """``[W_uk^T q_nope | q_rope]`` (B, T, H, rank + rope): the query
        that meets a cached row ``[c | k_r]`` directly. float32
        accumulation, rounded once to the model's dtype."""
        w_uk = self._w_kvb()[:, :, :self.qk_nope_head_dim]
        q_lat = jnp.einsum("bthd,chd->bthc", q_nope._data, w_uk,
                           preferred_element_type=jnp.float32)
        return Tensor(jnp.concatenate(
            [q_lat.astype(q_rope._data.dtype), q_rope._data], axis=-1))

    def unabsorb(self, o_lat) -> Tensor:
        """``W_o [W_uv,h o_lat,h]_h``: ``o_lat`` (B, T, H, rank) -> (B, T,
        hidden)."""
        b, t = o_lat.shape[0], o_lat.shape[1]
        w_uv = self._w_kvb()[:, :, self.qk_nope_head_dim:]
        o = jnp.einsum("bthc,chd->bthd", o_lat._data, w_uv,
                       preferred_element_type=jnp.float32)
        return self.o_proj(Tensor(o.astype(o_lat._data.dtype).reshape(
            b, t, self.num_heads * self.v_head_dim)))

    def forward_absorbed(self, u):
        """``forward`` computed the other way: scores and weighted sums
        over the rows ``[c | k_r]`` (plain ``jnp``, no cache). Tests hold
        the two forms equal."""
        q_nope, q_rope, c, k_r = self.latent(u)
        q = self.absorbed_queries(q_nope, q_rope)._data
        rows = jnp.concatenate([c._data, k_r._data], axis=-1)   # (B, T, W)
        t = rows.shape[1]
        exact = (jax.lax.Precision.HIGHEST if rows.dtype == jnp.float32
                 else None)
        s = jnp.einsum("bthw,bsw->bhts", q, rows, precision=exact,
                       preferred_element_type=jnp.float32) * self.scale
        seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        o_lat = jnp.einsum("bhts,bsc->bthc", p,
                           rows[..., :self.kv_lora_rank].astype(jnp.float32),
                           precision=exact)
        return self.unabsorb(Tensor(o_lat.astype(rows.dtype)))

    def attend_cached(self, u, cache, li):
        """The serving path: write the rows ``[c | k_r]`` of ``u`` (at
        ``cache.positions``) into layer ``li``'s latent pages of ``cache``
        and attend over them absorbed.
        Scopes: ``attn.mla.proj`` around the products with weights,
        ``attn.mla.core`` around scores, softmax and the weighted sum over
        cached rows."""
        with jax.named_scope("attn.mla.proj"):
            q_nope, q_rope, c, k_r = self.latent(u, cache.positions)
            q = self.absorbed_queries(q_nope, q_rope)
            rows = ops.concat([c, k_r], axis=-1)
        with jax.named_scope("attn.mla.core"):
            o_lat = cache.attend_latent(li, q, rows, self.kv_lora_rank,
                                        self.scale)
        with jax.named_scope("attn.mla.proj"):
            return self.unabsorb(o_lat)
