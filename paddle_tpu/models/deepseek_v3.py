"""DeepSeek-V3 decoder (``model_type`` ``deepseek_v3``; Kakao's
Kanana-2-30B-A3B is the published instance the benchmark serves).

A pre-norm residual block a layer, ``a = x + Attn_l(RMSNorm(x))``, ``y = a
+ FFN_l(RMSNorm(a))``; after the last block a final RMSNorm and an untied
head.

* ``Attn_l``: multi-head latent attention (``nn.MultiHeadLatentAttention``):
  queries projected straight from the hidden state (``q_lora_rank`` None),
  keys and values expanded from one compressed row ``[c | k_r]`` a token,
  ``kv_lora_rank + qk_rope_head_dim`` wide, which is all a layer caches.
  ``rope_scaling`` None: the softmax scale is ``qk_head_dim ** -0.5``.
* ``FFN_l``: the first ``first_k_dense_replace`` layers a SwiGLU of
  ``intermediate_size``; after them ``nn.SwiGLUMoE``: a float32 sigmoid
  router over ``n_routed_experts``, ``num_experts_per_tok`` SwiGLU experts
  of ``moe_intermediate_size`` weighted by their renormalised scores times
  ``routed_scaling_factor``, plus ``n_shared_experts`` shared ones as one
  SwiGLU of the summed width (``n_group`` = ``topk_group`` = 1: no group
  limit; other values are refused).

``forward(ids)`` runs a whole sequence in the published (materialised) form;
``paged_adapter()`` is what ``inference.PagedEngine`` serves the model
through: every layer keeps latent pages (``("latent_kv", row_width)``), over
which a chunk attends in the absorbed form, and a sparse layer an
expert-load counter beside them.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.parameter import ParamAttr

__all__ = ["DeepseekV3Config", "DeepseekV3ForCausalLM", "DeepseekV3Model",
           "deepseek_v3_tiny"]


@dataclass
class DeepseekV3Config:
    """The published keys under their published names."""
    vocab_size: int = 128256
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    # latent attention
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1000000.0
    rope_interleave: bool = True
    rope_scaling: Optional[dict] = None
    # feed-forward
    intermediate_size: int = 6144
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    moe_intermediate_size: int = 768
    routed_scaling_factor: float = 2.448
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    #: ``(lo, hi)``: the routed experts this chip holds (None: all)
    experts_held: Optional[Tuple[int, int]] = None
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    max_seq_len: int = 4096

    def __post_init__(self):
        if self.q_lora_rank is not None:
            raise ValueError("q_lora_rank: only the form without a query "
                             "compression (None) is built")
        if self.rope_scaling is not None:
            raise ValueError("rope_scaling: only None is built (it would "
                             "change the rotary angles and the softmax "
                             "scale)")
        if (self.n_group, self.topk_group) != (1, 1):
            raise ValueError("n_group / topk_group: only 1 / 1 (no group "
                             "limit on the router's choice) is built")
        if self.moe_layer_freq != 1:
            raise ValueError("moe_layer_freq: only 1 is built")
        if self.experts_held is None:
            self.experts_held = (0, self.n_routed_experts)
        self.experts_held = tuple(self.experts_held)

    # what the engine and the rest of the zoo call these
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    def sparse(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace


def deepseek_v3_tiny(**kw) -> DeepseekV3Config:
    """Three layers, dense then two sparse; heads of 16 + 8 over a latent
    row of 32 + 8."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_hidden_layers", 3)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("kv_lora_rank", 32)
    kw.setdefault("qk_nope_head_dim", 16)
    kw.setdefault("qk_rope_head_dim", 8)
    kw.setdefault("v_head_dim", 16)
    kw.setdefault("intermediate_size", 96)
    kw.setdefault("n_routed_experts", 16)
    kw.setdefault("num_experts_per_tok", 4)
    kw.setdefault("moe_intermediate_size", 48)
    kw.setdefault("max_seq_len", 128)
    return DeepseekV3Config(**kw)


def _linear(in_f, out_f, std):
    return nn.Linear(in_f, out_f, bias_attr=False,
                     weight_attr=ParamAttr(initializer=Normal(0.0, std)))


class DeepseekV3MLP(nn.Layer):
    """``down(silu(gate u) * up u)``."""

    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        std = cfg.initializer_range
        self.gate_proj = _linear(cfg.hidden_size, cfg.intermediate_size, std)
        self.up_proj = _linear(cfg.hidden_size, cfg.intermediate_size, std)
        self.down_proj = _linear(cfg.intermediate_size, cfg.hidden_size, std)

    def forward(self, u):
        return self.down_proj(F.swiglu(self.gate_proj(u), self.up_proj(u)))


class DeepseekV3Block(nn.Layer):
    def __init__(self, cfg: DeepseekV3Config, layer: int):
        super().__init__()
        self.sparse = cfg.sparse(layer)
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size,
                                          epsilon=cfg.rms_norm_eps)
        self.self_attn = nn.MultiHeadLatentAttention(
            cfg.hidden_size, cfg.num_attention_heads, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            rope_theta=cfg.rope_theta, rope_interleave=cfg.rope_interleave,
            rms_norm_eps=cfg.rms_norm_eps, init_std=cfg.initializer_range)
        self.post_attention_layernorm = nn.RMSNorm(
            cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        if self.sparse:
            self.mlp = nn.SwiGLUMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.moe_intermediate_size * cfg.n_shared_experts,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                experts_held=cfg.experts_held,
                routed_scale=cfg.routed_scaling_factor,
                norm_topk=cfg.norm_topk_prob,
                init_std=cfg.initializer_range)
        else:
            self.mlp = DeepseekV3MLP(cfg)

    @property
    def mlp_scope(self) -> str:
        return "moe" if self.sparse else "mlp"

    def forward(self, x):
        with jax.named_scope("attn.mla"):
            x = x + self.self_attn(self.input_layernorm(x))
        with jax.named_scope(self.mlp_scope):
            return x + self.mlp(self.post_attention_layernorm(x))


class DeepseekV3Model(nn.Layer):
    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=ParamAttr(
                initializer=Normal(0.0, cfg.initializer_range)))
        self.layers = nn.LayerList(
            [DeepseekV3Block(cfg, i) for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
        for blk in self.layers:
            x = blk(x)
        return self.norm(x)


class DeepseekV3ForCausalLM(nn.Layer):
    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.cfg = cfg
        self.model = DeepseekV3Model(cfg)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_size,
                               cfg.initializer_range)

    def forward(self, input_ids):
        h = self.model(input_ids)
        with jax.named_scope("lm_head"):
            return self.lm_head(h)

    def paged_adapter(self):
        """What ``inference.PagedEngine`` serves this model through."""
        return _DeepseekV3Paged(self)


class _DeepseekV3Paged:
    """``PagedEngine`` adapter: per layer the cache states the engine
    carries, and the per-chunk forward over them."""

    def __init__(self, model: DeepseekV3ForCausalLM):
        self.model = model
        self.cfg = cfg = model.cfg
        # the one K/V head of the absorbed form, a cached row wide
        self.num_kv_heads = 1
        self.head_dim = cfg.kv_lora_rank + cfg.qk_rope_head_dim

    def cache_layout(self, dtype):
        """Latent pages in every layer, ``("latent_kv", kv_lora_rank +
        qk_rope_head_dim)``, and beside them the expert-load counter of a
        sparse layer."""
        cfg = self.cfg
        held = cfg.experts_held[1] - cfg.experts_held[0]
        pages = ("latent_kv", self.head_dim)
        counter = ("accumulator", (held + 2,), jnp.int32)
        return [(pages, counter) if cfg.sparse(li) else pages
                for li in range(cfg.num_hidden_layers)]

    def forward_chunk(self, tokens, cache, logits_t: int = 1):
        model = self.model
        valid = Tensor(cache.valid)
        with jax.named_scope("embed"):
            x = model.model.embed_tokens(Tensor(tokens))
        for li, blk in enumerate(model.model.layers):
            with jax.named_scope("attn.mla"):
                x = x + blk.self_attn.attend_cached(
                    blk.input_layernorm(x), cache, li)
            with jax.named_scope(blk.mlp_scope):
                u = blk.post_attention_layernorm(x)
                if blk.sparse:
                    out, load = blk.mlp(u, valid=valid, with_load=True)
                    cache.accumulate(li, load._data)
                else:
                    out = blk.mlp(u)
                x = x + out
        x = model.model.norm(x)
        last = cache.head_rows(x, logits_t)
        with jax.named_scope("lm_head"):
            return model.lm_head(last)
