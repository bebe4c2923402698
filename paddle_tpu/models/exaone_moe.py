"""K-EXAONE decoder (``model_type`` ``exaone_moe``; LG AI Research's
K-EXAONE-236B-A23B is the published instance).

A pre-norm residual block a layer, ``a = x + Attn_l(RMSNorm(x))``, ``y = a
+ FFN_l(RMSNorm(a))``; after the last block a final RMSNorm and an untied
head.

* ``Attn_l``: grouped-query attention, ``q`` and ``k`` RMS-normed per head,
  no bias. ``layer_types[l]`` says which kind: ``sliding_attention`` (three
  of every four, ``LLLG``) applies rotary embedding (rotate-half) to q and k
  and sees key j from query i iff ``0 <= i - j < sliding_window``;
  ``full_attention`` is causal and applies NO positional encoding (EXAONE
  4.0's hybrid attention).
* ``FFN_l``: ``mlp_layer_types[l]`` ``dense`` (the first
  ``first_k_dense_replace`` layers) is a SwiGLU of ``intermediate_size``;
  ``sparse`` is ``nn.SwiGLUMoE``: a float32 sigmoid router over
  ``num_experts``, ``num_experts_per_tok`` SwiGLU experts of
  ``moe_intermediate_size`` weighted by their renormalised scores times
  ``routed_scaling_factor``, plus ``num_shared_experts`` shared ones.

The multi-token-prediction module of the published checkpoint
(``num_nextn_predict_layers``) is a draft head beside the served logits
and is not built here.

``forward(ids)`` runs a whole sequence (tests, trainers);
``paged_adapter()`` is what ``inference.PagedEngine`` serves the model
through: a full layer pages its K/V, a window layer holds
``sliding_window`` plus one chunk of rows a slot whatever the context, and
a sparse layer keeps an expert-load counter beside its attention state.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn, ops
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.parameter import ParamAttr
from .llama import rotary_embedding

__all__ = ["ExaoneMoeConfig", "ExaoneMoeForCausalLM", "ExaoneMoeModel",
           "exaone_moe_tiny"]

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass
class ExaoneMoeConfig:
    """The published keys under their published names."""
    vocab_size: int = 153600
    hidden_size: int = 6144
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    #: per layer ``sliding_attention`` or ``full_attention`` (None: the
    #: published ``LLLG`` pattern over ``num_hidden_layers``)
    layer_types: Optional[Tuple[str, ...]] = None
    sliding_window: int = 128
    rope_parameters: dict = field(
        default_factory=lambda: {"rope_theta": 1000000.0,
                                 "rope_type": "default"})
    # feed-forward
    intermediate_size: int = 18432
    first_k_dense_replace: int = 1
    #: per layer ``dense`` or ``sparse`` (None: ``first_k_dense_replace``
    #: dense layers, then sparse)
    mlp_layer_types: Optional[Tuple[str, ...]] = None
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    moe_intermediate_size: int = 2048
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    #: ``(lo, hi)``: the routed experts this chip holds (None: all)
    experts_held: Optional[Tuple[int, int]] = None
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    max_seq_len: int = 4096

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = tuple(FULL if (i + 1) % 4 == 0 else SLIDING
                                     for i in range(n))
        if self.mlp_layer_types is None:
            self.mlp_layer_types = tuple(
                "dense" if i < self.first_k_dense_replace else "sparse"
                for i in range(n))
        self.layer_types = tuple(self.layer_types)
        self.mlp_layer_types = tuple(self.mlp_layer_types)
        if len(self.layer_types) != n or len(self.mlp_layer_types) != n:
            raise ValueError("layer_types and mlp_layer_types name one kind "
                             "a layer of num_hidden_layers")
        bad = (set(self.layer_types) - {SLIDING, FULL}) | (
            set(self.mlp_layer_types) - {"dense", "sparse"})
        if bad:
            raise ValueError(f"unknown layer kinds {sorted(bad)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        self.experts_held = tuple(self.experts_held)

    # what the engine and the rest of the zoo call these
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def rope_theta(self) -> float:
        return float(self.rope_parameters["rope_theta"])


def exaone_moe_tiny(**kw) -> ExaoneMoeConfig:
    """Five layers, dense then ``S S F S`` as the benchmark's cut has them,
    a window of 8."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_hidden_layers", 5)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("sliding_window", 8)
    kw.setdefault("intermediate_size", 96)
    kw.setdefault("num_experts", 16)
    kw.setdefault("num_experts_per_tok", 4)
    kw.setdefault("moe_intermediate_size", 48)
    kw.setdefault("max_seq_len", 128)
    return ExaoneMoeConfig(**kw)


def _linear(in_f, out_f, std):
    return nn.Linear(in_f, out_f, bias_attr=False,
                     weight_attr=ParamAttr(initializer=Normal(0.0, std)))


class ExaoneMoeAttention(nn.Layer):
    """Grouped-query attention, softmax scale ``head_dim ** -0.5``, no bias,
    q and k RMS-normed over ``head_dim``; a window layer rotates q and k and
    masks to its window, a full layer is causal with no positions."""

    def __init__(self, cfg: ExaoneMoeConfig, kind: str):
        super().__init__()
        self.cfg, self.kind = cfg, kind
        std = cfg.initializer_range
        nq = cfg.num_attention_heads * cfg.head_dim
        nkv = cfg.num_key_value_heads * cfg.head_dim
        self.q_proj = _linear(cfg.hidden_size, nq, std)
        self.k_proj = _linear(cfg.hidden_size, nkv, std)
        self.v_proj = _linear(cfg.hidden_size, nkv, std)
        self.o_proj = _linear(nq, cfg.hidden_size, std)
        self.q_norm = nn.RMSNorm(cfg.head_dim, epsilon=cfg.rms_norm_eps)
        self.k_norm = nn.RMSNorm(cfg.head_dim, epsilon=cfg.rms_norm_eps)

    def qkv(self, u, start=0):
        """``q, k`` normed (and rotated from position ``start``: an int,
        (B,) per-sequence offsets, or the rows' (B, T) positions) and
        ``v``, each (B, T, heads, D)."""
        cfg = self.cfg
        b, t = u.shape[0], u.shape[1]
        nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        q = self.q_norm(ops.reshape(self.q_proj(u), [b, t, nh, hd]))
        k = self.k_norm(ops.reshape(self.k_proj(u), [b, t, nkv, hd]))
        v = ops.reshape(self.v_proj(u), [b, t, nkv, hd])
        if self.kind == SLIDING:
            q = rotary_embedding(q, cfg.rope_theta, pos_offset=start)
            k = rotary_embedding(k, cfg.rope_theta, pos_offset=start)
        return q, k, v

    def forward(self, u):
        cfg = self.cfg
        b, t = u.shape[0], u.shape[1]
        nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        q, k, v = self.qkv(u)
        rep = nh // nkv
        if rep > 1:
            k = ops.reshape(ops.tile(ops.unsqueeze(k, 3), [1, 1, 1, rep, 1]),
                            [b, t, nh, hd])
            v = ops.reshape(ops.tile(ops.unsqueeze(v, 3), [1, 1, 1, rep, 1]),
                            [b, t, nh, hd])
        if self.kind == FULL:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        else:
            back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
            seen = (back >= 0) & (back < cfg.sliding_window)
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=Tensor(seen[None, None]))
        return self.o_proj(ops.reshape(out, [b, t, nh * hd]))


class ExaoneMoeMLP(nn.Layer):
    """``down(silu(gate u) * up u)``."""

    def __init__(self, cfg: ExaoneMoeConfig):
        super().__init__()
        std = cfg.initializer_range
        self.gate_proj = _linear(cfg.hidden_size, cfg.intermediate_size, std)
        self.up_proj = _linear(cfg.hidden_size, cfg.intermediate_size, std)
        self.down_proj = _linear(cfg.intermediate_size, cfg.hidden_size, std)

    def forward(self, u):
        return self.down_proj(F.swiglu(self.gate_proj(u), self.up_proj(u)))


_ATTN_SCOPES = {SLIDING: "attn.window", FULL: "attn.full"}


class ExaoneMoeBlock(nn.Layer):
    def __init__(self, cfg: ExaoneMoeConfig, kind: str, mlp_kind: str):
        super().__init__()
        self.kind, self.sparse = kind, mlp_kind == "sparse"
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size,
                                          epsilon=cfg.rms_norm_eps)
        self.self_attn = ExaoneMoeAttention(cfg, kind)
        self.post_attention_layernorm = nn.RMSNorm(
            cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        if self.sparse:
            self.mlp = nn.SwiGLUMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.moe_intermediate_size * cfg.num_shared_experts,
                cfg.num_experts, cfg.num_experts_per_tok,
                experts_held=cfg.experts_held,
                routed_scale=cfg.routed_scaling_factor,
                norm_topk=cfg.norm_topk_prob,
                init_std=cfg.initializer_range)
        else:
            self.mlp = ExaoneMoeMLP(cfg)

    @property
    def attn_scope(self) -> str:
        return _ATTN_SCOPES[self.kind]

    @property
    def mlp_scope(self) -> str:
        return "moe" if self.sparse else "mlp"

    def forward(self, x):
        with jax.named_scope(self.attn_scope):
            x = x + self.self_attn(self.input_layernorm(x))
        with jax.named_scope(self.mlp_scope):
            return x + self.mlp(self.post_attention_layernorm(x))


class ExaoneMoeModel(nn.Layer):
    def __init__(self, cfg: ExaoneMoeConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=ParamAttr(
                initializer=Normal(0.0, cfg.initializer_range)))
        self.layers = nn.LayerList(
            [ExaoneMoeBlock(cfg, kind, mlp_kind) for kind, mlp_kind
             in zip(cfg.layer_types, cfg.mlp_layer_types)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
        for blk in self.layers:
            x = blk(x)
        return self.norm(x)


class ExaoneMoeForCausalLM(nn.Layer):
    def __init__(self, cfg: ExaoneMoeConfig):
        super().__init__()
        self.cfg = cfg
        self.model = ExaoneMoeModel(cfg)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_size,
                               cfg.initializer_range)

    def forward(self, input_ids):
        h = self.model(input_ids)
        with jax.named_scope("lm_head"):
            return self.lm_head(h)

    def paged_adapter(self):
        """What ``inference.PagedEngine`` serves this model through."""
        return _ExaoneMoePaged(self)


class _ExaoneMoePaged:
    """``PagedEngine`` adapter: per layer the cache states the engine
    carries, and the per-chunk forward over them."""

    def __init__(self, model: ExaoneMoeForCausalLM):
        self.model = model
        self.cfg = cfg = model.cfg
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim

    def cache_layout(self, dtype):
        """A layer's attention state, ``("paged_kv",)`` for a full layer
        and ``("window_kv", sliding_window)`` for a window layer, and beside
        it the expert-load counter of a sparse layer."""
        cfg = self.cfg
        held = cfg.experts_held[1] - cfg.experts_held[0]
        counter = ("accumulator", (held + 2,), jnp.int32)
        out = []
        for kind, mlp_kind in zip(cfg.layer_types, cfg.mlp_layer_types):
            attn = ("paged_kv",) if kind == FULL \
                else ("window_kv", cfg.sliding_window)
            out.append((attn, counter) if mlp_kind == "sparse" else attn)
        return out

    def forward_chunk(self, tokens, cache, logits_t: int = 1):
        model, cfg = self.model, self.cfg
        bsz, t = tokens.shape
        nh, hd = cfg.num_attention_heads, cfg.head_dim
        valid = Tensor(cache.valid)
        with jax.named_scope("embed"):
            x = model.model.embed_tokens(Tensor(tokens))
        for li, blk in enumerate(model.model.layers):
            with jax.named_scope(blk.attn_scope):
                q, k, v = blk.self_attn.qkv(blk.input_layernorm(x),
                                            cache.positions)
                window = cfg.sliding_window if blk.kind == SLIDING else None
                out = cache.attend(li, q, k, v, window=window)
                x = x + blk.self_attn.o_proj(
                    ops.reshape(out, [bsz, t, nh * hd]))
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
