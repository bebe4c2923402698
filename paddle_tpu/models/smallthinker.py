"""SmallThinker decoder (``model_name`` ``smallthinker_*``; PowerInfer's
SmallThinker-21BA3B-Instruct is the published instance), on the TRAINING
path.

A pre-norm residual block a layer whose router reads the ATTENTION's input:

    x = RMSNorm_in(h)
    (idx, p) = route(x)          the 6 largest of x W_r, softmax over them
    a = h + Attn_l(x) W_o
    u = RMSNorm_post(a)
    h' = a + sum_{e in idx} p_e D_e (relu(G_e u) * U_e u)

after the last block a final RMSNorm and a head of its own (untied).

* ``Attn_l``: grouped-query attention, no bias, no q/k norm, scale
  ``head_dim ** -0.5``. ``rope_layout[l] = 1``: rotate-half rotary embedding
  on q and k; ``0``: no positional encoding. ``sliding_window_layout[l] =
  1``: query ``i`` sees key ``j`` iff ``0 <= i - j < sliding_window_size``;
  ``0``: causal over all keys. Through the flash kernels
  (``F.flash_attention(..., causal=True, window=...)``), K and V repeated to
  the query heads before the call (the kernels take one K/V head a query
  head).
* the expert layer is ``nn.SwiGLUMoE`` with ReGLU experts
  (``activation="relu"``), the softmax-over-the-chosen route and the routing
  handed in; no shared expert, no dense layer. ``experts_held`` says which
  experts live here; the layer computes their part of the sum.

``forward(ids, labels=None)`` returns logits, or ``(None, loss)``: the
next-token cross entropy with the head's product inside the loss. Under
``FLAGS_enable_metrics`` a compiled train step carries the expert-load
counters this model declares (``step_counters``). No cache and no
``paged_adapter`` here: the model is trained, not served (serving it needs a
``window_kv`` ring of ``sliding_window_size`` rows a lane: ROADMAP R3).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn, ops
from ..nn import functional as F
from ..nn.functional import experts as _experts
from ..nn.initializer import Normal
from ..nn.parameter import ParamAttr
from ..observability import trace as _trace
from ._head import next_token_loss
from ._remat import remat_block
from .lfm2_moe import _linear, export_expert_load
from .llama import rotary_embedding

__all__ = ["SmallThinkerConfig", "SmallThinkerForCausalLM",
           "SmallThinkerModel", "smallthinker_tiny"]


@dataclass
class SmallThinkerConfig:
    """The published keys under their published names."""
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    #: per layer 1 (rotary embedding) or 0 (none) / 1 (window) or 0 (full);
    #: None: the published pattern, layer 0 of every four full and NoPE
    rope_layout: Optional[Tuple[int, ...]] = None
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    sliding_window_size: int = 4096
    rope_theta: float = 1500000.0
    max_position_embeddings: int = 16384
    # experts: the router is ``moe_num_primary_experts`` wide
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_ffn_hidden_size: int = 768
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    #: ``(lo, hi)``: the routed experts this chip holds (None: all)
    experts_held: Optional[Tuple[int, int]] = None
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    #: activation-checkpoint every block
    recompute: bool = False

    def __post_init__(self):
        n = self.num_hidden_layers
        published = tuple(int(i % 4 != 0) for i in range(n))
        for key in ("rope_layout", "sliding_window_layout"):
            layout = tuple(getattr(self, key) or published)
            if len(layout) != n or set(layout) - {0, 1}:
                raise ValueError(f"{key} is one 0 or 1 a layer of "
                                 "num_hidden_layers")
            setattr(self, key, layout)
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if not self.moe_primary_router_apply_softmax:
            raise ValueError("every published smallthinker config routes by "
                             "a softmax over the chosen logits; no other "
                             "route is built")
        if self.tie_word_embeddings:
            raise ValueError("every published smallthinker config has a head "
                             "of its own; no tied form is built")
        if self.experts_held is None:
            self.experts_held = (0, self.moe_num_primary_experts)
        self.experts_held = tuple(self.experts_held)


def smallthinker_tiny(**kw) -> SmallThinkerConfig:
    """Four layers, one period of the published pattern (full + NoPE, then
    three window + rotary), 16 experts of which 4 are chosen."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_hidden_layers", 4)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("sliding_window_size", 8)
    kw.setdefault("max_position_embeddings", 64)
    kw.setdefault("moe_num_primary_experts", 16)
    kw.setdefault("moe_num_active_primary_experts", 4)
    kw.setdefault("moe_ffn_hidden_size", 48)
    return SmallThinkerConfig(**kw)


def _residual_std(cfg: SmallThinkerConfig) -> float:
    """Projections that write into the residual stream, scaled by depth."""
    return cfg.initializer_range / math.sqrt(2 * cfg.num_hidden_layers)


class SmallThinkerAttention(nn.Layer):
    """Causal grouped-query attention, scale ``head_dim ** -0.5``, no bias,
    rotated or not and windowed or not as the layer's two flags say."""

    def __init__(self, cfg: SmallThinkerConfig, index: int):
        super().__init__()
        self.cfg = cfg
        self.rotary = bool(cfg.rope_layout[index])
        self.window = (cfg.sliding_window_size
                       if cfg.sliding_window_layout[index] else None)
        h, hd, std = cfg.hidden_size, cfg.head_dim, cfg.initializer_range
        nq, nkv = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
        self.q_proj = _linear(h, nq, std)
        self.k_proj = _linear(h, nkv, std)
        self.v_proj = _linear(h, nkv, std)
        self.o_proj = _linear(nq, h, _residual_std(cfg))

    def forward(self, x):
        cfg = self.cfg
        b, t = x.shape[0], x.shape[1]
        nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        q = ops.reshape(self.q_proj(x), [b, t, nh, hd])
        k = ops.reshape(self.k_proj(x), [b, t, nkv, hd])
        v = ops.reshape(self.v_proj(x), [b, t, nkv, hd])
        if self.rotary:
            q = rotary_embedding(q, cfg.rope_theta)
            k = rotary_embedding(k, cfg.rope_theta)
        rep = nh // nkv
        if rep > 1:
            k = ops.reshape(ops.tile(ops.unsqueeze(k, 3), [1, 1, 1, rep, 1]),
                            [b, t, nh, hd])
            v = ops.reshape(ops.tile(ops.unsqueeze(v, 3), [1, 1, 1, rep, 1]),
                            [b, t, nh, hd])
        out, _ = F.flash_attention(q, k, v, causal=True, window=self.window)
        return self.o_proj(ops.reshape(out, [b, t, nh * hd]))


class SmallThinkerBlock(nn.Layer):
    def __init__(self, cfg: SmallThinkerConfig, index: int):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size,
                                          epsilon=cfg.rms_norm_eps)
        self.self_attn = SmallThinkerAttention(cfg, index)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   epsilon=cfg.rms_norm_eps)
        self.block_sparse_moe = nn.SwiGLUMoE(
            cfg.hidden_size, cfg.moe_ffn_hidden_size, 0,
            cfg.moe_num_primary_experts, cfg.moe_num_active_primary_experts,
            experts_held=cfg.experts_held, init_std=cfg.initializer_range,
            activation="relu", route="softmax")
        # what writes into the residual stream starts scaled by depth
        moe = self.block_sparse_moe
        moe.w_down = moe.create_parameter(
            list(moe.w_down.shape),
            attr=ParamAttr(initializer=Normal(0.0, _residual_std(cfg))))

    @property
    def attn_scope(self) -> str:
        return "attn.window" if self.self_attn.window else "attn.full"

    def forward(self, h, with_load: bool = False):
        """The block's output; with ``with_load`` also the layer's load
        vector."""
        moe = self.block_sparse_moe
        x = self.input_layernorm(h)
        with jax.named_scope("moe"):        # the router reads x, not u
            routing = moe.route(x)
        with jax.named_scope(self.attn_scope):
            a = h + self.self_attn(x)
        with jax.named_scope("moe"):
            u = self.post_attention_layernorm(a)
            if not with_load:
                return a + moe(u, routing=routing)
            out, load = moe(u, routing=routing, with_load=True)
            return a + out, load


class SmallThinkerModel(nn.Layer):
    def __init__(self, cfg: SmallThinkerConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=ParamAttr(
                initializer=Normal(0.0, cfg.initializer_range)))
        self.layers = nn.LayerList(
            [SmallThinkerBlock(cfg, i) for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        #: ``(stride, pairs)`` of a routed layer's grouped product in the
        #: counting step as it was traced (None: the masked form)
        self._walk = None

    def step_counters(self) -> dict:
        """``observability.trace.STEP_COUNTERS`` this model feeds while a
        compiled train step counts."""
        cfg = self.cfg
        lo, hi = cfg.experts_held
        return {"moe.expert_load": _trace.StepCounter(
            (cfg.num_hidden_layers, hi - lo + 2), jnp.int32,
            self._export_load)}

    def _export_load(self, fresh):
        export_expert_load(fresh, self.cfg.experts_held[0], self._walk)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
        counting = _trace.counting_step()
        if counting:
            lo, hi = self.cfg.experts_held
            self._walk = _experts.pair_walk(
                math.prod(input_ids.shape),
                self.cfg.moe_num_active_primary_experts, hi - lo,
                self.cfg.moe_num_primary_experts)
        loads = []
        for blk in self.layers:
            if counting:
                x, load = self._run(blk, x, True)
                loads.append(load._data)
            else:
                x = self._run(blk, x, False)
        if loads:
            _trace.count_in_step("moe.expert_load", jnp.stack(loads))
        return self.norm(x)

    def _run(self, blk, x, with_load):
        fn = (lambda h: blk(h, True)) if with_load else blk
        return remat_block(fn, x) if self.cfg.recompute else fn(x)


class SmallThinkerForCausalLM(nn.Layer):
    """The head is a matrix of its own; the loss is next-token cross entropy
    with the head's product inside it."""

    def __init__(self, cfg: SmallThinkerConfig):
        super().__init__()
        self.cfg = cfg
        self.model = SmallThinkerModel(cfg)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_size,
                               cfg.initializer_range)

    def forward(self, input_ids, labels=None):
        h = self.model(input_ids)
        if labels is None:
            with jax.named_scope("lm_head"):
                return self.lm_head(h)
        return None, next_token_loss(h, self.lm_head.weight, labels,
                                     transpose_y=False)

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in self.parameters())
