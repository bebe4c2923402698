"""LFM2-MoE decoder (``model_type`` ``lfm2_moe``; LiquidAI's LFM2-8B-A1B is
the published instance), on the TRAINING path.

A pre-norm residual block a layer, ``h = h + Operator_l(RMSNorm(h))``, ``h =
h + FFN_l(RMSNorm(h))``; after the last block a final RMSNorm and a head
tied to the embedding.

* ``Operator_l``: ``layer_types[l]`` says which. ``conv`` is the gated short
  convolution: ``[B | C | z] = x W_in``, a depthwise causal filter of
  ``conv_L_cache`` taps over ``B * z`` (no bias, zeros left of the first
  token), ``(C * conv) W_out``. ``full_attention`` is grouped-query
  attention, q and k RMS-normed over each head's width, rotate-half rotary
  embedding over the whole head, causal; through the flash kernels, K and V
  repeated to the query heads before the call (the kernels take one K/V head
  a query head; the repeat is 2 x 67 MB at 2 x 8192 tokens, and autodiff
  sums the group's gradients back).
* ``FFN_l``: the first ``num_dense_layers`` layers are a SwiGLU of
  ``intermediate_size``; the rest are ``nn.SwiGLUMoE`` with no shared
  expert: a float32 sigmoid router over ``num_experts``, an expert bias that
  only steers the choice (a buffer: no gradient, no update),
  ``num_experts_per_tok`` SwiGLU experts of ``moe_intermediate_size``
  weighted by their scores renormalised with ``+ 1e-6``. ``experts_held``
  says which experts live here; the layer computes their part of the sum.

``forward(ids, labels=None)`` returns logits, or ``(None, loss)``: the
next-token cross entropy with the head's product inside the loss, a block of
rows at a time (at 2 x 8192 tokens the float32 logits alone are 1 GB and
their gradient another). Under ``FLAGS_enable_metrics`` a compiled train
step carries the expert-load counters this model declares
(``step_counters``). No cache and no ``paged_adapter`` here: the model is
trained, not served.
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
from .llama import rotary_embedding

__all__ = ["Lfm2MoeConfig", "Lfm2MoeForCausalLM", "Lfm2MoeModel",
           "lfm2_moe_tiny"]

CONV, FULL = "conv", "full_attention"


@dataclass
class Lfm2MoeConfig:
    """The published keys under their published names."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 24
    #: per layer ``conv`` or ``full_attention`` (None: the published
    #: pattern of LFM2-8B-A1B over ``num_hidden_layers``)
    layer_types: Optional[Tuple[str, ...]] = None
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 128000
    # feed-forward
    intermediate_size: int = 7168
    num_dense_layers: int = 2
    num_experts: int = 32
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1792
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    #: ``(lo, hi)``: the routed experts this chip holds (None: all)
    experts_held: Optional[Tuple[int, int]] = None
    norm_eps: float = 1e-5
    initializer_range: float = 0.02
    #: activation-checkpoint every block
    recompute: bool = False

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            published = (2, 6, 10, 14, 18, 21)
            self.layer_types = tuple(FULL if i in published else CONV
                                     for i in range(n))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != n:
            raise ValueError("layer_types names one kind a layer of "
                             "num_hidden_layers")
        bad = set(self.layer_types) - {CONV, FULL}
        if bad:
            raise ValueError(f"unknown layer kinds {sorted(bad)}")
        if self.conv_bias:
            raise ValueError("conv_bias is false in every published "
                             "lfm2_moe config; no biased form is built")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        self.experts_held = tuple(self.experts_held)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def sparse_layers(self) -> int:
        return max(0, self.num_hidden_layers - self.num_dense_layers)


def lfm2_moe_tiny(**kw) -> Lfm2MoeConfig:
    """Six layers as the benchmark's cut has them (``conv conv | full conv
    conv conv``: two dense, four with experts), 16 experts of which 4 are
    chosen."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_hidden_layers", 6)
    kw.setdefault("layer_types", (CONV, CONV, FULL, CONV, CONV, CONV))
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("intermediate_size", 96)
    kw.setdefault("num_experts", 16)
    kw.setdefault("num_experts_per_tok", 4)
    kw.setdefault("moe_intermediate_size", 48)
    return Lfm2MoeConfig(**kw)


def _linear(in_f, out_f, std):
    return nn.Linear(in_f, out_f, bias_attr=False,
                     weight_attr=ParamAttr(initializer=Normal(0.0, std)))


def _residual_std(cfg: Lfm2MoeConfig) -> float:
    """Projections that write into the residual stream, scaled by depth."""
    return cfg.initializer_range / math.sqrt(2 * cfg.num_hidden_layers)


class Lfm2MoeShortConv(nn.Layer):
    """``(C * conv(B * z)) W_out`` with ``[B | C | z] = x W_in``."""

    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        h, std = cfg.hidden_size, cfg.initializer_range
        self.in_proj = _linear(h, 3 * h, std)
        self.conv_weight = self.create_parameter(
            [h, cfg.conv_L_cache],
            attr=ParamAttr(initializer=Normal(0.0, std)))
        self.out_proj = _linear(h, h, _residual_std(cfg))

    def forward(self, x):
        return self.out_proj(
            F.gated_short_conv(self.in_proj(x), self.conv_weight))


class Lfm2MoeAttention(nn.Layer):
    """Causal grouped-query attention, scale ``head_dim ** -0.5``, no bias,
    q and k RMS-normed over ``head_dim`` and rotated."""

    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        self.cfg = cfg
        h, hd, std = cfg.hidden_size, cfg.head_dim, cfg.initializer_range
        nq, nkv = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
        self.q_proj = _linear(h, nq, std)
        self.k_proj = _linear(h, nkv, std)
        self.v_proj = _linear(h, nkv, std)
        self.out_proj = _linear(nq, h, _residual_std(cfg))
        self.q_layernorm = nn.RMSNorm(hd, epsilon=cfg.norm_eps)
        self.k_layernorm = nn.RMSNorm(hd, epsilon=cfg.norm_eps)

    def forward(self, x):
        cfg = self.cfg
        b, t = x.shape[0], x.shape[1]
        nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        q = self.q_layernorm(ops.reshape(self.q_proj(x), [b, t, nh, hd]))
        k = self.k_layernorm(ops.reshape(self.k_proj(x), [b, t, nkv, hd]))
        v = ops.reshape(self.v_proj(x), [b, t, nkv, hd])
        q = rotary_embedding(q, cfg.rope_theta)
        k = rotary_embedding(k, cfg.rope_theta)
        rep = nh // nkv
        if rep > 1:
            k = ops.reshape(ops.tile(ops.unsqueeze(k, 3), [1, 1, 1, rep, 1]),
                            [b, t, nh, hd])
            v = ops.reshape(ops.tile(ops.unsqueeze(v, 3), [1, 1, 1, rep, 1]),
                            [b, t, nh, hd])
        out, _ = F.flash_attention(q, k, v, causal=True)
        return self.out_proj(ops.reshape(out, [b, t, nh * hd]))


class Lfm2MoeMLP(nn.Layer):
    """``w2(silu(w1 u) * w3 u)``."""

    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        h, wide, std = (cfg.hidden_size, cfg.intermediate_size,
                        cfg.initializer_range)
        self.w1 = _linear(h, wide, std)
        self.w3 = _linear(h, wide, std)
        self.w2 = _linear(wide, h, _residual_std(cfg))

    def forward(self, u):
        return self.w2(F.swiglu(self.w1(u), self.w3(u)))


class Lfm2MoeBlock(nn.Layer):
    def __init__(self, cfg: Lfm2MoeConfig, index: int):
        super().__init__()
        self.kind = cfg.layer_types[index]
        self.sparse = index >= cfg.num_dense_layers
        self.operator_norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.norm_eps)
        if self.kind == CONV:
            self.conv = Lfm2MoeShortConv(cfg)
        else:
            self.self_attn = Lfm2MoeAttention(cfg)
        self.ffn_norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.norm_eps)
        if self.sparse:
            self.feed_forward = nn.SwiGLUMoE(
                cfg.hidden_size, cfg.moe_intermediate_size, 0,
                cfg.num_experts, cfg.num_experts_per_tok,
                experts_held=cfg.experts_held,
                routed_scale=cfg.routed_scaling_factor,
                norm_topk=cfg.norm_topk_prob,
                init_std=cfg.initializer_range, norm_eps=1e-6)
            # what writes into the residual stream starts scaled by depth
            down = self.feed_forward.w_down
            self.feed_forward.w_down = self.feed_forward.create_parameter(
                list(down.shape), attr=ParamAttr(
                    initializer=Normal(0.0, _residual_std(cfg))))
            # the published bias is a buffer moved by a load rule outside
            # the gradient: here nothing moves it
            self.feed_forward.e_score_correction_bias.stop_gradient = True
        else:
            self.feed_forward = Lfm2MoeMLP(cfg)

    def forward(self, h, with_load: bool = False):
        """The block's output; with ``with_load`` (a sparse block's) also
        the layer's load vector."""
        if self.kind == CONV:
            with jax.named_scope("short_conv"):
                h = h + self.conv(self.operator_norm(h))
        else:
            with jax.named_scope("attn"):
                h = h + self.self_attn(self.operator_norm(h))
        if not self.sparse:
            with jax.named_scope("mlp"):
                return h + self.feed_forward(self.ffn_norm(h))
        with jax.named_scope("moe"):
            u = self.ffn_norm(h)
            if not with_load:
                return h + self.feed_forward(u)
            out, load = self.feed_forward(u, with_load=True)
            return h + out, load


def export_expert_load(fresh, first_held: int, walk=None):
    """An epoch's load of a trained model's routed layers, (layers, E_held +
    2) int64 on the host (``functional.experts.load_arrays`` a layer), to
    the expert-load metrics; with ``walk``, a step's ``(stride, pairs)``
    (``functional.experts.pair_walk``), also how many strides of the pair
    buffer the grouped product walked for those pairs."""
    from ..distributed.fleet import moe as _moe
    tokens = fresh[:, :-2]
    worst = [float(t.max() / t.mean()) for t in tokens if t.sum() > 0]
    _moe.stamp_expert_load(
        tokens.sum(axis=0), first_held, fresh[:, -2].sum(),
        fresh[:, -1].sum(), max(worst, default=None))
    if walk is not None:
        _moe.stamp_pair_strides(fresh[:, -2], fresh[:, -1], *walk)


class Lfm2MoeModel(nn.Layer):
    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=ParamAttr(
                initializer=Normal(0.0, cfg.initializer_range)))
        self.layers = nn.LayerList(
            [Lfm2MoeBlock(cfg, i) for i in range(cfg.num_hidden_layers)])
        self.embedding_norm = nn.RMSNorm(cfg.hidden_size,
                                         epsilon=cfg.norm_eps)
        #: ``(stride, pairs)`` of a routed layer's grouped product in the
        #: counting step as it was traced (None: the masked form)
        self._walk = None

    def step_counters(self) -> dict:
        """``observability.trace.STEP_COUNTERS`` this model feeds while a
        compiled train step counts."""
        cfg = self.cfg
        if not cfg.sparse_layers:
            return {}
        lo, hi = cfg.experts_held
        return {"moe.expert_load": _trace.StepCounter(
            (cfg.sparse_layers, hi - lo + 2), jnp.int32, self._export_load)}

    def _export_load(self, fresh):
        export_expert_load(fresh, self.cfg.experts_held[0], self._walk)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
        counting = _trace.counting_step() and self.cfg.sparse_layers > 0
        if counting:
            lo, hi = self.cfg.experts_held
            self._walk = _experts.pair_walk(
                math.prod(input_ids.shape), self.cfg.num_experts_per_tok,
                hi - lo, self.cfg.num_experts)
        loads = []
        for blk in self.layers:
            if counting and blk.sparse:
                x, load = self._run(blk, x, True)
                loads.append(load._data)
            else:
                x = self._run(blk, x, False)
        if loads:
            _trace.count_in_step("moe.expert_load", jnp.stack(loads))
        return self.embedding_norm(x)

    def _run(self, blk, x, with_load):
        fn = (lambda h: blk(h, True)) if with_load else blk
        return remat_block(fn, x) if self.cfg.recompute else fn(x)


class Lfm2MoeForCausalLM(nn.Layer):
    """The head is the embedding, transposed; the loss is next-token cross
    entropy with the head's product inside it."""

    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        self.cfg = cfg
        self.model = Lfm2MoeModel(cfg)

    def forward(self, input_ids, labels=None):
        h = self.model(input_ids)
        table = self.model.embed_tokens.weight
        if labels is None:
            with jax.named_scope("lm_head"):
                return ops.matmul(h, table, transpose_y=True)
        return None, next_token_loss(h, table, labels, transpose_y=True)

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in self.parameters())
